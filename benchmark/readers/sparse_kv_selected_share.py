"""How sparse the window was, in percent: the tokens attention read over
the cached tokens its rows could have read (``sparse_kv_selected`` over
``sparse_ctx_scored``, summed over the model calls inside the traced
window: ``sparse_calls``). 100 where every context is under ``topk`` - the
selection then selects everything.

A fact of the traffic's SHAPES, not of what a kernel kept: the engine counts
both from the rows' lengths alone (``min(context, topk)`` a row,
``models/mixtral.py`` ``sparse_rows``), so one traffic reads one number
whatever the seed or the kernel (23.848 in every traced run of PR 38). It
says how much a gather-form attention could leave unread in this window; an
exact selection takes ``min(context, topk)`` tokens a row by definition, and
what the program really took is held per probe by ``reference/keye.py``
``held`` (every row exactly as many tokens as the reference)."""

from benchmark.readers import sparse_calls


def read(ctx):
    calls = sparse_calls.calls(ctx)
    scored = sum(c["ctx_scored"] for c in calls)
    if not scored:
        return None
    return 100.0 * sum(c["kv_selected"] for c in calls) / scored
