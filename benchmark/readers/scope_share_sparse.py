"""``scope_share`` with the learned selection's own scopes known: device
time in operations whose innermost named scope is one of ``scopes``, over
device busy time, in percent. The selection's scopes (``attn_index``: the
index projections, the index keys' write and the scores; ``attn_select``:
the thresholds) lie INSIDE ``attn`` in the program, and
``program_spans.SCOPES`` - fixed, what ``scope_share`` reads by - does not
name them, so that reader books them to ``attn``. A program that names
neither reports nothing."""

from benchmark.harness import program_spans as ps

SPARSE_SCOPES = ("attn_index", "attn_select")


def read(ctx, scopes):
    program = ps.load(ctx)
    if program is None or not program.ops:
        return None
    window = ctx["trace"].window()
    mine = busy = 0.0
    named = False
    for ops in program.ops.values():
        by_scope = ps.scope_seconds(ops, window, ps.SCOPES + SPARSE_SCOPES)
        named = named or any(s in by_scope for s in SPARSE_SCOPES)
        mine += sum(by_scope.get(s, 0.0) for s in scopes)
        busy += sum(by_scope.values())
    return 100.0 * mine / busy if busy and named else None
