"""What the model calls inside the traced window say of their learned token
selection: one entry a call segment - a ``decode_step``'s decode rows, its
chunk (``chunk_sparse_*``), a ``prefill_chunk`` alone - with the cached
tokens its rows scored (``ctx_scored``), the tokens attention read of them
(``kv_selected``), and the tokens whose index keys / selected keys and
values the segment must READ once (``keys_read``, ``kv_read``: a chunk's
rows share one context, every decode row has its own). Shape facts the
engine puts on its spans (``inference/engine_v2.py`` ``_sparse_args``), ONE
layer's. A program whose spans carry none reports nothing. Not a reader
itself: the three sparse readers share it."""

from benchmark.harness import program_spans as ps


def calls(ctx):
    program = ps.load(ctx)
    if program is None:
        return []
    topk = ctx["cell"].model["sa_config"]["topk"]
    window = ctx["trace"].window()
    out = []
    for s in ps.named(program.spans, "decode_step", window):
        scored = s.arg("sparse_ctx_scored")
        if scored is not None:      # the decode rows: each its own context
            out.append({"ctx_scored": scored,
                        "kv_selected": s.arg("sparse_kv_selected"),
                        "keys_read": scored,
                        "kv_read": s.arg("sparse_kv_selected")})
        if s.arg("chunk_sparse_ctx_scored") is not None:
            out.append(_chunk(s.arg("chunk_sparse_ctx_scored"),
                              s.arg("chunk_sparse_kv_selected"),
                              s.arg("chunk_ctx"), s.arg("chunk_tokens"),
                              topk))
    for s in ps.named(program.spans, "prefill_chunk", window):
        if s.arg("sparse_ctx_scored") is not None:
            out.append(_chunk(s.arg("sparse_ctx_scored"),
                              s.arg("sparse_kv_selected"), s.arg("ctx"),
                              s.arg("tokens"), topk))
    return out


def _chunk(scored, selected, ctx, tokens, topk):
    """A chunk of ``tokens`` rows at context offset ``ctx``: its rows read
    one context of ``ctx + tokens`` index keys; the selected keys and values
    they need are at least one row's ``min(context, topk)``."""
    context = ctx + tokens
    return {"ctx_scored": scored, "kv_selected": selected,
            "keys_read": context, "kv_read": min(context, topk)}
