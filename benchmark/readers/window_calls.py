"""What the model calls inside the traced window say of their two kinds of
KV state: one entry a call segment - a ``decode_step``'s decode rows, its
chunk (``chunk_kv_tokens_*``), a ``prefill_chunk`` alone - with the cached
tokens ONE full layer and ONE window layer read for it (``full``,
``window``) and, for a chunk, its context offset and rows. Shape facts the
engine puts on its spans (``inference/engine_v2.py`` ``_kv_kind_args``). A
program whose spans carry none (a family with one kind of KV state; a
program older than the span arguments) reports nothing. Not a reader itself:
the window readers share it."""

from benchmark.harness import program_spans as ps


def calls(ctx):
    program = ps.load(ctx)
    if program is None:
        return []
    window = ctx["trace"].window()
    out = []
    for s in ps.named(program.spans, "decode_step", window):
        if s.arg("kv_tokens_full"):
            out.append({"kind": "decode", "full": s.arg("kv_tokens_full"),
                        "window": s.arg("kv_tokens_window")})
        if s.arg("chunk_kv_tokens_full") is not None:
            out.append({"kind": "chunk",
                        "full": s.arg("chunk_kv_tokens_full"),
                        "window": s.arg("chunk_kv_tokens_window"),
                        "ctx": int(s.arg("chunk_ctx")),
                        "tokens": int(s.arg("chunk_tokens"))})
    for s in ps.named(program.spans, "prefill_chunk", window):
        if s.arg("kv_tokens_full") is not None:
            out.append({"kind": "chunk", "full": s.arg("kv_tokens_full"),
                        "window": s.arg("kv_tokens_window"),
                        "ctx": int(s.arg("ctx")),
                        "tokens": int(s.arg("tokens"))})
    return out
