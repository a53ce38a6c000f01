"""What the model calls inside the traced window say of their latent (MLA)
cache: one entry a call segment - a ``decode_step``'s decode rows, its chunk
(``chunk_kv_tokens_latent``), a ``prefill_chunk`` alone - with the cached
rows ONE layer reads for it (``tokens``) and, for a chunk, its context
offset and rows. Shape facts the engine puts on its spans
(``inference/engine_v2.py`` ``_kv_kind_args``). A program whose spans carry
none (a family with K and V pools; a program older than the span arguments)
reports nothing. Not a reader itself: the latent readers share it."""

from benchmark.harness import program_spans as ps


def calls(ctx):
    program = ps.load(ctx)
    if program is None:
        return []
    window = ctx["trace"].window()
    out = []
    for s in ps.named(program.spans, "decode_step", window):
        if s.arg("kv_tokens_latent"):
            out.append({"kind": "decode",
                        "tokens": s.arg("kv_tokens_latent")})
        if s.arg("chunk_kv_tokens_latent") is not None:
            out.append({"kind": "chunk",
                        "tokens": s.arg("chunk_kv_tokens_latent"),
                        "ctx": int(s.arg("chunk_ctx")),
                        "rows": int(s.arg("chunk_tokens"))})
    for s in ps.named(program.spans, "prefill_chunk", window):
        if s.arg("kv_tokens_latent") is not None:
            out.append({"kind": "chunk", "tokens": s.arg("kv_tokens_latent"),
                        "ctx": int(s.arg("ctx")),
                        "rows": int(s.arg("tokens"))})
    return out
