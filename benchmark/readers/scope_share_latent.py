"""``scope_share`` with a latent-attention family's own scopes known: device
time in operations whose innermost named scope is one of ``scopes``, over
device busy time, in percent. ``attn_latent`` (a layer's latent attention:
the bottleneck projections, norms and rope, and the walk over the latent
pool; the pool update inside it is ``kv_write``), ``mla_absorb`` (the
``W_uk`` / ``W_uv`` matmuls around the walk) and ``mla_expand`` (keys and
values rebuilt a head, where a program builds that form) lie INSIDE ``attn``
in the program, ``dense_ffn`` (a leading dense layer's FFN) inside ``ffn``,
and ``program_spans.SCOPES`` - fixed, what ``scope_share`` reads by - does
not name them, so that reader books them to ``attn`` and ``ffn``. A program
that names none of them reports nothing."""

from benchmark.harness import program_spans as ps

LATENT_SCOPES = ("attn_latent", "mla_absorb", "mla_expand", "dense_ffn")


def read(ctx, scopes):
    program = ps.load(ctx)
    if program is None or not program.ops:
        return None
    window = ctx["trace"].window()
    mine = busy = 0.0
    named = False
    for ops in program.ops.values():
        by_scope = ps.scope_seconds(ops, window, ps.SCOPES + LATENT_SCOPES)
        named = named or any(s in by_scope for s in LATENT_SCOPES)
        mine += sum(by_scope.get(s, 0.0) for s in scopes)
        busy += sum(by_scope.values())
    return 100.0 * mine / busy if busy and named else None
