"""``scope_share`` with the retention layer's own scopes known: device time
in operations whose innermost named scope is one of ``scopes``, over device
busy time, in percent. The layer's scopes (``retention_proj``: q, k, v, the
gate, their norms and rotary embedding, and ``W_o``; ``retention_state``: the
single-token update and its query; ``retention_chunk``: the chunked form)
lie INSIDE ``attn`` in the program, and ``program_spans.SCOPES`` - fixed,
what ``scope_share`` reads by - does not name them, so that reader books the
whole layer to ``attn``. A program that names none of them reports
nothing."""

from benchmark.harness import program_spans as ps

RETENTION_SCOPES = ("retention_proj", "retention_state", "retention_chunk")


def read(ctx, scopes):
    program = ps.load(ctx)
    if program is None or not program.ops:
        return None
    window = ctx["trace"].window()
    mine = busy = 0.0
    named = False
    for ops in program.ops.values():
        by_scope = ps.scope_seconds(ops, window, ps.SCOPES + RETENTION_SCOPES)
        named = named or any(s in by_scope for s in RETENTION_SCOPES)
        mine += sum(by_scope.get(s, 0.0) for s in scopes)
        busy += sum(by_scope.values())
    return 100.0 * mine / busy if busy and named else None
