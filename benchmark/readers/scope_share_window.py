"""``scope_share`` with the two kinds of attention layer's own scopes known:
device time in operations whose innermost named scope is one of ``scopes``,
over device busy time, in percent. ``attn_window`` and ``attn_full`` (the
paged attention step of a sliding-window layer and of a full layer; the
pool update inside each is ``kv_write``) lie INSIDE ``attn`` in the program,
and ``program_spans.SCOPES`` - fixed, what ``scope_share`` reads by - does
not name them, so that reader books them to ``attn``. A program that names
neither reports nothing."""

from benchmark.harness import program_spans as ps

WINDOW_SCOPES = ("attn_window", "attn_full")


def read(ctx, scopes):
    program = ps.load(ctx)
    if program is None or not program.ops:
        return None
    window = ctx["trace"].window()
    mine = busy = 0.0
    named = False
    for ops in program.ops.values():
        by_scope = ps.scope_seconds(ops, window, ps.SCOPES + WINDOW_SCOPES)
        named = named or any(s in by_scope for s in WINDOW_SCOPES)
        mine += sum(by_scope.get(s, 0.0) for s in scopes)
        busy += sum(by_scope.values())
    return 100.0 * mine / busy if busy and named else None
