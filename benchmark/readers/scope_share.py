"""Device time in operations under the given named scopes of the model step
(``program_spans.SCOPES``; an operation belongs to the innermost scope in its
``op_name``) over device busy time, in percent, summed over the chips."""

from benchmark.harness import program_spans as ps


def read(ctx, scopes):
    program = ps.load(ctx)
    if program is None or not program.ops:
        return None
    window = ctx["trace"].window()
    mine = busy = 0.0
    for ops in program.ops.values():
        by_scope = ps.scope_seconds(ops, window)
        if set(by_scope) <= {ps.NO_SCOPE}:
            return None          # the program names no scope
        mine += sum(by_scope.get(s, 0.0) for s in scopes)
        busy += sum(by_scope.values())
    return 100.0 * mine / busy if busy else None
