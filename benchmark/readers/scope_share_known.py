"""``scope_share`` with a layer's own scopes known, for ANY layer: device
time in operations whose innermost named scope is one of ``scopes``, over
device busy time, in percent. ``known`` names the scopes a family's layer
opens INSIDE one of ``program_spans.SCOPES`` (a state layer's projections,
convolutions and state ops inside ``attn``): ``scope_share`` reads by the
fixed list alone and books them to the outer scope. The metric's file gives
both lists, so a later layer brings a JSON file and no reader. A program
that names none of ``known`` reports nothing."""

from benchmark.harness import program_spans as ps


def seconds_by_scope(program, window, known) -> dict:
    """Device seconds by innermost scope, ``known`` beside the fixed list,
    summed over the chips."""
    total = {}
    for ops in program.ops.values():
        for scope, s in ps.scope_seconds(
                ops, window, ps.SCOPES + tuple(known)).items():
            total[scope] = total.get(scope, 0.0) + s
    return total


def read(ctx, scopes, known):
    program = ps.load(ctx)
    if program is None or not program.ops:
        return None
    by_scope = seconds_by_scope(program, ctx["trace"].window(), known)
    busy = sum(by_scope.values())
    if not busy or not any(s in by_scope for s in known):
        return None
    return 100.0 * sum(by_scope.get(s, 0.0) for s in scopes) / busy
