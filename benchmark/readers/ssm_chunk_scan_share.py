"""Device time under the ``ssm_state`` scope that is NOT the single-token
update's kernel - the chunked scan of a prompt's rows and the state's read
and write around it: what a chunked-scan kernel has to win - over device
busy time, in percent. ``scope_share_ssm`` books both to ``ssm_state``; this
takes the kernel's own events (``nemotron_h_roofline.kernel_seconds``) out.
A program that names no state-space scope reports nothing."""

from benchmark.harness import program_spans as ps
from benchmark.readers import nemotron_h_roofline
from benchmark.readers.scope_share_ssm import SSM_SCOPES


def read(ctx, kernel, scope="ssm_state"):
    program = ps.load(ctx)
    if program is None or not program.ops:
        return None
    window = ctx["trace"].window()
    ops = next(iter(program.ops.values()))      # serve cells: one chip
    by_scope = ps.scope_seconds(ops, window, ps.SCOPES + SSM_SCOPES)
    busy = sum(by_scope.values())
    if not busy or not any(s in by_scope for s in SSM_SCOPES):
        return None
    rest = by_scope.get(scope, 0.0) \
        - nemotron_h_roofline.kernel_seconds(ctx, kernel)
    return 100.0 * max(rest, 0.0) / busy
