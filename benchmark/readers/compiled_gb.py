"""Bytes the largest compiled program of the cell (the training step, in a
training cell) holds on each chip at the fullest point of its run
(``device.program_bytes``), in GB."""

from benchmark.harness.device import program_bytes


def read(ctx):
    programs = ctx.get("programs")
    if not programs:
        return None
    return max(program_bytes(p) for p in programs) / 1e9
