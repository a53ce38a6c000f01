"""The memory a run reports: the compiler's peak of the largest program the
process compiled ahead of time, never cut off and never above the chip."""

import jax
import jax.numpy as jnp
import pytest

from benchmark.harness import device


def test_programs_compiled_ahead_of_time_are_seen_and_counted(monkeypatch):
    monkeypatch.setattr(jax.stages.Lowered, "compile",
                        jax.stages.Lowered.compile)   # restored afterwards
    seen = device.record_compiled()
    x = jnp.ones((256, 256), jnp.float32)
    small = jax.jit(lambda a: a + 1).lower(x[:8, :8]).compile()
    large = jax.jit(lambda a: (a @ a) @ (a.T @ a)).lower(x).compile()
    jax.jit(lambda a: a * 2)(x)        # plain dispatch: not the program's way
    assert seen == [small, large]
    m = large.memory_analysis()
    assert device.program_bytes(large) == m.peak_memory_in_bytes
    # live together at the fullest point, so never above what is allocated
    assert device.program_bytes(large) <= (
        m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.temp_size_in_bytes)
    assert device.memory_peak_bytes(seen) >= device.program_bytes(large) \
        > device.program_bytes(small)


def test_a_count_above_the_chip_fails_the_run(monkeypatch):
    class Chip:
        def memory_stats(self):
            return {"peak_bytes_in_use": 10, "bytes_limit": 1000}

    class Program:
        def __init__(self, peak):
            self.peak = peak

        def memory_analysis(self):
            return type("M", (), {"peak_memory_in_bytes": self.peak})()

    monkeypatch.setattr(jax, "local_devices", lambda: [Chip()])
    assert device.memory_peak_bytes([Program(900), Program(40)]) == 900
    assert device.memory_peak_bytes([]) == 10
    with pytest.raises(RuntimeError, match="count is wrong"):
        device.memory_peak_bytes([Program(1001)])
    with pytest.raises(RuntimeError, match="no peak"):
        device.program_bytes(Program(0))
