"""What the benchmark asks of the machine before it measures, and what it
reports about it afterwards."""

from __future__ import annotations

import os
from typing import Any, Dict, Iterable

from . import peaks
from .manifest import ROOT


class Refused(RuntimeError):
    """The run may not measure here. ``run.py`` prints the reason on stderr
    and exits non-zero without a result line."""


def compile_cache_dir() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else a fixed directory inside the checkout (the path is part of
    the cache's key). The program's own helper,
    ``deepspeed_tpu/utils/compile_cache.py``, applies the same rule, so the
    two never disagree."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    path = placed or os.path.join(ROOT, ".xla_cache")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    # every program of the cell, however quick to compile, is cached: a
    # second run of a cell must find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def check(chips: int, rehearsal: bool) -> Dict[str, Any]:
    """The device as JAX reports it; refuses a device the cell is not for."""
    import jax

    from deepspeed_tpu.tuning.persist import tuned_path

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if os.path.exists(tuned_path()):
        raise Refused(
            f"{tuned_path()} is steering the flash kernel's block sizes; it "
            f"is no part of any commit - remove it")
    if len(devs) != chips:
        raise Refused(f"{len(devs)} devices visible, the cell is for {chips}")
    if rehearsal:
        return info
    if info["platform"] != "tpu":
        raise Refused(f"no accelerator: JAX reports platform "
                      f"{info['platform']!r} (a rehearsal is --rehearse)")
    try:
        peaks.peaks_for(info["kind"])
    except peaks.UnknownDevice as e:
        raise Refused(str(e)) from None
    return info


def record_compiled() -> list:
    """Every program JAX compiles ahead of time in this process from here
    on, as ``jax.stages.Compiled`` objects in a list that grows. The
    program's compile monitor (``telemetry.compile`` / ``compile_monitor``,
    which every configuration turns on) lowers and compiles each jitted
    entry point through ``jax.stages.Lowered.compile``; watching that public
    stage keeps the benchmark out of the program's own caches."""
    import jax

    seen: list = []
    compile_ = jax.stages.Lowered.compile

    def compile_and_keep(self, *args, **kwargs):
        compiled = compile_(self, *args, **kwargs)
        seen.append(compiled)
        return compiled

    jax.stages.Lowered.compile = compile_and_keep
    return seen


def program_bytes(compiled) -> int:
    """Bytes one compiled program holds on a device at the fullest point of
    its run: ``memory_analysis().peak_memory_in_bytes``, the compiler's own
    peak over arguments, results and temporaries that are live together.
    (Arguments plus temporaries, which this benchmark first used, counts
    buffers that are never live together: 17.9 GB for the ZeRO-3 step that
    runs on a 16 GB chip, where the peak is 15.87 GB.)"""
    m = compiled.memory_analysis()
    peak = int(m.peak_memory_in_bytes)
    if peak <= 0:
        raise RuntimeError("memory_analysis() gives no peak for a compiled "
                           f"program: {m}")
    return peak


def memory_peak_bytes(programs: Iterable) -> int:
    """Peak on the fullest chip. The allocator's ``peak_bytes_in_use`` does
    not see a program's temporaries (8.42 GB where the compiled training
    step holds 12.7; PERF.md section 5), so the peak is the larger of it and
    the largest compiled program of the cell. A program that ran cannot
    have held more than the allocator's limit: a count above it is a wrong
    count, and the run fails rather than report it."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    seen = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    limit = min((int(s["bytes_limit"]) for s in stats if "bytes_limit" in s),
                default=None)
    largest = max((program_bytes(p) for p in programs), default=0)
    if limit is not None and largest > limit:
        raise RuntimeError(
            f"a compiled program is counted at {largest} bytes on a device "
            f"whose allocator holds {limit}: the count is wrong")
    return max(seen, largest)


def jax_key(seed: int):
    """A PRNG key from any whole number: ``--seed`` may be larger than 32
    signed bits hold."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)
