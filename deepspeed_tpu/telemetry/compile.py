"""Compile-aware telemetry: recompilation sentinel + analytic cost model.

The two classic silent killers of JAX/TPU production jobs are invisible to
wall-clock telemetry: an **unnoticed recompilation storm** (a shape or
sharding that drifts per step retraces and recompiles the same program over
and over — each one minutes on real silicon) and a headline MFU number with
**no decomposition** (one ThroughputTimer scalar says nothing about where
the flops went). This module answers both:

- :class:`CompileMonitor` is the shared registration helper every jitted
  entry point in ``runtime/engine.py`` and ``inference/engine_v2.py`` routes
  through (``monitor.jit(name, fn, **jit_kwargs)``). Default **OFF**: a
  disabled monitor returns the ``jax.jit`` object untouched, so the default
  program is byte-identical (pinned by parity tests). Enabled, it dispatches
  through explicitly lowered+compiled programs, which makes every
  trace/lower/compile an *observed event*: per-program lowering and compile
  wall time, the abstract-shape signature that triggered it, cache hits vs
  misses, and **recompile detection** (same program name, new signature)
  with a config-gated budget that warns or raises after N unexpected
  recompiles in steady state. Each lower + compile runs inside a
  ``compile`` span of the tracer (ring and profiler timeline).
- Each compile pulls ``lower(...).compile().cost_analysis()`` flops/bytes
  (guarded — backends may return ``None``), giving the TelemetryHub an
  analytic per-program cost model - and ``memory_analysis()`` the bytes the
  program holds at its fullest point
  (``Compile/<program>/peak_memory_bytes``): the headline MFU decomposes into
  ``Train/mfu/<program>`` and ``Serving/mfu/<program>`` gauges (prefill vs
  decode vs train-step) instead of one ThroughputTimer number. A program
  registered with its KV ``pools`` also says what it moves to re-house them
  (``pool_copy_bytes``: 0 for one that writes them where they lie) and what
  it aliases argument-to-result (``aliased_bytes``).
- :class:`CompileAccount` (one a process: :func:`process_account`) says where
  START-UP went. The monitor above sees only the programs registered with it;
  JAX publishes every trace, lowering, backend compile and persistent-cache
  request of the whole process through ``jax.monitoring``, and the account
  listens: the first ENABLED monitor registers one event listener and one
  duration listener (a disabled monitor registers none - the default path
  stays event-free). It keeps each event as an interval on the
  ``time.perf_counter`` clock and answers in seconds of the intervals' UNION
  (an outer function's trace contains the traces of the functions it calls),
  for the whole process or up to a moment (``totals(before=...)``), and by
  function (``by_program``). The monitor's own inspection of each compiled
  program (cost and memory analysis, the HLO text's pool scan) is timed into
  it too (``monitor_analysis_s``): tracing code on the set-up path pays its
  way in the open.

Two caches, two words: ``cache_hits`` (``ProgramStats``,
``Compile/<program>/cache_hits``) counts DISPATCHES the monitor's in-memory
signature table served; ``persistent_cache_hits`` / ``_misses`` and the
account's ``cache_hits`` / ``cache_misses`` count ``compile()`` calls JAX's
persistent compilation cache served from disk or had to compile and write.

Event names (``Compile/<program>/<metric>``, ``Compile/total/*``,
``Compile/process/*``, ``<group>/mfu/<program>``) are registered in
``telemetry/schema.py``; ``telemetry_report.py --compile`` renders the
offline summary.
"""

from __future__ import annotations

import math
import re
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ..utils.logging import logger
from ..utils.peaks import UnknownDevice, device_peaks
from .trace import NULL_TRACER

__all__ = ["CompileMonitorConfig", "CompileMonitor", "MonitoredFunction",
           "ProgramStats", "RecompileBudgetExceeded", "peak_flops_total",
           "pool_copy_bytes", "CompileAccount", "process_account"]

Event = Tuple[str, float, int]

_NAME_SANITIZE = re.compile(r"[^A-Za-z0-9_]")


@dataclass
class CompileMonitorConfig:
    """The ``telemetry.compile`` config block (docs/observability.md).

    Default OFF: every monitored jit site gets the plain ``jax.jit`` object
    back and nothing is recorded — the default program is byte-identical."""

    enabled: bool = False
    # distinct signatures per program treated as expected warmup (bucketed
    # serving programs legitimately compile one variant per bucket; raise
    # this to the bucket count to keep the budget quiet through warmup)
    warmup_signatures: int = 1
    # unexpected recompiles (beyond warmup, across all programs) tolerated
    # before on_budget fires; 0 = unlimited (sentinel records, never acts)
    recompile_budget: int = 0
    # warn | raise — what to do when the budget is exhausted
    on_budget: str = "warn"
    # pull cost_analysis() flops/bytes per compiled program (feeds the
    # per-program MFU attribution; None-returning backends degrade to 0)
    cost_analysis: bool = True


class RecompileBudgetExceeded(RuntimeError):
    """Raised when ``recompile_budget`` is exhausted with ``on_budget:
    raise`` — a recompilation storm in steady state is a production
    incident, not a log line."""


@dataclass
class ProgramStats:
    """Cumulative per-program compile accounting (one registered name)."""

    name: str
    group: str = "Train"            # event group for the MFU gauges
    compiles: int = 0               # lower+compile executions (signatures)
    cache_hits: int = 0             # dispatches served by the monitor's table
    recompiles: int = 0             # compiles beyond the first signature
    lower_ms: float = 0.0           # cumulative lowering wall time
    compile_ms: float = 0.0         # cumulative backend-compile wall time
    cost_flops: float = 0.0         # per-call flops (last compile's analysis)
    cost_bytes: float = 0.0         # per-call bytes accessed (last compile)
    peak_memory_bytes: int = 0      # largest compiled signature's device peak
    pool_copy_bytes: int = 0        # last compile: pool-shaped copies (``pools``)
    aliased_bytes: int = 0          # last compile: arguments aliased to results
    analysis_ms: float = 0.0        # cumulative: the monitor's own inspection
    persistent_cache_hits: int = 0  # compile() calls JAX's disk cache served
    persistent_cache_misses: int = 0    # ... and those it compiled and wrote
    calls_since_drain: int = 0      # executions since the last events() drain
    signatures: List[Any] = field(default_factory=list)


def peak_flops_total() -> Optional[float]:
    """bf16 peak FLOP/s of every local chip together, from the one published
    table (``utils/peaks.py``) — or None on a device the table does not
    list, where the MFU gauges are then ABSENT (a utilization against an
    assumed peak is not a measurement)."""
    try:
        return device_peaks().bf16_flops * max(1, jax.device_count())
    except UnknownDevice:
        return None


def _sharding_signature(x: jax.Array) -> str:
    """Canonical sharding key. jax's dispatch cache treats these spellings
    as ONE sharding, so the signature must too — otherwise step 1's
    explicitly-placed state vs step 2's compiled outputs would read as a
    phantom recompile:

    - ``PartitionSpec(None, None)`` == ``PartitionSpec()`` (trailing
      ``None`` entries stripped);
    - a single-axis tuple entry ``('data',)`` == the bare axis ``'data'``
      (single-element entry tuples unwrapped)."""
    sh = getattr(x, "sharding", None)
    if sh is None:
        return ""
    spec = getattr(sh, "spec", None)
    if spec is not None:
        entries = tuple(e[0] if isinstance(e, tuple) and len(e) == 1
                        else tuple(e) if isinstance(e, tuple) else e
                        for e in spec)
        while entries and entries[-1] is None:
            entries = entries[:-1]
        mesh = getattr(sh, "mesh", None)
        shape = getattr(mesh, "shape", None)
        return (f"named:{tuple(shape.items()) if shape else ()}:{entries}:"
                f"{getattr(sh, 'memory_kind', '')}")
    return str(sh)


def _leaf_signature(x: Any) -> Tuple:
    """Hashable abstract signature of one argument leaf: shape/dtype (and
    sharding, which also forces recompiles) for arrays, the python type for
    everything else (weak-typed scalars of one type share a trace)."""
    if isinstance(x, jax.Array):
        return (tuple(x.shape), str(x.dtype), _sharding_signature(x))
    shape = getattr(x, "shape", None)
    if shape is not None:  # numpy / duck-typed host arrays
        return (tuple(shape), str(getattr(x, "dtype", "")), "host")
    return (type(x).__name__,)


def _abstract_signature(args, kwargs) -> Tuple:
    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(_leaf_signature(x) for x in leaves))


def _cost_analysis(compiled) -> Tuple[float, float]:
    """(flops, bytes_accessed) per call from XLA's cost analysis; 0.0s when
    the backend returns None/[]/{} or raises (the CPU fallback contract)."""
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return 0.0, 0.0
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    if not isinstance(cost, dict):
        return 0.0, 0.0
    try:
        return (float(cost.get("flops", 0.0) or 0.0),
                float(cost.get("bytes accessed", 0.0) or 0.0))
    except (TypeError, ValueError):
        return 0.0, 0.0


def _peak_memory_bytes(compiled) -> int:
    """Bytes the compiled program holds on a device at the fullest point of
    its run (arguments, results and temporaries live together) from
    ``memory_analysis()``; 0 where the backend gives none. The allocator's
    ``peak_bytes_in_use`` does not see a program's temporaries."""
    try:
        return max(0, int(compiled.memory_analysis().peak_memory_in_bytes))
    except Exception:
        return 0


_HLO_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
              "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
              "u64": 8}
# ``%name = dtype[dims]{layout} opcode(``: an instruction with one array result
_HLO_ARRAY_OP = re.compile(
    r"^\s*(?:ROOT\s+)?%\S+ = (\w+)\[([\d,]+)\]\S* ([\w-]+)\(")


def pool_copy_bytes(hlo_text: str, pools) -> int:
    """Bytes of a compiled program's results that re-house a stacked pool:
    every ``copy``, ``dynamic-slice``, ``dynamic-update-slice``, loop fusion
    or ``AllocateBuffer`` whose result has the shape of one of ``pools``
    (``[L, ...]`` arrays or shapes) or of one layer of it (``[...]``,
    ``[1, ...]``). A program that writes its pools where they lie has none:
    0 is the serving programs' target (models/_paged.scan_layers)."""
    dims = set()
    for pool in pools:
        shape = tuple(getattr(pool, "shape", pool))
        dims |= {shape, shape[1:], (1,) + shape[1:]}
    total = 0
    for line in hlo_text.splitlines():
        m = _HLO_ARRAY_OP.match(line)
        if m is None:
            continue
        dtype, shape, opcode = m.groups()
        shape = tuple(map(int, shape.split(",")))
        if shape not in dims or not (
                opcode in ("copy", "dynamic-slice", "dynamic-update-slice")
                or opcode == "fusion" and "kind=kLoop" in line
                or opcode == "custom-call" and '"AllocateBuffer"' in line):
            continue
        total += _HLO_BYTES.get(dtype, 4) * math.prod(shape)
    return total


def _aliased_bytes(compiled) -> int:
    """Bytes of arguments the compiled program's results live in (donated
    and taken up: ``memory_analysis().alias_size_in_bytes``); 0 where the
    backend gives none."""
    try:
        return max(0, int(compiled.memory_analysis().alias_size_in_bytes))
    except Exception:
        return 0


# jax.monitoring's names (public, JAX 0.9) -> the account's phases. A duration
# is an interval; a count is a moment. Every other event is let pass.
_DURATION_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_COUNT_PHASES = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_request",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
# what totals() answers: seconds are the union of these phases' intervals,
# counts the number of these phases' records
_ACCOUNT_SECONDS = {
    "trace_lower_s": ("trace", "lower"),
    "backend_compile_s": ("backend_compile",),
    "cache_retrieval_s": ("cache_retrieval",),
    "monitor_analysis_s": ("monitor_analysis",),
}
_ACCOUNT_COUNTS = {
    "cache_hits": "cache_hit",
    "cache_misses": "cache_miss",
    "cache_requests": "cache_request",
    "programs_compiled": "backend_compile",
}
# phases that end a whole program on their thread (see CompileAccount._fold)
_WHOLE_PROGRAM = ("backend_compile", "monitor_analysis")
_JIT_WRAPPED = re.compile(r"^p?jit\((.*)\)$")

# (phase, fun_name, t0, t1, thread): perf_counter seconds, get_ident()
AccountRecord = Tuple[str, str, float, float, int]


def _union_s(intervals) -> float:
    """Length of the union of ``(t0, t1)`` intervals."""
    total, end = 0.0, -math.inf
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def _tally(records) -> Dict[str, float]:
    """The account's answer over ``records``."""
    out: Dict[str, float] = {}
    for key, phases in _ACCOUNT_SECONDS.items():
        out[key] = _union_s((r[2], r[3]) for r in records if r[0] in phases)
    for key, phase in _ACCOUNT_COUNTS.items():
        out[key] = sum(r[0] == phase for r in records)
    return out


def _add_tally(into: Dict[str, float], records) -> None:
    for key, value in _tally(records).items():
        into[key] = into.get(key, 0) + value


def _add_tally_by_name(into: Dict[str, Dict[str, float]], records) -> None:
    by_name: Dict[str, List[AccountRecord]] = {}
    for r in records:
        by_name.setdefault(r[1], []).append(r)
    for name, mine in by_name.items():
        _add_tally(into.setdefault(name, {}), mine)


class CompileAccount:
    """Where the process's compile time went, from ``jax.monitoring`` (module
    docstring). One record an event, ``(phase, fun_name, t0, t1, thread)``
    with ``t1 = time.perf_counter()`` at the callback and ``t0 = t1 -
    duration``; phases ``trace``, ``lower``, ``backend_compile`` (which holds
    a cache retrieval where there was one), ``cache_retrieval``, the three
    cache counts, and ``monitor_analysis`` (the compile monitor's own work
    after a compile, :meth:`record`).

    Memory is bounded: past ``cap`` records (far above any set-up's; a
    server that keeps compiling for a week must not grow) all of them are
    FOLDED into running totals, and a ``before`` earlier than that moment is
    refused - the account cannot split what it has folded. The fold waits
    for the end of a whole program (a backend compile or the monitor's
    analysis of it: nothing of that thread is in flight then, so no interval
    straddles the fold and the union stays exact) or, failing that, for
    twice the cap. A callback takes
    the account's own lock, which no dispatch takes, for an append; in a
    steady window nothing compiles and no callback runs."""

    def __init__(self, cap: int = 1 << 17):
        self.cap = max(2, int(cap))
        self.events_seen = 0              # records ever taken (folded too)
        self._records: List[AccountRecord] = []
        self._folded: Dict[str, float] = {}
        self._folded_by_name: Dict[str, Dict[str, float]] = {}
        self._folded_until = -math.inf
        self._totals: Optional[Dict[str, float]] = None   # of everything
        self._installed = False
        self._lock = threading.Lock()

    # ------------------------------------------------------------------ #
    def install(self) -> None:
        """Register the two listeners with ``jax.monitoring``, once."""
        with self._lock:
            if self._installed:
                return
            self._installed = True
        jax.monitoring.register_event_listener(self.on_event)
        jax.monitoring.register_event_duration_secs_listener(self.on_duration)

    def on_event(self, event: str, **_kwargs) -> None:
        phase = _COUNT_PHASES.get(event)
        if phase is not None:
            now = time.perf_counter()
            self.record(phase, "", now, now)

    def on_duration(self, event: str, duration_secs: float,
                    **kwargs) -> None:
        phase = _DURATION_PHASES.get(event)
        if phase is not None:
            now = time.perf_counter()
            self.record(phase, str(kwargs.get("fun_name", "")),
                        now - duration_secs, now)

    def record(self, phase: str, fun_name: str, t0: float,
               t1: float) -> None:
        m = _JIT_WRAPPED.match(fun_name)   # 'jit(f)' lowers what 'f' traced
        name = m.group(1) if m else fun_name
        thread = threading.get_ident()
        with self._lock:
            recs = self._records
            if phase == "backend_compile":
                # the cache's events carry no name: they happened inside
                # this compile, on this thread
                i = len(recs) - 1
                while i >= 0 and recs[i][3] >= t0:
                    r = recs[i]
                    if r[4] == thread and r[0].startswith("cache_"):
                        recs[i] = (r[0], name) + r[2:]
                    i -= 1
            recs.append((phase, name, t0, t1, thread))
            self.events_seen += 1
            self._totals = None
            if len(recs) > self.cap and (phase in _WHOLE_PROGRAM
                                         or len(recs) > 2 * self.cap):
                self._fold(t1)

    def _fold(self, until: float) -> None:
        _add_tally(self._folded, self._records)
        _add_tally_by_name(self._folded_by_name, self._records)
        self._records = []
        self._folded_until = until

    # ------------------------------------------------------------------ #
    def _ended_before(self, before: Optional[float]):
        with self._lock:
            if before is not None and before < self._folded_until:
                raise ValueError(
                    f"the account has folded its records up to "
                    f"{self._folded_until} and cannot split them at "
                    f"{before}")
            recs = list(self._records)
            folded = dict(self._folded)
            by_name = {n: dict(t) for n, t in self._folded_by_name.items()}
        if before is not None:
            recs = [r for r in recs if r[3] <= before]
        return recs, folded, by_name

    def totals(self, before: Optional[float] = None) -> Dict[str, float]:
        """Seconds by phase (union of intervals, all threads together) and
        counts, over the events that ended before ``before`` (a
        ``perf_counter`` time; everything if None): ``trace_lower_s``,
        ``backend_compile_s``, ``cache_retrieval_s``, ``monitor_analysis_s``,
        ``cache_hits``, ``cache_misses``, ``cache_requests`` and
        ``programs_compiled`` (every program the process asked the backend
        for, hit or miss, eager one-op programs too)."""
        cached = self._totals
        if before is None and cached is not None:
            return dict(cached)       # a drain every step re-adds nothing
        seen = self.events_seen
        recs, folded, _ = self._ended_before(before)
        _add_tally(folded, recs)
        if before is None:
            with self._lock:
                if self.events_seen == seen:
                    self._totals = dict(folded)
        return folded

    def by_program(self, before: Optional[float] = None,
                   top: int = 10) -> List[Dict[str, Any]]:
        """:meth:`totals` by ``fun_name`` (``jit(f)`` counted with ``f``),
        the ``top`` costliest in seconds first. A function's own seconds
        contain those of the functions it calls."""
        recs, _, by_name = self._ended_before(before)
        _add_tally_by_name(by_name, recs)
        rows = [{"program": name, **t} for name, t in by_name.items()]
        rows.sort(key=lambda r: -(r["trace_lower_s"] + r["backend_compile_s"]
                                  + r["monitor_analysis_s"]))
        return rows[:top]

    def cache_outcome(self, since: float) -> Tuple[int, int, int]:
        """``(hits, misses, requests)`` of the persistent cache on the
        CALLING thread since ``since``: what one ``compile()`` met."""
        thread = threading.get_ident()
        counts = dict.fromkeys(("cache_hit", "cache_miss", "cache_request"),
                               0)
        with self._lock:
            for r in reversed(self._records):
                if r[3] < since:
                    break
                if r[4] == thread and r[0] in counts:
                    counts[r[0]] += 1
        return (counts["cache_hit"], counts["cache_miss"],
                counts["cache_request"])


_PROCESS_ACCOUNT = CompileAccount()


def process_account() -> CompileAccount:
    """The one account of this process: ``jax.monitoring``'s listeners are
    process-wide, so what they feed is too. Empty until the first enabled
    :class:`CompileMonitor` installs it, and deaf to what compiled before
    (weights materialised ahead of the engine's monitor)."""
    return _PROCESS_ACCOUNT


class MonitoredFunction:
    """A jitted entry point dispatching through the monitor's own
    signature → compiled-program cache. A signature miss runs the explicit
    ``lower()`` / ``compile()`` phases (timed separately) and records the
    compile; a hit calls the stored compiled program directly. Unknown
    attribute access (``.lower``, ``.trace``) passes through to the
    underlying ``jax.jit`` object so AOT consumers keep working."""

    def __init__(self, monitor: "CompileMonitor", name: str, jitted,
                 group: str, pools=()):
        self._monitor = monitor
        self._name = name
        self._jitted = jitted
        self._group = group
        self._pools = tuple(pools)
        self._compiled: Dict[Tuple, Any] = {}
        self._fallback = False

    def __getattr__(self, attr):  # .lower()/.trace()/… of the jitted fn
        return getattr(self._jitted, attr)

    def __call__(self, *args, **kwargs):
        if self._fallback:
            return self._jitted(*args, **kwargs)
        try:
            sig = _abstract_signature(args, kwargs)
            entry = self._compiled.get(sig)
        except Exception as e:  # unhashable static arg etc. — degrade once
            self._degrade(f"signature: {e}")
            return self._jitted(*args, **kwargs)
        if entry is not None:
            self._monitor._record_hit(self._name)
            try:
                return entry(*args, **kwargs)
            except (TypeError, ValueError) as e:
                # argument/signature mismatches the AOT executable raises
                # BEFORE execution starts — safe to degrade and re-dispatch
                # (donated buffers are untouched). Runtime execution errors
                # (XLA OOM, nan-checks, io_callback failures) propagate: a
                # silent re-execution would mask the failure, double-run
                # side effects, and with donated inputs already consumed
                # die with a confusing secondary error instead.
                self._degrade(f"AOT dispatch: {e}")
                return self._jitted(*args, **kwargs)
        # one span around lower + compile: on the profiler's timeline a
        # recompilation covers the device idle gap it causes
        with self._monitor.tracer.span("compile", cat="compile",
                                       program=self._name) as span:
            try:
                t0 = time.perf_counter()
                lowered = self._jitted.lower(*args, **kwargs)
                t1 = time.perf_counter()
                compiled = lowered.compile()
                t2 = time.perf_counter()
            except Exception as e:
                self._degrade(f"lower/compile: {e}")
                return self._jitted(*args, **kwargs)
            self._compiled[sig] = compiled
            # budget enforcement may raise — record AFTER caching the program
            # so a caller that catches RecompileBudgetExceeded can still
            # proceed
            self._monitor._record_compile(
                self._name, self._group, sig, lower_ms=(t1 - t0) * 1e3,
                compile_ms=(t2 - t1) * 1e3, compiled=compiled, span=span,
                pools=self._pools,
                cache=self._monitor.account.cache_outcome(since=t1))
        return compiled(*args, **kwargs)

    def _degrade(self, why: str) -> None:
        if not self._fallback:
            self._fallback = True
            logger.warning(f"compile monitor: program '{self._name}' fell "
                           f"back to plain jit dispatch ({why})")


class CompileMonitor:
    """See module docstring. ``cfg`` is any object carrying the
    :class:`CompileMonitorConfig` attributes; ``None`` or ``enabled: false``
    yields a disabled monitor whose :meth:`jit` returns plain ``jax.jit``
    objects and whose every other operation is a cheap no-op."""

    def __init__(self, cfg=None, tracer=None):
        self.cfg = cfg if cfg is not None else CompileMonitorConfig()
        self.enabled = bool(getattr(self.cfg, "enabled", False))
        self.warmup_signatures = max(
            1, int(getattr(self.cfg, "warmup_signatures", 1) or 1))
        self.recompile_budget = int(
            getattr(self.cfg, "recompile_budget", 0) or 0)
        self.on_budget = str(getattr(self.cfg, "on_budget", "warn") or "warn")
        self.cost_analysis = bool(getattr(self.cfg, "cost_analysis", True))
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.stats: Dict[str, ProgramStats] = {}
        self.unexpected_recompiles = 0
        self._budget_tripped = False
        self._lock = threading.Lock()
        # the process's compile account: installed by the first enabled
        # monitor, never by a disabled one (the default path is event-free)
        self.account = process_account()
        if self.enabled:
            self.account.install()
        # per-caller drain timestamps and first-dispatch marks, keyed by
        # event group ('' = an unscoped drain over every group). A drain's
        # first wall window is anchored at the group's first POST-compile
        # dispatch, not monitor construction — engine setup and compile
        # wall time must not dilute the first MFU window.
        self._last_drain: Dict[str, float] = {}
        self._dispatch_t0: Dict[str, float] = {}

    # ------------------------------------------------------------------ #
    def jit(self, name: str, fn: Callable, group: str = "Train", pools=(),
            **jit_kwargs):
        """The shared registration helper: ``jax.jit(fn, **jit_kwargs)``,
        wrapped for monitoring when enabled. Disabled → the exact jit object
        (default program byte-identical). ``pools``: the stacked ``[L, ...]``
        KV pools (arrays or shapes) the program is meant to write in place;
        each compile then records :func:`pool_copy_bytes` over them and the
        bytes aliased argument-to-result."""
        jitted = jax.jit(fn, **jit_kwargs)
        if not self.enabled:
            return jitted
        return self.wrap(name, jitted, group=group, pools=pools)

    def wrap(self, name: str, jitted, group: str = "Train", pools=()):
        """Wrap an already-jitted callable (for call sites that need jit
        options the helper doesn't forward)."""
        if not self.enabled:
            return jitted
        name = _NAME_SANITIZE.sub("_", name).lower() or "program"
        with self._lock:
            self.stats.setdefault(name, ProgramStats(name=name, group=group))
        return MonitoredFunction(self, name, jitted, group, pools)

    # ------------------------------------------------------------------ #
    def _record_hit(self, name: str) -> None:
        with self._lock:
            st = self.stats[name]
            st.cache_hits += 1
            st.calls_since_drain += 1
            self._dispatch_t0.setdefault(st.group, time.monotonic())

    def _record_compile(self, name: str, group: str, sig, lower_ms: float,
                        compile_ms: float, compiled, span, pools=(),
                        cache=(0, 0, 0)) -> None:
        """``cache``: ``(hits, misses, requests)`` of JAX's persistent cache
        during this program's ``compile()`` (``CompileAccount.cache_outcome``)."""
        t_analysis = time.perf_counter()
        flops = bytes_ = 0.0
        if self.cost_analysis:
            flops, bytes_ = _cost_analysis(compiled)
        peak = _peak_memory_bytes(compiled)
        pool_attrs = {}
        if pools:
            pool_attrs = {
                "pool_copy_bytes": pool_copy_bytes(compiled.as_text(), pools),
                "aliased_bytes": _aliased_bytes(compiled)}
        t_analysed = time.perf_counter()
        self.account.record("monitor_analysis", name, t_analysis, t_analysed)
        analysis_ms = (t_analysed - t_analysis) * 1e3
        hits, misses, requests = cache
        with self._lock:
            st = self.stats[name]
            recompile = len(st.signatures) >= 1
            unexpected = len(st.signatures) >= self.warmup_signatures
            st.signatures.append(sig)
            st.compiles += 1
            st.calls_since_drain += 1
            st.recompiles += int(recompile)
            st.lower_ms += lower_ms
            st.compile_ms += compile_ms
            st.analysis_ms += analysis_ms
            st.persistent_cache_hits += hits
            st.persistent_cache_misses += misses
            if flops > 0:
                st.cost_flops = flops
            if bytes_ > 0:
                st.cost_bytes = bytes_
            st.peak_memory_bytes = max(st.peak_memory_bytes, peak)
            for key, value in pool_attrs.items():
                setattr(st, key, value)
            if unexpected:
                self.unexpected_recompiles += 1
            over = (self.recompile_budget > 0 and not self._budget_tripped
                    and self.unexpected_recompiles > self.recompile_budget)
            if over:
                self._budget_tripped = True
            # _record_compile runs after lower+compile finished, so this
            # marks the start of the group's executed window
            self._dispatch_t0.setdefault(group, time.monotonic())
        span.set(lower_ms=round(lower_ms, 3), compile_ms=round(compile_ms, 3),
                 recompile=recompile, analysis_ms=round(analysis_ms, 3),
                 persistent_cache="hit" if hits else
                 "miss" if requests else "off", **pool_attrs)
        if recompile:
            logger.warning(
                f"recompilation detected: program '{name}' compiled a new "
                f"signature (#{len(st.signatures)}; {lower_ms:.1f}ms lower + "
                f"{compile_ms:.1f}ms compile) — steady-state shapes should "
                f"be stable")
        if over:
            msg = (f"recompile budget exhausted: {self.unexpected_recompiles}"
                   f" unexpected recompiles > budget {self.recompile_budget}"
                   f" (last: program '{name}') — a recompilation storm is "
                   f"burning step time")
            if self.on_budget == "raise":
                raise RecompileBudgetExceeded(msg)
            logger.warning(msg)

    # ------------------------------------------------------------------ #
    def program_flops(self, name: str) -> float:
        st = self.stats.get(name)
        return float(st.cost_flops) if st is not None else 0.0

    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-program accounting snapshot (tests, reports)."""
        with self._lock:
            return {n: {"compiles": st.compiles, "cache_hits": st.cache_hits,
                        "recompiles": st.recompiles,
                        "lower_ms": st.lower_ms, "compile_ms": st.compile_ms,
                        "cost_flops": st.cost_flops,
                        "cost_bytes": st.cost_bytes,
                        "peak_memory_bytes": st.peak_memory_bytes,
                        "pool_copy_bytes": st.pool_copy_bytes,
                        "aliased_bytes": st.aliased_bytes,
                        "analysis_ms": st.analysis_ms,
                        "persistent_cache_hits": st.persistent_cache_hits,
                        "persistent_cache_misses":
                            st.persistent_cache_misses,
                        "signatures": len(st.signatures)}
                    for n, st in self.stats.items()}

    def events(self, step: int = 0, window_s: Optional[float] = None,
               group: Optional[str] = None) -> List[Event]:
        """Drain: cumulative ``Compile/*`` series plus per-program
        ``<group>/mfu/<name>`` gauges attributing the calls executed since
        THIS CALLER's previous drain over ``window_s`` (the hub passes its
        measured per-step time; serving drains default to the wall window).

        ``group`` scopes the drain to one event group: a hub-shared monitor
        is drained by both the training hub (``group='Train'``, step-time
        window) and the serving engine (``group='Serving'``, wall window),
        and per-group call counters + drain timestamps keep the two
        attributions independent — an unscoped drain over a shared monitor
        would attribute serving calls over the train-step window (and vice
        versa). ``Compile/total/*`` stays cumulative over EVERY program
        regardless of the filter: one monotone series whichever caller
        drains - and so does ``Compile/process/*``, the process's compile
        account (every program JAX traced, lowered or compiled, registered
        here or not)."""
        if not self.enabled:
            return []
        now = time.monotonic()
        events: List[Event] = []
        peak_total = peak_flops_total()
        gkey = group if group is not None else ""
        with self._lock:
            last = self._last_drain.get(gkey)
            if last is None:
                # first drain for this caller: anchor the wall window at the
                # group's first post-compile dispatch (see _dispatch_t0)
                t0s = [t for g, t in self._dispatch_t0.items()
                       if group is None or g == group]
                last = min(t0s) if t0s else now
            self._last_drain[gkey] = now
            window = float(window_s) if window_s and window_s > 0 \
                else max(now - last, 1e-9)
            tot = {"programs": 0, "compiles": 0, "cache_hits": 0,
                   "recompiles": 0, "lower_ms": 0.0, "compile_ms": 0.0}
            for name in sorted(self.stats):
                st = self.stats[name]
                tot["programs"] += 1
                tot["compiles"] += st.compiles
                tot["cache_hits"] += st.cache_hits
                tot["recompiles"] += st.recompiles
                tot["lower_ms"] += st.lower_ms
                tot["compile_ms"] += st.compile_ms
                if group is not None and st.group != group:
                    continue
                events += [
                    (f"Compile/{name}/compiles", float(st.compiles), step),
                    (f"Compile/{name}/cache_hits", float(st.cache_hits),
                     step),
                    (f"Compile/{name}/recompiles", float(st.recompiles),
                     step),
                    (f"Compile/{name}/lower_ms", st.lower_ms, step),
                    (f"Compile/{name}/compile_ms", st.compile_ms, step),
                    (f"Compile/{name}/analysis_ms", st.analysis_ms, step),
                    (f"Compile/{name}/persistent_cache_hits",
                     float(st.persistent_cache_hits), step),
                    (f"Compile/{name}/persistent_cache_misses",
                     float(st.persistent_cache_misses), step)]
                if st.cost_flops > 0:
                    events.append((f"Compile/{name}/cost_flops",
                                   st.cost_flops, step))
                if st.cost_bytes > 0:
                    events.append((f"Compile/{name}/cost_bytes",
                                   st.cost_bytes, step))
                if st.peak_memory_bytes > 0:
                    events.append((f"Compile/{name}/peak_memory_bytes",
                                   float(st.peak_memory_bytes), step))
                if st.aliased_bytes > 0:    # a program that was given pools
                    events += [
                        (f"Compile/{name}/pool_copy_bytes",
                         float(st.pool_copy_bytes), step),
                        (f"Compile/{name}/aliased_bytes",
                         float(st.aliased_bytes), step)]
                if peak_total and st.cost_flops > 0 \
                        and st.calls_since_drain > 0:
                    mfu = (st.cost_flops * st.calls_since_drain
                           / (window * peak_total))
                    events.append((f"{st.group}/mfu/{name}", mfu, step))
                st.calls_since_drain = 0
            for key in ("programs", "compiles", "cache_hits", "recompiles",
                        "lower_ms", "compile_ms"):
                events.append((f"Compile/total/{key}", float(tot[key]), step))
        for key, value in self.account.totals().items():
            events.append((f"Compile/process/{key}", float(value), step))
        return events
