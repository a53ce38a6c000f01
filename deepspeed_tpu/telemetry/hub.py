"""TelemetryHub — unified per-step observability fan-out.

One rank-0-gated aggregation point for the four telemetry sources the engine
produces, fanned out through ``MonitorMaster`` (TensorBoard / WandB / Comet /
CSV / JSONL backends):

1. **step breakdown** — drains the engine's ``SynchronizedWallClockTimer``
   (fwd/bwd/step/train_batch) into ``Train/Step/{fwd,bwd,step,train_batch}_ms``
   events, gated by ``wall_clock_breakdown``;
2. **comms logger** — per-op ``Comm/<op>/{bytes,count}`` events from
   ``comm.CommsTelemetry`` (trace-time records of explicit AND engine-implied
   collectives), plus the periodic ``log_summary()`` at ``steps_per_print``;
3. **HBM memory** — ``Memory/{bytes_in_use,peak_bytes}`` events from
   ``MemoryTelemetry``, plus the ``memory_breakdown`` per-step log line;
4. **trace sessions** — a ``ProfilerSession`` bracketing the configured step
   window with ``jax.profiler.start_trace``/``stop_trace``.

The engine calls ``step_begin`` before and ``step_end`` after every optimizer
step; both are cheap no-ops on non-zero ranks and when nothing is enabled.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax

from ..comm import comm as dist
from ..utils.logging import log_dist
from ..utils.memory import see_memory_usage
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, BACKWARD_MICRO_TIMER,
                           FORWARD_GLOBAL_TIMER, FORWARD_MICRO_TIMER,
                           STEP_GLOBAL_TIMER, STEP_MICRO_TIMER,
                           TRAIN_BATCH_TIMER, SynchronizedWallClockTimer)
from .anomaly import AnomalyDetector
from .compile import CompileMonitor, peak_flops_total
from .memory import MemoryTelemetry
from .profiler import ProfilerSession
from .trace import Tracer

Event = Tuple[str, float, int]

# (timer name, event suffix) — emission order of the step-breakdown events.
# Every timer the engine can start appears here so each step_end drains (and
# resets) it; an undrained timer's record list would grow without bound.
_STEP_TIMERS = ((FORWARD_GLOBAL_TIMER, "fwd"),
                (BACKWARD_GLOBAL_TIMER, "bwd"),
                (STEP_GLOBAL_TIMER, "step"),
                (TRAIN_BATCH_TIMER, "train_batch"),
                (FORWARD_MICRO_TIMER, "fwd_micro"),
                (BACKWARD_MICRO_TIMER, "bwd_micro"),
                (STEP_MICRO_TIMER, "step_micro"),
                ("eval_batch", "eval"))


class TelemetryHub:
    def __init__(self, config, monitor=None,
                 timers: Optional[SynchronizedWallClockTimer] = None,
                 tput_timer=None):
        self.cfg = config
        self.monitor = monitor
        self.timers = timers if timers is not None else \
            SynchronizedWallClockTimer()
        self.tput_timer = tput_timer
        self.rank0 = jax.process_index() == 0
        self.memory = MemoryTelemetry()
        self.profiler = ProfilerSession(getattr(config, "profiler", None))
        cl = getattr(config, "comms_logger", None)
        if cl is not None and getattr(cl, "enabled", False):
            dist.configure(enabled=True, verbose=cl.verbose,
                           prof_all=cl.prof_all, prof_ops=list(cl.prof_ops),
                           debug=cl.debug)
        self.comms = dist.get_telemetry()
        # span tracer + crash flight recorder (telemetry/trace.py), gated by
        # the telemetry.trace config block; default OFF → zero ring
        # allocation beyond the deque itself. The spans also go to the
        # profiler's timeline, which records them while a profiler session
        # runs, whatever that block says
        self.tracer = Tracer(
            getattr(getattr(config, "telemetry", None), "trace", None),
            name="train", annotate=jax.profiler.TraceAnnotation)
        # Reliability/* counters (checkpoint commits/rollbacks, watchdog
        # trips, preemptions) — counted on every rank for tests/reports,
        # written through the monitor on rank 0
        self.reliability_counts: Dict[str, int] = {}
        # Serving/* gauges (prefix-cache hit tokens, prefill tokens saved,
        # retained-pool occupancy, evictions — docs/serving.md); tracked on
        # every rank for tests/reports, written through the monitor on rank 0
        self.serving_values: Dict[str, float] = {}
        # Train/overlap/* + Train/remat/* gauges (layer-prefetch depth/bytes,
        # per-policy remat saved bytes — docs/performance.md); same contract
        # as serving_values, names validated against telemetry.schema
        self.train_values: Dict[str, float] = {}
        # compile-aware perf explainability (docs/observability.md): the
        # recompilation sentinel + per-program cost model the engines route
        # their jitted entry points through, and the step-time anomaly
        # detector step_end feeds. Both default OFF — a disabled monitor
        # hands back plain jax.jit objects (default program byte-identical)
        # and a disabled detector keeps no state.
        tel = getattr(config, "telemetry", None)
        self.compile = CompileMonitor(getattr(tel, "compile", None),
                                      tracer=self.tracer)
        self.anomaly = AnomalyDetector(getattr(tel, "anomaly", None))
        # Compile/* counters + {Train,Serving}/mfu/* gauges (last drain) and
        # Anomaly/* occurrence counts, for metrics_snapshot and tests
        self.compile_values: Dict[str, float] = {}
        self.anomaly_counts: Dict[str, int] = {}
        # Memory/tier/* gauges (tiered memory subsystem — TieredStore /
        # HostKVPool drains; docs/memory.md). Closed registry in
        # telemetry.schema.MEMORY_TIER_SERIES; same contract as
        # serving_values.
        self.memory_tier_values: Dict[str, float] = {}
        # fleet observability plane (telemetry/fleet.py; docs/
        # observability.md "Fleet observability"): Fleet/* cross-replica
        # rollups and Serving/tenant/* SLO gauges. Same contract as
        # serving_values; metrics_snapshot folds the replica/tenant path
        # segment into a Prometheus label.
        self.fleet_values: Dict[str, float] = {}
        self.tenant_values: Dict[str, float] = {}
        # self-tuning runtime (tuning/tuner.py; docs/tuning.md): Tune/total/*
        # counters and per-knob Tune/knob/<name>/<metric> gauges. Same
        # contract as serving_values; metrics_snapshot folds the knob-name
        # path segment into a Prometheus label.
        self.tune_values: Dict[str, float] = {}
        self._closed = False

    # ------------------------------------------------------------------ #
    def train_event(self, name: str, value: float, step: int = 0) -> None:
        """Fan out one ``Train/<name>`` gauge (overlap-prefetch and remat-
        policy series — ``Train/overlap/*``, ``Train/remat/*``; the closed
        name registry lives in ``telemetry.schema.TRAIN_SERIES``). Last
        sample per series is the current value. Cheap when no monitor
        backend is enabled."""
        if not name.startswith("Train/"):
            name = "Train/" + name
        self.train_values[name] = float(value)
        if self.rank0 and self._monitor_on():
            self.monitor.write_events([(name, float(value), int(step))])

    # ------------------------------------------------------------------ #
    def serving_event(self, name: str, value: float, step: int = 0) -> None:
        """Fan out one ``Serving/<name>`` gauge (v2 serving engine counters,
        e.g. ``Serving/prefix_cache/*``). Unlike ``reliability_event`` these
        carry cumulative/gauge VALUES, so the last sample per series is the
        current total. Cheap when no monitor backend is enabled."""
        if not name.startswith("Serving/"):
            name = "Serving/" + name
        self.serving_values[name] = float(value)
        if self.rank0 and self._monitor_on():
            self.monitor.write_events([(name, float(value), int(step))])

    # ------------------------------------------------------------------ #
    def fleet_event(self, name: str, value: float, step: int = 0) -> None:
        """Fan out one ``Fleet/<name>`` gauge (cross-replica rollups from
        the fleet observability plane — ``Fleet/replica<i>/*``,
        ``Fleet/agg/*``, ``Fleet/outlier/*``; grammar validated by
        ``telemetry.schema``)."""
        if not name.startswith("Fleet/"):
            name = "Fleet/" + name
        self.fleet_values[name] = float(value)
        if self.rank0 and self._monitor_on():
            self.monitor.write_events([(name, float(value), int(step))])

    # ------------------------------------------------------------------ #
    def tenant_event(self, name: str, value: float, step: int = 0) -> None:
        """Fan out one ``Serving/tenant/<slug>/<metric>`` gauge (per-tenant
        SLO accounting — closed metric set in
        ``telemetry.schema.TENANT_METRICS``)."""
        if not name.startswith("Serving/tenant/"):
            name = "Serving/tenant/" + name.removeprefix(
                "Serving/").removeprefix("tenant/")
        self.tenant_values[name] = float(value)
        if self.rank0 and self._monitor_on():
            self.monitor.write_events([(name, float(value), int(step))])

    # ------------------------------------------------------------------ #
    def tune_event(self, name: str, value: float, step: int = 0) -> None:
        """Fan out one ``Tune/<name>`` gauge (the online tuner's trial/
        accept/revert counters and per-knob score deltas —
        ``Tune/total/*`` closed family plus ``Tune/knob/<name>/<metric>``
        over the closed ``telemetry.schema.TUNE_KNOB_METRICS`` set)."""
        if not name.startswith("Tune/"):
            name = "Tune/" + name
        self.tune_values[name] = float(value)
        if self.rank0 and self._monitor_on():
            self.monitor.write_events([(name, float(value), int(step))])

    # ------------------------------------------------------------------ #
    def memory_tier_event(self, name: str, value: float,
                          step: int = 0) -> None:
        """Fan out one ``Memory/tier/<name>`` gauge (tiered memory
        subsystem: per-tier resident/spilled bytes, transfer overlap,
        prefetch hit/miss — closed registry in
        ``telemetry.schema.MEMORY_TIER_SERIES``). Last sample per series is
        the current value."""
        if not name.startswith("Memory/tier/"):
            name = "Memory/tier/" + name.removeprefix("Memory/").removeprefix(
                "tier/")
        self.memory_tier_values[name] = float(value)
        if self.rank0 and self._monitor_on():
            self.monitor.write_events([(name, float(value), int(step))])

    def memory_tier_events(self, store, step: int = 0) -> List[Event]:
        """Drain one TieredStore's ``Memory/tier/*`` snapshot through the
        hub (the engine calls this per tiered step; the serving engine
        publishes its KV-spill gauges via :meth:`memory_tier_event`)."""
        events = list(store.events(step))
        for n, v, _ in events:
            self.memory_tier_values[n] = float(v)
        if self.rank0 and self._monitor_on() and events:
            self.monitor.write_events(events)
        return events

    # ------------------------------------------------------------------ #
    def reliability_event(self, name: str, value: float = 1.0,
                          step: int = 0) -> None:
        """Fan out one ``Reliability/<name>`` event (reliability subsystem:
        saver two-phase commits, watchdog detectors, PreemptionGuard; see
        docs/reliability.md). Cheap when no monitor backend is enabled."""
        if not name.startswith("Reliability/"):
            name = "Reliability/" + name
        self.reliability_counts[name] = \
            self.reliability_counts.get(name, 0) + 1
        if self.rank0 and self._monitor_on():
            self.monitor.write_events([(name, float(value), int(step))])

    # ------------------------------------------------------------------ #
    def compile_event(self, name: str, value: float, step: int = 0) -> None:
        """Fan out one ``Compile/*`` counter or ``{Train,Serving}/mfu/*``
        gauge (CompileMonitor drains — the serving engine publishes through
        here; the training side drains inside ``step_end``)."""
        self.compile_values[name] = float(value)
        if self.rank0 and self._monitor_on():
            self.monitor.write_events([(name, float(value), int(step))])

    # ------------------------------------------------------------------ #
    def _compile_events(self, step: int,
                        step_time_s: Optional[float]) -> List[Event]:
        """Drain the compile monitor: cumulative ``Compile/*`` series plus
        the per-program MFU attribution over the measured step time, the
        ``Train/mfu/total`` rollup, and — when the ThroughputTimer has a
        flops estimate — the ``Train/mfu/headline`` number the attribution
        should sum to."""
        events = self.compile.events(step, window_s=step_time_s,
                                     group="Train")
        if not events:
            return []
        # the analytic cost model doubles as the ThroughputTimer's flops
        # source when the flops profiler didn't run
        if self.tput_timer is not None and \
                not getattr(self.tput_timer, "flops_per_step", None):
            fl = max((st.cost_flops for st in self.compile.stats.values()
                      if st.group == "Train"), default=0.0)
            if fl > 0:
                self.tput_timer.set_flops_per_step(fl)
        mfu_total = sum(v for n, v, _ in events
                        if n.startswith("Train/mfu/"))
        if mfu_total > 0:
            events.append(("Train/mfu/total", mfu_total, step))
        if self.tput_timer is not None and \
                getattr(self.tput_timer, "flops_per_step", None):
            tf = self.tput_timer.avg_tflops_per_sec()
            peak_total = peak_flops_total()
            if tf > 0 and peak_total:
                events.append(("Train/mfu/headline",
                               tf * 1e12 / peak_total, step))
        for n, v, _ in events:
            self.compile_values[n] = float(v)
        return events

    # ------------------------------------------------------------------ #
    def observe_step_anomalies(self, step: int,
                               step_time_s: Optional[float] = None,
                               phase_ms: Optional[Dict[str, float]] = None,
                               host_times: Optional[List[float]] = None,
                               _write: bool = True) -> List[Event]:
        """Feed one step's timings to the anomaly detector; returns (and,
        by default, writes) the ``Anomaly/*`` events any finding produced.
        Fires the flight-recorder dump hook on findings when configured.
        ``host_times`` is the per-host step-time vector (ms) from
        ``_gather_host_step_times`` — gathered by ``step_end`` on every
        process BEFORE its rank-0 gate, since the gather is a collective;
        this method itself never communicates."""
        if not self.anomaly.enabled:
            return []
        findings = []
        if step_time_s:
            findings += self.anomaly.observe("step_time",
                                             float(step_time_s) * 1e3, step)
        for key, ms in (phase_ms or {}).items():
            findings += self.anomaly.observe(f"phase/{key}", ms, step)
        if host_times:
            findings += self.anomaly.observe_hosts(host_times, step)
        if not findings:
            return []
        events: List[Event] = []
        for f in findings:
            name = "Anomaly/" + f.series
            self.anomaly_counts[name] = self.anomaly_counts.get(name, 0) + 1
            events.append((name, float(f.value), step))
            self.tracer.instant("anomaly", cat="anomaly", series=f.series,
                                value=round(float(f.value), 4),
                                detail=f.detail)
            log_dist("anomaly: " + f.detail)
        if self.anomaly.dump_flight_recorder and self.tracer.enabled:
            self.trace_dump("anomaly")
        if _write and self.rank0 and self._monitor_on():
            self.monitor.write_events(events)
        return events

    def _gather_host_step_times(
            self, step_time_s: Optional[float]) -> Optional[List[float]]:
        """Gather every host's step time (ms) for the straggler check.
        ``process_allgather`` is a COLLECTIVE requiring all processes, so
        ``step_end`` calls this on EVERY rank before its rank-0 gate —
        outlier detection itself runs on rank 0 only. Single-host, disabled
        detector, and gather failure all return None; the synthetic path is
        ``anomaly.observe_hosts`` directly."""
        if not step_time_s or not self.anomaly.enabled or \
                self.anomaly.straggler_frac <= 0 or \
                jax.process_count() <= 1:
            return None
        try:
            import numpy as np
            from jax.experimental import multihost_utils

            times = np.asarray(multihost_utils.process_allgather(
                np.float64(float(step_time_s) * 1e3))).ravel()
            return [float(t) for t in times]
        except Exception:
            return None

    # ------------------------------------------------------------------ #
    def trace_dump(self, reason: str) -> Optional[str]:
        """Dump the flight recorder (watchdog violation, crash path);
        returns the path written, or None when tracing is off/empty."""
        if not self.tracer.enabled:
            return None
        return self.tracer.dump(reason)

    def metrics_snapshot(self) -> List[Tuple]:
        """``(event_name, value, kind[, labels])`` rows for the pull-based
        metrics endpoint (telemetry/metrics_server.py): Reliability/* and
        Anomaly/* occurrence counts as counters, Serving/* values as gauges,
        per-program Compile/* counters and MFU gauges carrying a
        ``program=`` label, plus the flight recorder's occupancy."""
        rows: List[Tuple] = []
        for name, count in sorted(self.reliability_counts.items()):
            rows.append((name, float(count), "counter"))
        for name, value in sorted(self.serving_values.items()):
            rows.append((name, float(value), "gauge"))
        for name, value in sorted(self.train_values.items()):
            rows.append((name, float(value), "gauge"))
        for name, value in sorted(self.memory_tier_values.items()):
            rows.append((name, float(value), "gauge"))
        for name, value in sorted(self.fleet_values.items()):
            parts = name.split("/")
            if name.startswith("Fleet/replica") and len(parts) == 3:
                # per-replica series fold onto one metric with a replica
                # label (the Compile/<program> pattern below)
                rows.append((f"Fleet/{parts[2]}", float(value), "gauge",
                             {"replica": parts[1][len("replica"):]}))
            else:
                rows.append((name, float(value), "gauge"))
        for name, value in sorted(self.tenant_values.items()):
            parts = name.split("/")
            if len(parts) == 4:
                rows.append((f"Serving/tenant/{parts[3]}", float(value),
                             "gauge", {"tenant": parts[2]}))
            else:
                rows.append((name, float(value), "gauge"))
        for name, value in sorted(self.tune_values.items()):
            parts = name.split("/")
            if name.startswith("Tune/knob/") and len(parts) == 4:
                # per-knob series fold onto one metric with a knob label
                # (the Compile/<program> pattern below)
                rows.append((f"Tune/{parts[3]}", float(value), "gauge",
                             {"knob": parts[2]}))
            elif name.startswith("Tune/total/"):
                rows.append((name, float(value), "counter"))
            else:
                rows.append((name, float(value), "gauge"))
        for name, count in sorted(self.anomaly_counts.items()):
            rows.append((name, float(count), "counter"))
        for name, value in sorted(self.compile_values.items()):
            parts = name.split("/")
            if name.startswith(("Compile/total/", "Compile/process/")):
                rows.append((name, float(value), "counter"))
            elif name.startswith("Compile/") and len(parts) == 3:
                # per-program series fold onto one metric with a program
                # label — the Prometheus-native shape for open program sets
                rows.append((f"Compile/{parts[2]}", float(value), "counter",
                             {"program": parts[1]}))
            elif len(parts) == 3 and parts[1] == "mfu":
                if parts[2] in ("total", "headline"):
                    # the rollups stay distinct unlabeled metrics
                    # (dstpu_train_mfu_total/_headline) — folded into the
                    # program label they'd double-count any Prometheus
                    # aggregation over the per-program gauges
                    rows.append((name, float(value), "gauge"))
                else:
                    rows.append((f"{parts[0]}/mfu", float(value), "gauge",
                                 {"program": parts[2]}))
            else:
                rows.append((name, float(value), "gauge"))
        if self.tracer.enabled:
            rows.append(("Telemetry/trace/ring_events",
                         float(len(self.tracer)), "gauge"))
        return rows

    # ------------------------------------------------------------------ #
    @property
    def wall_clock_breakdown(self) -> bool:
        return bool(getattr(self.cfg, "wall_clock_breakdown", False))

    def _monitor_on(self) -> bool:
        return self.monitor is not None and \
            bool(getattr(self.monitor, "enabled", False))

    # ------------------------------------------------------------------ #
    def step_begin(self, step: int) -> None:
        """Called with the global step about to execute."""
        if self.rank0:
            self.profiler.maybe_start(step)

    def step_end(self, step: int,
                 step_time_s: Optional[float] = None) -> List[Event]:
        """Called with the global step that just completed. Collects events
        from every enabled source, writes them through the monitor, emits the
        periodic log summaries, and advances the profiler window. Returns the
        events (for tests and callers that want them)."""
        # the straggler gather is a collective over every process — it must
        # run before the rank-0 gate or the first monitored step on a
        # multi-process job deadlocks waiting for the non-zero ranks
        host_times = self._gather_host_step_times(step_time_s)
        if not self.rank0:
            return []
        events: List[Event] = []
        mon_on = self._monitor_on()
        breakdown = self.wall_clock_breakdown
        phase_ms: Dict[str, float] = {}

        if breakdown:
            # drain (and reset) the phase timers whether or not a monitor
            # backend is attached — steady accumulation would skew the next
            # step's numbers. Aux timers (micro/eval) only emit when they
            # actually ran this step; an idle timer left over from another
            # execution path would otherwise spam zero-valued events.
            core = {FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                    STEP_GLOBAL_TIMER, TRAIN_BATCH_TIMER}
            for name, key in _STEP_TIMERS:
                if self.timers.has(name):
                    ms = self.timers(name).elapsed(reset=True) * 1000.0
                    if ms == 0.0 and name not in core:
                        continue
                    events.append((f"Train/Step/{key}_ms", ms, step))
                    phase_ms[key] = ms

        if mon_on or breakdown:
            if self.comms.enabled:
                events += self.comms.events(step)
                events += self._comm_efficiency_events(step, step_time_s)
            events += self.memory.events(step)
            if self.tput_timer is not None and \
                    getattr(self.tput_timer, "flops_per_step", None):
                tf = self.tput_timer.avg_tflops_per_sec()
                if tf > 0:
                    events.append(("Train/Step/tflops", tf, step))

        if self.compile.enabled:
            events += self._compile_events(step, step_time_s)
        if self.anomaly.enabled:
            # written below with the rest of this step's events
            events += self.observe_step_anomalies(step, step_time_s,
                                                  phase_ms,
                                                  host_times=host_times,
                                                  _write=False)

        spp = int(getattr(self.cfg, "steps_per_print", 0) or 0)
        if spp and step % spp == 0:
            if breakdown and events:
                parts = [f"{n.split('/')[-1]}: {v:.2f}"
                         for n, v, _ in events if n.endswith("_ms")]
                if parts:
                    log_dist("time (ms) | " + " | ".join(parts))
            if self.comms.enabled:
                self.comms.log_summary(step_time_s)
        if bool(getattr(self.cfg, "memory_breakdown", False)):
            see_memory_usage(f"after step {step}", force=True)

        if mon_on and events:
            self.monitor.write_events(events)
        self.profiler.maybe_stop(step)
        return events

    # ------------------------------------------------------------------ #
    def _comm_efficiency_events(self, step: int,
                                step_time_s: Optional[float]) -> List[Event]:
        """Comm-efficiency rollup for the overlap engine: total per-step
        algorithmic bytes across every recorded collective, the achieved
        algorithmic bus bandwidth, and — when ``comms_overlap.
        reference_bw_gbps`` names the link speed — the estimated
        UNOVERLAPPED comm fraction (serial comm time / step time; an upper
        bound, since overlapped collectives hide behind compute)."""
        total = self.comms.total_algo_bytes()
        if total <= 0:
            return []
        events: List[Event] = [("Comm/total/algo_bytes", total, step)]
        # per-link-class split (quantized/hierarchical collectives story):
        # DCN-tagged bytes are the scale-out wall hpZ/qwZ/qgZ attack
        events.append(("Comm/total/algo_bytes_dcn",
                       self.comms.total_algo_bytes("dcn"), step))
        events.append(("Comm/total/algo_bytes_ici",
                       self.comms.total_algo_bytes("ici"), step))
        if step_time_s:
            events.append(("Comm/total/busbw_gbps",
                           total / step_time_s / 1e9, step))
            co = getattr(self.cfg, "comms_overlap", None)
            ref_bw = float(getattr(co, "reference_bw_gbps", 0.0) or 0.0)
            if ref_bw > 0:
                serial_s = total / (ref_bw * 1e9)
                frac = min(1.0, serial_s / step_time_s)
                events.append(("Comm/total/est_comm_frac", frac, step))
                if getattr(co, "enabled", False):
                    # overlap-hidden comm fraction: the share of the serial
                    # comm time the step did NOT pay (1 - unoverlapped upper
                    # bound — itself a lower bound on what was hidden)
                    self.train_values["Train/overlap/hidden_comm_frac"] = \
                        1.0 - frac
                    events.append(("Train/overlap/hidden_comm_frac",
                                   1.0 - frac, step))
        return events

    # ------------------------------------------------------------------ #
    def close(self) -> None:
        """Engine shutdown: stop any live trace session, final-dump + close
        the span tracer, flush + close the monitor backends. Idempotent and
        atexit-safe: a second call (e.g. explicit close THEN the monitor's
        atexit hook, possibly after a JSONL rotation swapped file handles)
        is a no-op, and no step may raise out of interpreter shutdown."""
        if self._closed:
            return
        self._closed = True
        try:
            self.profiler.close()
        except Exception:
            pass
        try:
            self.tracer.close()
        except Exception:
            pass
        if self.monitor is not None:
            try:
                self.monitor.close()
            except Exception:
                pass
