"""Structured tracing, latency SLOs, flight recorder, metrics endpoint.

Covers the span tracer (`telemetry/trace.py`), the serving engine's
request-lifecycle instrumentation + TTFT/ITL/queue/e2e percentiles, the
crash-dump paths (watchdog violation, fault injection, preemption, close),
the pull-based Prometheus endpoint, the JSONL per-batch flush, the
telemetry event-schema contract, and the default-OFF zero-event parity.
"""

import json
import os
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.models import llama
from deepspeed_tpu.telemetry.trace import (TraceConfig, Tracer, dump_all,
                                           percentiles)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPORT = os.path.join(REPO, "scripts", "telemetry_report.py")


def _load_events_fn():
    if os.path.join(REPO, "scripts") not in sys.path:
        sys.path.insert(0, os.path.join(REPO, "scripts"))
    from telemetry_report import load_events

    return load_events


def _chrome(path):
    with open(path) as f:
        doc = json.load(f)
    assert "traceEvents" in doc and isinstance(doc["traceEvents"], list)
    return doc


def _check_nesting(doc):
    """Every span's parent (same trace) must time-enclose it, and ids must
    be unique — the 'loads, spans nest, ids consistent' acceptance bit."""
    spans = {e["args"]["span_id"]: e for e in doc["traceEvents"]
             if e["ph"] == "X"}
    assert len(spans) == len([e for e in doc["traceEvents"]
                              if e["ph"] == "X"]), "duplicate span ids"
    for e in spans.values():
        pid = e["args"].get("parent_id")
        if not pid or pid not in spans:  # parent may have rotated out of the
            continue                     # ring — that's flight-recorder law
        p = spans[pid]
        assert p["args"]["trace_id"] == e["args"]["trace_id"]
        slack = 1e3  # µs; host timestamps around async dispatch
        assert p["ts"] - slack <= e["ts"]
        assert e["ts"] + e["dur"] <= p["ts"] + p["dur"] + slack


# --------------------------------------------------------------------------- #
# Tracer unit behavior
# --------------------------------------------------------------------------- #
def test_tracer_spans_nest_and_export(tmp_path):
    tr = Tracer(TraceConfig(enabled=True, ring_size=256, dump_on_crash=False))
    with tr.span("outer", cat="t", step=1):
        with tr.span("inner", cat="t"):
            tr.instant("marker", cat="t", note="hi")
    req = tr.new_trace(label="request:7")
    h = tr.begin("request", cat="serving", trace=req, uid=7)
    tr.complete("prefill", h.t0_ns, h.t0_ns + 1_000, cat="serving",
                trace=req, parent=h.span_id, tokens=32)
    h.end(generated=4)
    out = tmp_path / "trace.json"
    assert tr.export(str(out)) == str(out)
    doc = _chrome(out)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"outer", "inner", "marker", "request", "prefill"} <= names
    _check_nesting(doc)
    inner = next(e for e in doc["traceEvents"] if e["name"] == "inner")
    outer = next(e for e in doc["traceEvents"] if e["name"] == "outer")
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    # explicit-lifecycle span kept its own trace id
    reqs = [e for e in doc["traceEvents"] if e["name"] == "request"]
    assert reqs[0]["args"]["trace_id"] == req
    assert reqs[0]["args"]["generated"] == 4
    tr.close(dump=False)


def test_tracer_ring_is_bounded_and_disabled_is_free(tmp_path):
    tr = Tracer(TraceConfig(enabled=True, ring_size=16, dump_on_crash=False))
    for i in range(100):
        tr.instant("e", i=i)
    assert len(tr) == 16
    # oldest rotated out, newest retained
    assert tr.events()[-1]["args"]["i"] == 99
    tr.close(dump=False)

    off = Tracer(TraceConfig(enabled=False))
    sp = off.span("x")
    assert sp is off.span("y")  # shared null span, no allocation
    assert sp is off.step_span("z", 3)
    with sp:
        off.instant("z")
    off.complete("c", 0, 10)
    assert len(off) == 0 and off.dump("why") is None
    # default-constructed (no config) is also off
    assert not Tracer(None).enabled


def test_dump_all_and_percentiles(tmp_path):
    out = tmp_path / "flight.json"
    tr = Tracer(TraceConfig(enabled=True, ring_size=64,
                            export_path=str(out), dump_on_crash=True))
    tr.instant("before_crash")
    paths = dump_all("unit_test")
    assert str(out) in paths
    assert _chrome(out)["otherData"]["reason"] == "unit_test"
    tr.close(dump=False)
    assert dump_all("after_close") == []  # closed tracer left the registry

    assert percentiles([], (50,)) == {"p50": 0.0}
    vals = list(range(1, 101))
    p = percentiles(vals, (50, 90, 99))
    assert p["p50"] == 50 and p["p90"] == 90 and p["p99"] == 99


def test_trace_config_parses():
    from deepspeed_tpu.runtime.config import parse_config

    cfg = parse_config({"telemetry": {"trace": {
        "enabled": True, "ring_size": 128, "export_path": "/tmp/t.json",
        "dump_on_crash": False}}})
    assert cfg.telemetry.trace.enabled
    assert cfg.telemetry.trace.ring_size == 128
    assert cfg.telemetry.trace.export_path == "/tmp/t.json"
    assert not cfg.telemetry.trace.dump_on_crash
    # default OFF
    assert not parse_config({}).telemetry.trace.enabled


# --------------------------------------------------------------------------- #
# training engine spans
# --------------------------------------------------------------------------- #
def _train_engine(tmp_path, extra=None):
    cfg = llama.LlamaConfig.tiny()
    spec = llama.model_spec(cfg, compute_dtype=jnp.float32)
    config = {"train_batch_size": 8,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
              "steps_per_print": 0}
    config.update(extra or {})
    engine, *_ = dst.initialize(model=spec, config=config)
    tokens = np.random.randint(0, cfg.vocab_size, (8, 33)).astype(np.int32)
    return engine, {"tokens": tokens}


def test_training_trace_spans_and_checkpoint(devices8, tmp_path):
    out = str(tmp_path / "train_trace.json")
    engine, batch = _train_engine(tmp_path, {
        "wall_clock_breakdown": True,
        "telemetry": {"trace": {"enabled": True, "export_path": out,
                                "dump_on_crash": False}}})
    for _ in range(2):
        engine.train_batch(batch)
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    assert engine.telemetry.tracer.export(out)
    doc = _chrome(out)
    names = [e["name"] for e in doc["traceEvents"]]
    for want in ("train/train_batch", "train/fwd", "train/bwd", "train/step",
                 "checkpoint/save", "checkpoint/publish"):
        assert want in names, f"missing span {want}"
    assert names.count("train/train_batch") == 2
    _check_nesting(doc)
    # phase spans nest under their step's train_batch span
    fwd = next(e for e in doc["traceEvents"] if e["name"] == "train/fwd")
    tb = [e for e in doc["traceEvents"] if e["name"] == "train/train_batch"]
    assert fwd["args"]["parent_id"] in {e["args"]["span_id"] for e in tb}
    engine.destroy()


def test_disabled_telemetry_training_zero_events(devices8, tmp_path):
    """Default config: no spans, no latency timers, no monitor events —
    the default-OFF bit-identical contract."""
    engine, batch = _train_engine(tmp_path)
    assert not engine.telemetry.tracer.enabled
    engine.train_batch(batch)
    engine.save_checkpoint(str(tmp_path / "ckpt"))
    assert len(engine.telemetry.tracer) == 0
    assert engine.telemetry.step_end(engine.global_steps) == []
    assert not engine.timers.has("fwd")
    engine.destroy()


def test_watchdog_violation_dumps_flight_recorder(devices8, tmp_path):
    from deepspeed_tpu.testing import faults

    out = str(tmp_path / "wd_trace.json")
    engine, batch = _train_engine(tmp_path, {
        "watchdog": {"enabled": True, "max_skipped_steps": 2,
                     "detect_non_finite": False, "on_violation": "warn"},
        "telemetry": {"trace": {"enabled": True, "export_path": out,
                                "dump_on_crash": False}}})
    engine.train_batch(batch)  # a healthy step lands in the ring first
    with faults.forced_nonfinite(engine, steps=2):
        engine.train_batch(batch)
        engine.train_batch(batch)
    assert engine.watchdog.violations == 1
    assert os.path.exists(out), "violation must dump the flight recorder"
    doc = _chrome(out)
    assert doc["otherData"]["reason"] == "watchdog_skip_limit"
    # the dump contains the steps PRECEDING the violation
    tb = [e for e in doc["traceEvents"] if e["name"] == "train/train_batch"]
    assert len(tb) >= 2
    engine.destroy()


def test_fault_crash_and_preemption_dump_traces(tmp_path):
    from deepspeed_tpu.elasticity.elastic_agent import PreemptionGuard
    from deepspeed_tpu.testing import faults

    out = str(tmp_path / "crash_trace.json")
    tr = Tracer(TraceConfig(enabled=True, export_path=out,
                            dump_on_crash=True))
    tr.instant("work_before_crash")

    class _CE:  # minimal checkpoint-engine stand-in
        def save(self, tree, path, **kw):
            return path

    ce = _CE()
    with pytest.raises(faults.SimulatedCrash):
        with faults.crash_after_save(ce):
            ce.save({}, str(tmp_path / "state"))
    assert os.path.exists(out)
    assert _chrome(out)["otherData"]["reason"] == "fault_crash_after_save"

    os.remove(out)
    guard = PreemptionGuard(save_dir=str(tmp_path / "pg"))
    faults.preempt(guard)
    assert guard.triggered
    assert os.path.exists(out), "preemption must dump the flight recorder"
    assert _chrome(out)["otherData"]["reason"] == "preemption_synthetic"
    tr.close(dump=False)


# --------------------------------------------------------------------------- #
# serving: request lifecycle + latency SLOs
# --------------------------------------------------------------------------- #
def _serving_engine(trace=False, hub=None, split=0, **widths):
    from deepspeed_tpu.inference.engine_v2 import build_engine_v2

    cfg = llama.LlamaConfig.tiny(**widths)
    params = llama.init(cfg, __import__("jax").random.PRNGKey(0))
    config = {"dtype": "float32", "prefill_bucket": 16,
              "split_prefill_chunk": split,
              "ragged": {"max_tracked_sequences": 4,
                         "max_ragged_batch_size": 4,
                         "memory_config_blocks": 64, "block_size": 16}}
    if trace:
        config["trace"] = {"enabled": True, "ring_size": 4096,
                           "dump_on_crash": False}
    return cfg, build_engine_v2(llama, cfg, params, config=config,
                                telemetry_hub=hub)


def test_serving_trace_lifecycle_and_latency(devices8, tmp_path):
    cfg, eng = _serving_engine(trace=True)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (24,)).tolist()
               for _ in range(3)]
    outs = eng.generate(prompts, max_new_tokens=6, steps_per_sync=2)
    assert all(len(o) == 6 for o in outs)
    out = str(tmp_path / "serving_trace.json")
    assert eng.export_trace(out)
    doc = _chrome(out)
    names = [e["name"] for e in doc["traceEvents"]]
    for want in ("request", "queue_wait", "prefill", "decode_quantum",
                 "first_token"):
        assert want in names, f"missing {want}"
    # no instant per decoded token (32 a tick would turn the ring over and
    # push out the spans a crash dump is kept for): every inter-token gap is
    # in the latency summary instead: 3 sequences x 3 quanta of 2 tokens
    # (the 5 tokens after the first, rounded up to whole quanta)
    assert "decode_token" not in names
    assert eng.latency_summary()["itl_ms"]["count"] == 3 * 3 * 2
    _check_nesting(doc)
    # one trace id per request, and its spans share it
    reqs = [e for e in doc["traceEvents"] if e["name"] == "request"]
    assert len(reqs) == 3
    assert len({e["args"]["trace_id"] for e in reqs}) == 3
    for e in doc["traceEvents"]:
        if e["name"] == "queue_wait":
            assert e["args"]["trace_id"] in \
                {r["args"]["trace_id"] for r in reqs}
    # latency SLOs populated with sane orderings
    lat = eng.latency_summary()
    for metric in ("ttft_ms", "itl_ms", "queue_ms", "e2e_ms"):
        assert lat[metric]["count"] > 0, metric
        assert lat[metric]["p50"] <= lat[metric]["p99"]
    assert lat["e2e_ms"]["count"] == 3
    # e2e >= ttft for any request population
    assert lat["e2e_ms"]["p99"] >= lat["ttft_ms"]["p50"]
    assert eng._req == {}  # every lifecycle closed


def test_serving_split_prefill_chunks_traced(devices8):
    cfg, eng = _serving_engine(trace=True, split=16)
    rng = np.random.default_rng(1)
    eng.put_split(0, rng.integers(0, cfg.vocab_size, (40,)).tolist())
    while 0 in eng._pending_prefill:
        eng.step()
    evs = eng.tracer.events()
    chunks = [e for e in evs if e["name"] == "prefill_chunk"]
    assert len(chunks) >= 2  # 40 tokens / 16-chunk → 3 chunks
    assert any(e["args"]["final"] for e in chunks)
    assert len(eng._lat["ttft_ms"]) == 1
    eng.finish(0)
    assert len(eng._lat["e2e_ms"]) == 1


def test_serving_disabled_records_nothing(devices8):
    """Defaults-OFF parity: the serving step path emits zero events and
    starts zero timers/lifecycles."""
    cfg, eng = _serving_engine(trace=False)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (20,)).tolist()
               for _ in range(2)]
    eng.generate(prompts, max_new_tokens=4)
    assert not eng.tracer.enabled
    assert len(eng.tracer) == 0
    assert eng._req == {}
    assert all(not v for v in eng._lat.values())


def test_latency_report_from_jsonl(devices8, tmp_path):
    """Acceptance: generate() through a hub lands Serving/latency/* in the
    JSONL stream and `telemetry_report.py --latency` prints the
    percentiles from the real recorded events."""
    from deepspeed_tpu.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import parse_config
    from deepspeed_tpu.telemetry import TelemetryHub

    rcfg = parse_config({
        "telemetry": {"trace": {"enabled": True, "dump_on_crash": False}},
        "jsonl_monitor": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "slo"}})
    hub = TelemetryHub(rcfg, monitor=MonitorMaster(rcfg))
    assert hub.tracer.enabled
    cfg, eng = _serving_engine(hub=hub)
    assert eng.tracer is hub.tracer  # shared flight recorder
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (20,)).tolist()
               for _ in range(3)]
    eng.generate(prompts, max_new_tokens=5)
    hub.close()
    jsonl = tmp_path / "slo" / "events.jsonl"
    recs = [json.loads(l) for l in open(jsonl)]
    names = {r["name"] for r in recs}
    assert "Serving/latency/ttft_ms_p50" in names
    assert "Serving/latency/e2e_ms_p99" in names
    out = subprocess.run([sys.executable, REPORT, str(jsonl), "--latency"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for token in ("ttft_ms", "itl_ms", "queue_ms", "e2e_ms", "p50", "p99"):
        assert token in out.stdout


# --------------------------------------------------------------------------- #
# metrics endpoint
# --------------------------------------------------------------------------- #
def test_metrics_server_serves_prometheus(tmp_path):
    from deepspeed_tpu.runtime.config import parse_config
    from deepspeed_tpu.telemetry import MetricsServer, TelemetryHub

    hub = TelemetryHub(parse_config(
        {"telemetry": {"trace": {"enabled": True, "dump_on_crash": False}}}))
    hub.reliability_event("checkpoint_saved", step=3)
    hub.reliability_event("checkpoint_saved", step=4)
    hub.serving_event("latency/ttft_ms_p50", 12.5, step=4)
    srv = MetricsServer(hub)
    port = srv.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "dstpu_reliability_checkpoint_saved 2" in body
        assert "dstpu_serving_latency_ttft_ms_p50 12.5" in body
        assert "# TYPE dstpu_reliability_checkpoint_saved counter" in body
        assert "# TYPE dstpu_serving_latency_ttft_ms_p50 gauge" in body
        ok = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10).read()
        assert ok == b"ok\n"
    finally:
        srv.stop()
    hub.close()


# --------------------------------------------------------------------------- #
# schema contract + report/monitor satellites
# --------------------------------------------------------------------------- #
def test_event_schema_on_real_jsonl(devices8, tmp_path):
    """CI schema check: every event name a real run emits matches the
    Group/.../metric convention and steps are monotonic per series."""
    from deepspeed_tpu.telemetry import validate_jsonl_records

    load_events = _load_events_fn()
    engine, batch = _train_engine(tmp_path, {
        "wall_clock_breakdown": True,
        "comms_logger": {"enabled": True},
        "jsonl_monitor": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "schema"}})
    for _ in range(2):
        engine.train_batch(batch)
    engine.telemetry.reliability_event("checkpoint_saved",
                                       step=engine.global_steps)
    engine.destroy()
    from deepspeed_tpu.comm import comm as dist
    dist.configure(enabled=False)
    recs = load_events(str(tmp_path / "schema" / "events.jsonl"))
    assert recs
    assert validate_jsonl_records(recs) == []


def test_event_schema_rejects_bad_events():
    from deepspeed_tpu.telemetry import validate_events

    good = [("Train/Step/fwd_ms", 1.0, 1), ("Train/Step/fwd_ms", 2.0, 2),
            ("Serving/latency/ttft_ms_p50", 3.0, 0),
            ("Reliability/violation/skip_limit", 1.0, 7)]
    assert validate_events(good) == []
    assert validate_events([("loss", 1.0, 1)])          # no group
    assert validate_events([("train/x", 1.0, 1)])       # lowercase group
    assert validate_events([("Train/x", float("nan"), 1)])
    assert validate_events([("Train/x", 1.0, -1)])
    # step going backwards in one series is flagged
    assert validate_events([("Train/x", 1.0, 5), ("Train/x", 1.0, 3)])


def test_jsonl_monitor_flushes_per_batch(tmp_path):
    """Crash-safety satellite: rows are on disk after write_events, BEFORE
    any close()/flush() — and close stays idempotent."""
    from deepspeed_tpu.monitor.monitor import JSONLMonitor

    class Cfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "job"

    mon = JSONLMonitor(Cfg())
    mon.write_events([("Train/loss", 1.5, 1)])
    path = tmp_path / "job" / "events.jsonl"
    assert len(open(path).readlines()) == 1  # no close, no flush — on disk
    mon.write_events([("Train/loss", 1.2, 2)])
    assert len(open(path).readlines()) == 2
    mon.close()
    mon.close()  # idempotent


def test_report_tolerates_truncation_and_all(tmp_path):
    load_events = _load_events_fn()
    path = tmp_path / "events.jsonl"
    with open(path, "w") as f:
        for step in (1, 2):
            f.write(json.dumps({"name": "Train/Step/fwd_ms",
                                "value": 1.0 * step, "step": step,
                                "ts": 0.0}) + "\n")
        f.write(json.dumps({"name": "Serving/latency/ttft_ms_p50",
                            "value": 9.0, "step": 2, "ts": 0.0}) + "\n")
        f.write('{"name": "Train/Step/bwd_ms", "val')  # crash-torn tail
    evs = load_events(str(path))
    assert len(evs) == 3  # torn final line dropped, report survives
    out = subprocess.run([sys.executable, REPORT, str(path), "--all"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for section in ("step time", "comm efficiency", "reliability",
                    "serving", "latency"):
        assert section in out.stdout, f"--all missing section {section!r}"


def test_report_trace_mode(tmp_path):
    tr = Tracer(TraceConfig(enabled=True, dump_on_crash=False))
    with tr.span("train/train_batch", step=1):
        tr.instant("marker")
    trace_path = tmp_path / "t.json"
    tr.export(str(trace_path))
    tr.close(dump=False)
    out = subprocess.run([sys.executable, REPORT, "--trace",
                          str(trace_path)],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "train/train_batch" in out.stdout and "marker" in out.stdout
    # no positional path and no --trace is a usage error
    bad = subprocess.run([sys.executable, REPORT],
                         capture_output=True, text=True, timeout=60)
    assert bad.returncode != 0


# --------------------------------------------------------------------------- #
# the same spans on the profiler's timeline (dstpu:<name>), and the
# device-side names (kernel names, named scopes)
# --------------------------------------------------------------------------- #
from deepspeed_tpu.telemetry import schema  # noqa: E402
from deepspeed_tpu.telemetry.trace import TIMELINE_PREFIX  # noqa: E402


class _Profiled:
    """A profiler session around a block; afterwards ``.spans`` holds the
    program's spans it recorded: per thread line, ``(name, start, end,
    stats)`` sorted by start, names without the ``dstpu:`` prefix."""

    def __init__(self, out_dir):
        self.out_dir = str(out_dir)
        self.spans = []

    def __enter__(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        import glob

        import jax
        from jax.profiler import ProfileData

        jax.profiler.stop_trace()
        path = sorted(glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                mine = [(e.name[len(TIMELINE_PREFIX):], e.start_ns,
                         e.start_ns + e.duration_ns, dict(e.stats))
                        for e in line.events
                        if e.name.startswith(TIMELINE_PREFIX)]
                if mine:
                    self.spans.append(sorted(mine, key=lambda s: (s[1],
                                                                   -s[2])))
        return False

    def named(self, name):
        return [s for line in self.spans for s in line if s[0] == name]

    def children(self, parent):
        """Spans directly inside ``parent`` on its thread's line."""
        line = next(ln for ln in self.spans if parent in ln)
        inside = [s for s in line if s is not parent
                  and parent[1] <= s[1] and s[2] <= parent[2]]
        return [s for s in inside
                if not any(o is not s and o[1] <= s[1] and s[2] <= o[2]
                           for o in inside)]


SCHED_CHILDREN = ["sched_expire", "sched_admit", "sched_preempt_guard",
                  "sched_step_engine", "sched_harvest", "sched_retire"]
TICK_STATS = {"tick", "admitted", "preempted", "live", "queued",
              "prefill_tokens", "decode_seqs", "kv_tokens", "tokens_out",
              "drains"}


def test_scheduler_ticks_on_the_profiler_timeline(devices8, tmp_path):
    """Ring OFF, profiler session ON: one ``dstpu:sched_tick`` per tick with
    its phases inside it in order and the tick's counts as stats; the counts
    add up to the work that was submitted. One program in flight (ISSUE 35):
    a tick's counts describe the program it LAUNCHED, whose tokens the tick
    after returns. Re-stated by ISSUE 37: a prompt of a chunk or less that
    is admitted beside a program in flight (16 and 5 tokens here; the 9 of
    the first tick found none) is no one-shot ``prefill_batch`` under an
    ``engine_drain{cause=put}`` any more - it rides a ``decode_step`` as a
    chunk, and no tick of the run drains."""
    from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                                 ServingScheduler)

    cfg, eng = _serving_engine(trace=False, split=16)
    sched = ServingScheduler(eng, SchedulerConfig())
    rng = np.random.default_rng(3)
    sizes = [(40, 5), (9, 4), (70, 3), (16, 6), (33, 2), (5, 7)]
    handles = [sched.submit(Request(
        prompt=rng.integers(0, cfg.vocab_size, (n,)).tolist(),
        max_new_tokens=m)) for n, m in sizes]
    lane, put_split = [], eng.put_split     # the prompts admitted by chunks
    eng.put_split = lambda uid, prompt, *a: (lane.append(len(prompt)),
                                             put_split(uid, prompt, *a))[1]
    ticks = []   # last_tick and the decode tokens seen from outside, per tick
    with _Profiled(tmp_path) as prof:
        while sched.pending:
            before = [len(h.tokens) for h in handles]
            sched.tick()
            after = [len(h.tokens) for h in handles]
            # a request's first token comes from its prefill, every later
            # one from one sequence-step of a decode batch
            decoded = sum(a - b - (b == 0 and a > 0)
                          for a, b in zip(after, before))
            ticks.append((dict(sched.last_tick), decoded))
    assert len(eng.tracer) == 0                 # the ring stayed off
    assert all(h.done and len(h.tokens) == m
               for h, (_, m) in zip(handles, sizes))
    spans = prof.named("sched_tick")
    assert len(spans) == len(ticks) == sched.stats["ticks"]
    launched = 0     # decode rows of the program the tick before launched
    for span, (last, decoded) in zip(spans, ticks):
        assert [c[0] for c in prof.children(span)] == SCHED_CHILDREN
        stats = {k: int(v) for k, v in span[3].items() if k in TICK_STATS}
        assert stats == last and set(stats) == TICK_STATS
        assert decoded == launched
        launched = last["decode_seqs"]
    assert launched == 0            # the last tick left nothing in flight
    prompt_tokens = sum(n for n, _ in sizes)
    assert sum(t["prefill_tokens"] for t, _ in ticks) == prompt_tokens \
        == sched.stats["prefill_tokens"] == eng.prefill_tokens_written
    assert sum(t["decode_seqs"] for t, _ in ticks) \
        == sum(m - 1 for _, m in sizes) == sched.stats["decode_seq_steps"]
    assert sched.stats["chunk_ticks"] \
        == sum(t["prefill_tokens"] > 0 for t, _ in ticks)
    assert sum(t["tokens_out"] for t, _ in ticks) == sum(m for _, m in sizes)
    # under the engine step: each LAUNCH with its host phases, in order (a
    # chunk beside live decodes rides in their ``decode_step``: one program,
    # one span, the chunk's facts as ``chunk_*``); the host's one sync on a
    # launched program, ``engine_wait`` then ``engine_emit``, lies where it
    # was read - under ``sched_step_engine`` after the next launch
    assert sorted(lane) == [5, 16, 33, 40, 70]
    assert sched.stats["chunked_admissions"] == len(lane)
    chunks = prof.named("prefill_chunk")
    decodes = prof.named("decode_step")
    mixed = [d for d in decodes if int(d[3]["chunk_tokens"])]
    assert mixed and len(mixed) == eng.mixed_steps
    assert sum(int(c[3]["tokens"]) for c in chunks) \
        + sum(int(d[3]["chunk_tokens"]) for d in mixed) == sum(lane)
    assert decodes and all(
        [k[0] for k in prof.children(s)] == ["engine_prep", "engine_dispatch"]
        for s in chunks + decodes)
    assert sum(int(d[3]["batch"]) for d in decodes) \
        == sched.stats["decode_seq_steps"]
    batches = prof.named("prefill_batch")     # the one-shot prompts, in admit
    assert sum(int(b[3]["n"]) for b in batches) == len(sizes) - len(lane)
    assert all([k[0] for k in prof.children(b)] == [
        "engine_prep", "engine_dispatch", "engine_wait", "engine_emit"]
        for b in batches)
    # one read a launched program (a final chunk with nothing live beside
    # it is one) and one a one-shot prefill, each inside a tick's phase:
    # the tick's own under ``sched_step_engine``. No admission read a
    # program in flight, so there is no ``engine_drain`` (ISSUE 37; the span
    # and its cause: tests/test_program_seq.py)
    final_alone = [c for c in chunks
                   if c[3]["final"] in ("True", "1", 1, True)]
    assert not prof.named("engine_drain")
    assert sum(eng.drains.values()) == 0 == sum(t["drains"] for t, _ in ticks)
    reads = [s for span in prof.named("sched_step_engine")
             for s in prof.children(span) if s[0] == "engine_wait"]
    assert len(reads) == len(decodes) + len(final_alone)
    assert len(prof.named("engine_wait")) == len(prof.named("engine_emit")) \
        == len(reads) + len(batches)
    overlapped = [int(d[3]["overlapped"]) for d in decodes]
    assert sum(overlapped) == eng.overlapped_steps > 0
    names = {s[0] for line in prof.spans for s in line}
    assert names <= schema.TRACER_SPANS, names - schema.TRACER_SPANS
    ev = dict((n, v) for n, v, _ in sched.sched_events())
    assert ev["Serving/sched/prefill_tokens"] == prompt_tokens
    assert schema.validate_events(sched.sched_events()) == []


def test_an_admission_beside_a_program_in_flight_reads_nothing(
        devices8, monkeypatch):
    """ISSUE 37, on the ring: a tick that admits a prompt of a chunk or
    less while a program is in flight opens no ``engine_drain`` and no
    ``prefill_batch``; the host reads the device ONCE in it (``np.asarray``
    of a device array inside ``engine_v2``: the tick's own collect of the
    program before), and
    the prompt rides the tick's ``decode_step`` as a first-and-final chunk
    launched over an unread program."""
    from deepspeed_tpu.inference import engine_v2 as engine_mod
    from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                                 ServingScheduler)

    cfg, eng = _serving_engine(trace=True, split=16)
    sched = ServingScheduler(eng, SchedulerConfig())
    rng = np.random.default_rng(37)
    mk = lambda n, m: Request(                              # noqa: E731
        prompt=rng.integers(0, cfg.vocab_size, (n,)).tolist(),
        max_new_tokens=m)
    live = [sched.submit(mk(n, 12)) for n in (20, 7)]
    for _ in range(3):
        sched.tick()
    assert eng.in_flight == 1 and all(h.tokens for h in live)
    short = sched.submit(mk(11, 4))
    seen = len(eng.tracer.events())
    reads = {"asarray": 0}

    class _CountedNumpy:
        def __getattr__(self, name):
            value = getattr(np, name)
            if name != "asarray":
                return value

            def counted(x, *args, **kwargs):
                # a device result, not ``put_split``'s list of prompt ids
                reads["asarray"] += isinstance(x, jax.Array)
                return value(x, *args, **kwargs)
            return counted

    monkeypatch.setattr(engine_mod, "np", _CountedNumpy())
    sched.tick()
    monkeypatch.undo()
    spans = [e for e in eng.tracer.events()[seen:] if e["ph"] == "X"]
    names = [e["name"] for e in spans]
    assert "engine_drain" not in names and "prefill_batch" not in names
    assert names.count("engine_wait") == 1 == reads["asarray"]
    (step,) = [e["args"] for e in spans if e["name"] == "decode_step"]
    assert step["chunk_uid"] == short.uid and step["chunk_tokens"] == 11
    assert step["chunk_ctx"] == 0 and step["chunk_final"]
    assert step["overlapped"] == 1 and step["batch"] == 2
    (tick,) = [e["args"] for e in spans if e["name"] == "sched_tick"]
    assert tick["admitted"] == 1 and tick["drains"] == 0
    assert tick["prefill_tokens"] == 11
    assert sched.stats["chunked_admissions"] == 2 and not short.tokens
    assert sum(eng.drains.values()) == 0
    sched.run()
    assert short.done and len(short.tokens) == 4


def test_train_step_on_the_profiler_timeline(devices8, tmp_path):
    engine, batch = _train_engine(tmp_path, {
        "telemetry": {"compile": {"enabled": True}}})
    assert not engine.telemetry.tracer.enabled
    with _Profiled(tmp_path / "prof") as prof:
        for _ in range(2):
            engine.train_batch(batch)
    assert len(engine.telemetry.tracer) == 0
    steps = prof.named("train_step")
    assert [int(s[3]["step_num"]) for s in steps] == [1, 2]
    for s in steps:
        assert [c[0] for c in prof.children(s)] == [
            "train_shard_batch", "train/train_batch", "train_sync",
            "train_step_end"]
    # the first step compiled: one compile span, under the dispatch, that
    # covers lower + compile and says which program it was
    comp = prof.named("compile")
    assert len(comp) == 1 and comp[0][3]["program"] == "train_step"
    assert comp[0] in prof.children(prof.named("train/train_batch")[0])
    assert (comp[0][2] - comp[0][1]) / 1e6 >= float(
        comp[0][3]["lower_ms"]) + float(comp[0][3]["compile_ms"])
    summary = engine.telemetry.compile.summary()["train_step"]
    assert summary["peak_memory_bytes"] > 0
    names = {s[0] for line in prof.spans for s in line}
    assert names <= schema.TRACER_SPANS, names - schema.TRACER_SPANS
    engine.destroy()


def test_serving_compile_span_says_what_it_copies_of_the_pools(devices8):
    """A serving program is registered with its KV pools: its ``compile``
    span and ``Compile/<program>/`` series carry ``pool_copy_bytes`` and
    ``aliased_bytes`` (values are the chip compiler's to give:
    tests/test_chip_compile.py holds them to 0 and the pools' bytes)."""
    from deepspeed_tpu.inference.engine_v2 import build_engine_v2
    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    cfg = llama.LlamaConfig.tiny()
    eng = build_engine_v2(
        llama, cfg, llama.init(cfg, __import__("jax").random.PRNGKey(0)),
        config={"dtype": "float32", "prefill_bucket": 16,
                "compile_monitor": {"enabled": True},
                "trace": {"enabled": True, "ring_size": 4096,
                          "dump_on_crash": False},
                "ragged": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 4,
                           "memory_config_blocks": 64, "block_size": 16}})
    eng.put(0, list(range(1, 12)))
    eng.step()
    spans = {e["args"]["program"]: e["args"] for e in eng.tracer.events()
             if e["ph"] == "X" and e["name"] == "compile"}
    assert {"prefill", "decode"} <= set(spans)
    for program in ("prefill", "decode"):
        assert spans[program]["pool_copy_bytes"] >= 0
        assert spans[program]["aliased_bytes"] >= 0
        assert {"pool_copy_bytes", "aliased_bytes"} \
            <= set(eng.compile_monitor.summary()[program])
    events = eng.compile_monitor.events(group="Serving")
    assert schema.validate_events(events) == []
    # the counter itself, on a program's text: pool-shaped results of the
    # opcodes that re-house a pool count, anything else does not
    pool = (2, 64, 2, 16, 16)
    text = "\n".join((
        "  %copy.1 = f32[2,64,2,16,16]{4,3,2,1,0} copy(%p)",
        "  %ds.2 = f32[1,64,2,16,16]{4,3,2,1,0} dynamic-slice(%p, %i)",
        "  ROOT %f.3 = f32[64,2,16,16]{3,2,1,0} fusion(%p), kind=kLoop",
        "  %f.4 = f32[64,2,16,16]{3,2,1,0} fusion(%p), kind=kOutput",
        "  %w.5 = f32[2,64,2,16,16]{4,3,2,1,0} custom-call(%p), "
        'custom_call_target="tpu_custom_call"',
        "  %c.6 = f32[4,16]{1,0} copy(%q)"))
    one_layer = 64 * 2 * 16 * 16 * 4
    assert pool_copy_bytes(text, [pool]) == 4 * one_layer


def test_ring_and_timeline_share_one_call_site(devices8, tmp_path):
    """Ring ON and a profiler session: the same spans land in both, and the
    ring's spans are all registered names."""
    cfg, eng = _serving_engine(trace=True, split=16)
    rng = np.random.default_rng(1)
    with _Profiled(tmp_path) as prof:
        eng.put_split(0, rng.integers(0, cfg.vocab_size, (40,)).tolist())
        while 0 in eng._pending_prefill:
            eng.step()
        eng.step()
        eng.finish(0)
    ring = [e for e in eng.tracer.events() if e["ph"] == "X"]
    assert {e["name"] for e in ring} <= schema.TRACER_SPANS
    assert {e["name"] for e in eng.tracer.events() if e["ph"] == "i"} \
        <= schema.TRACER_INSTANTS
    for name in ("prefill_chunk", "decode_step", "engine_prep",
                 "engine_dispatch", "engine_wait", "engine_emit"):
        assert len(prof.named(name)) \
            == sum(e["name"] == name for e in ring) > 0, name
    chunk = next(e for e in ring if e["name"] == "prefill_chunk")
    assert chunk["args"]["tokens"] == 16 and chunk["args"]["ctx"] == 0
    assert eng.last_step == {"prefill_tokens": 0, "prefill_kv_tokens": 0,
                             "decode_seqs": 1, "kv_tokens": 41,
                             "attn_tiles_live": 4, "attn_tiles_grid": 4,
                             "attn_live_tile_share": 1.0}


def test_prefill_spans_say_what_the_kernel_walked(devices8):
    """``prefill_chunk`` / ``prefill_batch`` carry the blocks their longest
    row attends over beside the table's width, and ``last_step`` the KV
    positions of the tick's prefill calls: its admissions' one-shot
    prefills and the step's chunk."""
    cfg, eng = _serving_engine(trace=True, split=16)
    bs, width = eng.state.block_size, eng.state.max_blocks_per_seq
    rng = np.random.default_rng(5)
    prompt = lambda n: rng.integers(0, cfg.vocab_size, (n,)).tolist()
    eng.put_split(0, prompt(45))
    admissions = [[(1, 9), (2, 14)], [], [(3, 16)], []]   # per tick
    seen = 0
    for tick, admitted in enumerate(admissions):
        if admitted:
            eng.put_many([(uid, prompt(n)) for uid, n in admitted])
        eng.step()
        spans = [e for e in eng.tracer.events()[seen:] if e["ph"] == "X"]
        seen = len(eng.tracer.events())
        chunks = [e["args"] for e in spans if e["name"] == "prefill_chunk"]
        batches = [e["args"] for e in spans if e["name"] == "prefill_batch"]
        assert len(batches) == bool(admitted)
        assert len(chunks) == (tick < 3)       # 45 tokens: 16 + 16 + 13
        for a in chunks:
            assert a["kv_blocks"] == -(-(a["ctx"] + a["tokens"]) // bs)
        for a in batches:
            assert a["kv_blocks"] == -(-max(n for _, n in admitted) // bs)
        for a in chunks + batches:
            assert 1 <= a["kv_blocks"] <= a["table_blocks"] == width
        assert eng.last_step["prefill_kv_tokens"] \
            == sum(a["ctx"] + a["tokens"] for a in chunks) \
            + sum(n for _, n in admitted)
    assert eng.last_step["prefill_kv_tokens"] == 0


@pytest.mark.parametrize("head", [128, 16])
def test_decode_span_says_how_much_of_the_grid_is_live(devices8,
                                                       monkeypatch, head):
    """``decode_step`` (and ``last_step``) carry, for one layer's
    ``paged_decode`` call, the KV tiles that hold live context, the tiles
    the walk takes and their ratio, from the kernel's own tile sizes and the
    slots' lengths. Heads of a whole lane tile: the walk fetches its own
    pages, each slot walks to its own end and the two counts are one - free
    slots take the one tile that writes their row. Narrower heads keep the
    grid of ``BlockSpec`` pages: every slot walks as far as the longest."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_KV_TOKENS", 32)   # two 16-token pages a tile
    monkeypatch.setattr(pa, "_DECODE_KV_TOKENS", 32)
    cfg, eng = _serving_engine(trace=True, hidden_size=4 * head)
    nkv, hd = cfg.num_kv_heads, cfg.head_size
    pages, heads, n_kv = pa._decode_tiles(
        nkv, cfg.num_heads // nkv, hd, 16, eng.state.max_blocks_per_seq, 4,
        False)
    assert hd == head and (pages, heads) == (2, nkv) and n_kv > 2
    own = pa._fetches_pages(hd, False)
    assert own == (head == 128)
    rng = np.random.default_rng(2)
    eng.put_many([(uid, rng.integers(0, cfg.vocab_size, (n,)).tolist())
                  for uid, n in ((0, 70), (1, 9))])
    for _ in range(3):
        lens = eng._slot_lens.copy()        # as the dispatch sees them
        eng.step()
        args = [e["args"] for e in eng.tracer.events()
                if e["ph"] == "X" and e["name"] == "decode_step"][-1]
        tiles = lens // 32 + 1              # the current token included
        assert sorted(tiles) == [1, 1, 1, 3]   # two free slots: a tile each
        assert args["attn_tiles_live"] == tiles.sum() == 6
        assert args["attn_tiles_grid"] == (6 if own else 4 * tiles.max())
        assert args["attn_live_tile_share"] == (1.0 if own else 0.5)
        assert {k: eng.last_step[k] for k in args if k.startswith("attn_")} \
            == {k: v for k, v in args.items() if k.startswith("attn_")}
    assert pa.decode_tile_counts(lens, cfg.num_heads, eng.cache["k"].shape, 4,
                                 eng.state.max_blocks_per_seq, False) \
        == (6, 6 if own else 12)
    # a wider tile where the budget and the table hold it: the counts follow
    monkeypatch.setattr(pa, "_DECODE_KV_TOKENS", 64)
    assert pa.decode_tile_counts(lens, cfg.num_heads, eng.cache["k"].shape, 4,
                                 eng.state.max_blocks_per_seq, False) \
        == ((5, 5) if own else (6, 12))


# the multi-token walk's counter (ISSUE 45): sequences' (contexts, real rows),
# rows a sequence, table blocks, window -> (live, taken, table-wide) steps A
# KV HEAD, counted by hand at 32-token KV tiles and 16-row query tiles
HAND_COUNTED_WALKS = {
    # one tile of rows: context 40 + 13 real rows reach tiles 0 and 1 of 4
    "one_chunk": (([40], [13]), 16, 8, None, (2, 2, 4)),
    # the dummy takes the longest's two steps and none of them is live
    "batched_with_a_dummy": (([40, 0], [13, 0]), 16, 8, None, (2, 4, 8)),
    # three query tiles at context 40: their last rows end at 56, 72 and 80,
    # so they hold 2, 3 and 3 live tiles and each walks the longest's 3 of 8
    "three_query_tiles": (([40], [40]), 40, 16, None, (8, 9, 24)),
    # a window of 20 behind position 70 starts in tile 1: tile 0 is dead at
    # the FRONT, and the grid still starts there
    "window_starts_in_tile_1": (([70], [16]), 16, 8, 20, (2, 3, 4)),
    # a key short of two wide (64-key) tiles: four narrow tiles, all of the
    # table's
    "a_key_short_of_two_wide_tiles": (([111], [16]), 16, 8, None, (4, 4, 4)),
    # ISSUE 48, from 128 keys on a walk takes 64-key tiles: a context that
    # fills its table walks all of it, in two steps
    "context_fills_the_table": (([112], [16]), 16, 8, None, (2, 2, 2)),
    "two_wide_tiles_of_the_tables_four": (([112], [16]), 16, 16, None,
                                          (2, 2, 4)),
    # the longest sequence decides for the batch: the short one and the
    # dummy take its three wide steps, the short one's first is live
    "the_longest_decides_for_the_batch": (([150, 10, 0], [16, 16, 0]), 16, 16,
                                          None, (4, 9, 12)),
    # a window of 20 behind position 200: wide tiles 0 and 1 are dead at the
    # front, 2 and 3 live
    "wide_tiles_under_a_window": (([200], [16]), 16, 16, 20, (2, 4, 4)),
    # three query tiles at context 140 end at 156, 172 and 180: three wide
    # tiles each
    "wide_tiles_three_query_tiles": (([140], [40]), 40, 16, None, (9, 9, 12)),
}


# ISSUE 62, the same walks where the walk fetches its own pages (heads of
# 128): ONE tile width whatever the length - 64 keys where the table holds
# one, 32 where it is 4 blocks - and each (sequence, query tile) walks from
# its own first live tile to its own last, so taken == live: (live, table-wide)
HAND_COUNTED_OWN_PAGES = {
    "one_chunk": (1, 2),            # 53 keys: one 64-key tile of the table's 2
    "batched_with_a_dummy": (1, 4),     # the dummy walks nothing
    "three_query_tiles": (5, 12),       # rows end at 56, 72, 80: 1 + 2 + 2
    "window_starts_in_tile_1": (2, 2),  # 51..86: tiles 0 and 1
    "a_key_short_of_two_wide_tiles": (2, 2),
    "context_fills_the_table": (2, 2),
    "two_wide_tiles_of_the_tables_four": (2, 4),
    "the_longest_decides_for_the_batch": (3 + 1, 12),   # no longer: 3, 1, 0
    "wide_tiles_under_a_window": (2, 4),    # 181..216: tiles 2 and 3
    "wide_tiles_three_query_tiles": (9, 12),
}


@pytest.mark.parametrize("hd", [64, 128])
@pytest.mark.parametrize("case", sorted(HAND_COUNTED_WALKS))
def test_prefill_tile_counts_are_the_hand_count(case, hd, monkeypatch):
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_KV_TOKENS", 32)   # two 16-token pages a tile
    monkeypatch.setattr(pa, "_WIDE_KV_TOKENS", 64)  # four, from 128 keys on
    monkeypatch.setattr(pa, "_Q_ROWS", 32)      # 16 tokens x a group of 2
    (ctx, lens), t, table, window, want = HAND_COUNTED_WALKS[case]
    nkv = 3
    counts = pa.prefill_tile_counts(ctx, lens, t, 2 * nkv, (nkv, 16, hd),
                                    table, window)
    pages = pa.prefill_kv_pages(ctx, lens, t, 2 * nkv, (nkv, 16, hd), table)
    if pa._fetches_pages(hd, False):
        live, wide = HAND_COUNTED_OWN_PAGES[case]
        assert counts == (nkv * live, nkv * live, nkv * wide)
        assert pages == 4
        return
    assert counts == tuple(nkv * n for n in want)
    longest = max(c + n for c, n in zip(ctx, lens))
    assert pages == (4 if longest >= 128 else 2)


def _chunk_tile_spans(eng, prompt_tokens):
    """The ``chunk_attn_tiles_*`` and ``chunk_attn_kv_tile`` of every mixed
    ``decode_step`` and every ``prefill_chunk`` while a split prompt enters
    beside a live stream."""
    rng = np.random.default_rng(1)
    vocab = eng.family.cfg.vocab_size
    eng.put(1, rng.integers(1, vocab, (9,)).tolist())
    eng.put_split(0, rng.integers(1, vocab, (prompt_tokens,)).tolist())
    while eng.state.seqs[0].prefilling:
        eng.step()
    eng.step()
    spans = [e for e in eng.tracer.events() if e["ph"] == "X"]
    keys = ["chunk_attn_tiles_" + k for k in ("live", "grid", "table")] \
        + ["chunk_attn_kv_tile"]
    mixed = [e["args"] for e in spans if e["name"] == "decode_step"
             and e["args"]["chunk_tokens"]]
    chunks = [e["args"] for e in spans if e["name"] == "prefill_chunk"]
    plain = [e["args"] for e in spans if e["name"] == "decode_step"
             and not e["args"]["chunk_tokens"]]
    assert mixed and plain and not any(k in a for a in plain for k in keys)
    for a in mixed + chunks:
        assert all(type(a[k]) is int for k in keys)     # numbers, no lists
    return ([(a["chunk_ctx"], a["chunk_tokens"]) + tuple(a[k] for k in keys)
             for a in mixed],
            [(a["ctx"], a["tokens"]) + tuple(a[k] for k in keys)
             for a in chunks])


def test_chunk_spans_say_how_far_the_prefill_walk_went(devices8,
                                                       monkeypatch):
    """A mixed tick's ``decode_step`` and the request's ``prefill_chunk``
    carry, for ONE layer's ``paged_prefill`` call, the grid steps that hold
    context the chunk attends, the steps the grid takes and the steps a
    grid as wide as the block table would take - host integers of the
    launch, from the kernel's own tile sizes."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_KV_TOKENS", 32)   # two 16-token pages a tile
    cfg, eng = _serving_engine(trace=True, split=16)
    assert (cfg.num_kv_heads, eng.state.max_blocks_per_seq) == (2, 8)
    mixed, chunks = _chunk_tile_spans(eng, 60)
    # 2 KV heads x (1, 1, 2, 2 of the table's 4 tiles): the walk ends with
    # the chunk's last row; no walk is long, every one at the 32-key tile
    assert mixed == chunks == [(0, 16, 2, 2, 8, 32), (16, 16, 2, 2, 8, 32),
                               (32, 16, 4, 4, 8, 32), (48, 12, 4, 4, 8, 32)]


def test_chunk_spans_say_which_kv_tile_the_walk_took(devices8, monkeypatch):
    """``chunk_attn_kv_tile`` on every mixed ``decode_step`` and every
    ``prefill_chunk``: the KV tokens a grid step of the chunk's walk takes,
    by the program's own rule (``paged_attention._takes_wide``, which
    ``tests/test_pallas_kernels.py`` holds the program's ``cond`` to) - the
    narrow tile until the chunk's last row reaches two wide tiles, the wide
    one from there on - and the tile counts are counted at that tile."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_KV_TOKENS", 16)       # one 16-token page
    monkeypatch.setattr(pa, "_WIDE_KV_TOKENS", 32)  # two, from 64 keys on
    cfg, eng = _serving_engine(trace=True, split=16)
    nkv, table = cfg.num_kv_heads, eng.state.max_blocks_per_seq
    assert (nkv, table) == (2, 8)
    mixed, chunks = _chunk_tile_spans(eng, 100)
    assert mixed == chunks and [c[:2] for c in chunks] == [
        (16 * i, 16) for i in range(6)] + [(96, 4)]
    assert [c[-1] for c in chunks] == [16, 16, 16, 32, 32, 32, 32]
    for ctx, n, live, grid, wide, tile in chunks:
        assert tile == 16 * pa.prefill_kv_pages(
            [ctx], [n], 16, cfg.num_heads, eng.cache["k"].shape, table,
            itemsize=4)
        steps = -(-(ctx + n) // tile)       # one query tile, all of it live
        assert (live, grid, wide) == (nkv * steps, nkv * steps,
                                      nkv * 128 // tile)


def test_chunk_spans_sum_both_kinds_walks_over_their_layers(monkeypatch):
    """A family with two table kinds: one call a kind - the window kind's at
    the context counted from the blocks it gave back, through its own short
    table, under its window -, each times the kind's layers, summed into
    plain numbers."""
    from deepspeed_tpu.inference.engine_v2 import build_engine_v2
    from deepspeed_tpu.models import cohere2_moe
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_KV_TOKENS", 8)    # two 4-token pages a tile
    cfg = cohere2_moe.Cohere2MoeConfig.tiny()
    eng = build_engine_v2(
        cohere2_moe, cfg, cohere2_moe.init(cfg, jax.random.PRNGKey(0)),
        config={"dtype": "float32", "prefill_bucket": 8,
                "split_prefill_chunk": 8,
                "trace": {"enabled": True, "ring_size": 4096,
                          "dump_on_crash": False},
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 70, "block_size": 4}})
    kind, = eng.state.window_kinds
    full, window = eng.cache["k"].shape, eng.cache["k_window"].shape
    assert (full[0], window[0], kind.window, kind.blocks_per_seq,
            cfg.num_kv_heads) == (1, 3, 16, 7, 2)
    mixed, chunks = _chunk_tile_spans(eng, 51)
    assert mixed == chunks and [c[:2] for c in chunks] == [
        (8 * i, 8) for i in range(6)] + [(48, 3)]
    table = eng.state.max_blocks_per_seq
    for ctx, n, *got, tile in chunks:
        given = max(0, ctx - 16 + 1) // 4 * 4   # tokens the kind gave back
        want = np.asarray(pa.prefill_tile_counts(
            [ctx], [n], 8, cfg.num_heads, full, table, itemsize=4)) \
            + 3 * np.asarray(pa.prefill_tile_counts(
                [ctx - given], [n], 8, cfg.num_heads, window, 7, 16,
                itemsize=4))
        assert got == want.tolist()
        assert tile == 8    # neither kind's walk is long: the widest is narrow
    # by hand, the last chunk (context 48, 3 real rows), 2 KV heads: the
    # full layer holds 7 live tiles of 8 tokens, takes 7 and its table has
    # max_blocks / 2; a window layer, 8 blocks given back, sits at context
    # 16 of a 7-block table: tiles 0-2 live, 3 taken, 4 table-wide
    assert chunks[-1][2:5] == (2 * (7 + 3 * 3), 2 * (7 + 3 * 3),
                               2 * (-(-table // 2) + 3 * 4))


def test_chunk_spans_count_a_learned_selections_masked_walk():
    """Under a learned selection (Keye's family at heads of 128 lanes and
    32-token blocks) the chunk's walk is ``paged_sparse_prefill``, which
    fetches its own pages: every mixed ``decode_step`` carries the counts
    of ONE layer's call at that walk's own tile - 1 024 keys where the
    table holds one -, and ``chunk_attn_tiles_grid ==
    chunk_attn_tiles_live``: the reading that says the mechanism engaged."""
    from test_keye import TINY, family

    from deepspeed_tpu.inference.engine_v2 import build_engine_v2
    from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse

    hf = {**TINY, "head_dim": 128, "max_position_embeddings": 1024}
    cfg = family.build_cfg(hf, drop_tokens=False)
    eng = build_engine_v2(
        family.module(), cfg, family.init(cfg, jax.random.PRNGKey(0)),
        config={"dtype": "float32", "prefill_bucket": 16,
                "split_prefill_chunk": 16,
                "trace": {"enabled": True, "ring_size": 4096,
                          "dump_on_crash": False},
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 40, "block_size": 32}})
    nkv, table = cfg.num_kv_heads, eng.state.max_blocks_per_seq
    assert (nkv, table, eng.cache["k"].shape[-1]) == (2, 32, 128)
    assert sparse.prefill_pages(16, cfg.num_heads, eng.cache["k"].shape,
                                table, 4) * 32 == 1024
    mixed, chunks = _chunk_tile_spans(eng, 40)
    # one query tile, one 1 024-key tile of the table's one, two KV heads
    assert mixed == chunks == [(0, 16, 2, 2, 2, 1024), (16, 16, 2, 2, 2, 1024),
                               (32, 8, 2, 2, 2, 1024)]


def _kernel_cases():
    """One tiny call into each ``pallas_call`` site -> the kernel's name."""
    import jax

    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.pallas import paged_sparse_attention as ps
    from deepspeed_tpu.ops.pallas import delta, retention, ssm, ssm_scan
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    from deepspeed_tpu.ops.pallas.norms import (layer_norm_pallas,
                                                rms_norm_pallas)
    from deepspeed_tpu.ops.pallas.quantize import (dequantize_int8_pallas,
                                                   quantize_int8_pallas)
    from deepspeed_tpu.ops.sparse_attention import blocksparse_attention

    f32 = jnp.float32
    qkv = [jnp.ones((1, 128, 2, 32), f32)] * 3
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True)
    flash_grad = jax.grad(lambda *a: jnp.sum(flash(*a)), argnums=(0, 1, 2))
    layout = np.tril(np.ones((8, 8), bool))
    sparse = lambda q, k, v: blocksparse_attention(
        q, k, v, layout, 16, causal=True, use_kernel=True)
    sparse_bwd = jax.grad(lambda *a: jnp.sum(sparse(*a)), argnums=(0, 1, 2))

    pool = jnp.ones((8, 2, 16, 32), f32)
    tables, lens = jnp.zeros((2, 4), jnp.int32), jnp.ones((2,), jnp.int32)
    x, w = jnp.ones((16, 128), f32), jnp.ones((128,), f32)
    q8, scales = jnp.ones((4, 256), jnp.int8), jnp.ones((4,), f32)
    state, rows = jnp.ones((2, 4, 24, 128), f32), jnp.asarray([0, 2])
    # a learned token selection's five (ops/pallas/paged_sparse_attention.py,
    # ISSUE 38): an index pool of 64-wide keys, two tokens a row
    ipool = jnp.ones(ps.index_pool_shape(1, 8, 16, 64), f32)
    idx, thr = jnp.ones((2, 16, 64), f32), jnp.zeros((2, 16), jnp.int32)
    return {
        "flash_fwd": (flash, qkv),
        "flash_bwd_dq": (flash_grad, qkv),
        "flash_bwd_dkv": (flash_grad, qkv),
        "sparse_flash_fwd": (sparse, qkv),
        "sparse_flash_bwd_dq": (sparse_bwd, qkv),
        "sparse_flash_bwd_dkv": (sparse_bwd, qkv),
        "paged_decode": (pa.paged_decode_attention,
                         [jnp.ones((2, 4, 32), f32), pool, pool, tables,
                          lens]),
        "paged_prefill": (pa.paged_prefill_attention,
                          [jnp.ones((2, 3, 4, 32), f32), pool, pool,
                           tables, lens]),
        # the same name's second call site: heads of 128 fetch their own
        # pages (ISSUE 62; heads of 32 walk the grid of BlockSpec pages)
        "paged_prefill:own_pages": (
            pa.paged_prefill_attention,
            [jnp.ones((2, 3, 4, 128), f32), jnp.ones((8, 2, 16, 128), f32),
             jnp.ones((8, 2, 16, 128), f32), tables, lens]),
        "paged_kv_write": (
            lambda k, kp, vp, bt, n: pa.paged_kv_write(k, k, kp, vp, bt, n,
                                                       n)[:2],
            [jnp.ones((2, 3, 2, 32), f32), pool, pool, tables, lens]),
        "rms_norm_fwd": (rms_norm_pallas, [x, w]),
        "layer_norm_fwd": (layer_norm_pallas, [x, w, w]),
        "quantize_int8": (lambda a: quantize_int8_pallas(a, group_size=256),
                          [jnp.ones((1024,), f32)]),
        "dequantize_int8": (lambda q, s: dequantize_int8_pallas(
            q, s, group_size=256), [q8, scales]),
        # the per-slot state pool's three (ops/pallas/ssm.py, ISSUE 31)
        "state_rows_read": (lambda p, r: ssm.state_rows_read(
            p, 1, r, (16, 8, 128)), [state, rows]),
        "state_rows_write": (lambda p, r, new: ssm.state_rows_write(
            p, 1, r, new, (16, 8, 128)), [state, rows,
                                          jnp.ones((2, 8, 128), f32)]),
        "ssm_decode_update": (
            lambda p, r, v, bc: ssm.ssm_decode_update(p, 1, r, r == 0, v, v,
                                                      bc, bc)[1],
            [state, rows, jnp.ones((2, 128), f32), jnp.ones((2, 16), f32)]),
        # a segment of many tokens (ops/pallas/ssm_scan.py, ISSUE 58): 16
        # tokens of two rows, 4 heads of 32 channels, N = 128
        "ssm_chunk_scan": (
            lambda p, r, x, bc: ssm_scan.ssm_chunk_scan(
                p, 1, r, r == 0, x, x[..., 0], -jnp.ones((4,), f32), bc, bc,
                8)[0],
            [jnp.ones((2, 3, 136, 128), f32), rows,
             jnp.ones((2, 16, 4, 32), f32), jnp.ones((2, 16, 128), f32)]),
        # a retention layer's two (ops/pallas/retention.py, ISSUE 55): a pool
        # of two slots at two key-value heads of 16, two query heads each
        "retention_decode_update": (
            lambda p, r, q: retention.retention_decode_update(
                p, 1, r, r == 0, q, q[:, :2], q[:, :2], q[:, :2, 0])[1],
            [jnp.ones((2, 3, 40, 192), f32), rows, jnp.ones((2, 4, 16), f32)]),
        "retention_chunk": (
            lambda p, r, q: retention.retention_chunk(
                p, 1, r, r == 0, q, q[:, :, :2], q[:, :, :2],
                q[:, :, :2, 0], tile=8)[1],
            [jnp.ones((2, 3, 40, 192), f32), rows,
             jnp.ones((2, 12, 4, 16), f32)]),
        # a delta-rule layer's single-token one (ops/pallas/delta.py, ISSUE
        # 57):
        # a pool of two slots at four heads of 16 x 16 over a tail
        "delta_decode_update": (
            lambda p, r, q: delta.delta_decode_update(
                p, 1, r, r == 0, q, q, q, -q, q[:, :, 0])[1],
            [jnp.ones((2, 3, 32, 64), f32), rows, jnp.ones((2, 4, 16), f32)]),
        # and its multi-token kernel (ops/pallas/delta_chunk.py, ISSUE 60),
        # at heads it tiles: two of 128 x 128, four tokens a row
        "delta_chunk": (
            lambda p, r, q: delta.delta_chunk(
                p, 1, r, r == 0, q, q, q, -q, q[..., 0])[1],
            [jnp.ones((2, 3, 144, 256), f32), rows,
             jnp.ones((2, 4, 2, 128), f32)]),
        "paged_index_write": (
            lambda k, p, bt, n: ps.paged_index_write(k, p, bt, n, n, layer=0),
            [jnp.ones((2, 3, 64), f32), ipool, tables, lens]),
        "paged_index_scores": (
            lambda q, w_, p, bt, n: ps.paged_index_scores(q, w_, p, bt, n, n,
                                                          layer=0),
            [jnp.ones((2, 3, 4, 64), f32), jnp.ones((2, 3, 4), f32), ipool,
             tables, lens]),
        "paged_sparse_select": (
            lambda s_, q: ps.paged_sparse_select(s_, q, topk=8)[0],
            [jnp.ones((8, 128), f32), jnp.arange(8, dtype=jnp.int32)]),
        "paged_sparse_decode": (
            lambda q, i, t_, bt, n: ps.paged_sparse_decode_attention(
                q, pool, pool, i, t_, t_, bt, n),
            [jnp.ones((2, 4, 32), f32), idx[:, :8], thr[:, 0], tables,
             lens]),
        "paged_sparse_prefill": (
            lambda q, i, t_, bt, n: ps.paged_sparse_prefill_attention(
                q, pool, pool, i, t_, t_, bt, n, n),
            [jnp.ones((2, 3, 4, 32), f32), idx, thr, tables, lens]),
        # the same name's second call site: heads of 128 fetch their own
        # pages (ISSUE 63; heads of 32 walk the grid of BlockSpec pages)
        "paged_sparse_prefill:own_pages": (
            lambda q, k, i, t_, bt, n: ps.paged_sparse_prefill_attention(
                q, k, k, i, t_, t_, bt, n, n),
            [jnp.ones((2, 3, 4, 128), f32), jnp.ones((8, 2, 16, 128), f32),
             idx, thr, tables, lens]),
        "moe_grouped_matmul": (
            lambda x, wg, wd, e, n: gm.moe_grouped_matmul(
                x, wg, wg, wd, e, e + 16, n, 1, tile=16),
            [jnp.ones((32, 128), f32), jnp.ones((2, 3, 128, 128), f32),
             jnp.ones((2, 3, 128, 128), f32),
             jnp.zeros((2,), jnp.int32), jnp.ones((), jnp.int32)]),
    }


KERNEL_NAMES = ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv",
                "sparse_flash_fwd", "sparse_flash_bwd_dq",
                "sparse_flash_bwd_dkv", "paged_decode", "paged_prefill",
                "paged_kv_write", "rms_norm_fwd", "layer_norm_fwd", "quantize_int8",
                "dequantize_int8", "state_rows_read", "state_rows_write",
                "ssm_decode_update", "ssm_chunk_scan", "paged_index_write",
                "paged_index_scores",
                "paged_sparse_select", "paged_sparse_decode",
                "paged_sparse_prefill", "moe_grouped_matmul",
                "retention_decode_update", "retention_chunk",
                "delta_decode_update", "delta_chunk"]


# a name with two call sites names the second ``<name>:<which>``
CALL_SITES = KERNEL_NAMES + ["paged_prefill:own_pages",
                             "paged_sparse_prefill:own_pages"]


@pytest.mark.parametrize("site", CALL_SITES)
def test_every_pallas_call_site_names_its_kernel(site):
    """The name is what the device trace shows for the kernel's events (the
    HLO instruction is named after it), whatever the program around it;
    under autodiff it arrives wrapped, ``transpose(jvp(<name>))``. The two
    multi-token walks share ONE name: the benchmark's rooflines and every
    trace reader match ``paged_prefill``, whichever walk a pool takes."""
    import re

    import jax

    fn, args = _kernel_cases()[site]
    kernel = site.partition(":")[0]
    text = jax.jit(fn).lower(*args).as_text(debug_info=True)
    # (a call site inside a jitted wrapper - ``ssm_chunk_scan`` - opens the
    # wrapper's own name stack: the name then starts the location)
    assert re.search(rf"[/(\"]{kernel}\)*/pallas_call", text)


def test_every_pallas_call_site_is_in_the_list():
    import re

    folder = os.path.join(REPO, "deepspeed_tpu", "ops", "pallas")
    found = []
    for f in sorted(os.listdir(folder)):
        if f.endswith(".py"):
            text = open(os.path.join(folder, f)).read()
            calls = len(re.findall(r"pl\.pallas_call\(", text))
            names = re.findall(r"^\s+name=\"([a-z0-9_]+)\",$", text, re.M)
            assert calls == len(names), f
            found += names
    assert sorted(found) == sorted(s.partition(":")[0] for s in CALL_SITES)


def _scopes_of(fn, *args):
    import re

    import jax

    text = jax.jit(fn).lower(*args).compile().as_text()
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        found |= set(re.findall(r"[A-Za-z_][A-Za-z0-9_]*", op_name))
    return found


@pytest.mark.parametrize("family,program,want", [
    ("llama", "train", {"embed", "norm", "attn", "ffn", "logits", "loss",
                        "optimizer"}),
    ("mixtral", "train", {"embed", "norm", "attn", "moe_router",
                          "moe_experts", "logits", "loss", "optimizer"}),
    ("llama", "decode", {"embed", "norm", "attn", "kv_write", "ffn",
                         "logits", "sample"}),
    ("mixtral", "decode", {"embed", "norm", "attn", "kv_write", "moe_router",
                           "moe_experts", "logits", "sample"}),
    # window and full layers each under a scope of their own inside attn,
    # the ungated shared experts under ffn (ISSUE 42)
    ("cohere2_moe", "decode", {"embed", "norm", "attn", "attn_window",
                               "attn_full", "kv_write", "moe_router",
                               "moe_experts", "ffn", "logits", "sample"}),
])
def test_model_step_blocks_are_named_scopes(devices8, family, program, want):
    """The block boundaries of the model step are in the ``op_name`` of the
    compiled operations, where a trace reduction sums device time by them."""
    import importlib

    import jax

    mod = importlib.import_module(f"deepspeed_tpu.models.{family}")
    cfg = {"llama": "LlamaConfig", "mixtral": "MixtralConfig",
           "cohere2_moe": "Cohere2MoeConfig"}[family]
    cfg = getattr(mod, cfg).tiny()
    if program == "train":
        engine, *_ = dst.initialize(
            model=mod.model_spec(cfg, compute_dtype=jnp.float32),
            config={"train_batch_size": 8, "steps_per_print": 0,
                    "gradient_clipping": 1.0,
                    "optimizer": {"type": "adamw", "params": {"lr": 1e-2}}})
        engine._build_train_step()
        batch = engine._shard_batch({"tokens": np.zeros((8, 33), np.int32)},
                                    with_gas_dim=True)
        found = _scopes_of(engine._train_step, engine.state, batch,
                           engine._lr_override)
        engine.destroy()
    else:
        from deepspeed_tpu.inference.engine_v2 import build_engine_v2

        params = mod.init(cfg, jax.random.PRNGKey(0))
        eng = build_engine_v2(mod, cfg, params, config={
            "dtype": "float32", "prefill_bucket": 16,
            "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                       "memory_config_blocks": 64, "block_size": 16}})
        found = _scopes_of(
            eng._decode_fn(1, False), eng.params, eng.cache, eng._prev,
            *map(jnp.asarray, eng._slots()), jax.random.PRNGKey(0))
    assert want <= found, want - found
