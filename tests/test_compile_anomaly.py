"""Compile-aware perf explainability: recompilation sentinel, HLO cost-model
MFU attribution, step-time anomaly detection.

Covers the CompileMonitor (`telemetry/compile.py`) registration helper and
its default-OFF byte-identity pins, recompile detection (shape change →
exactly one event) and the config-gated recompile budget, the guarded
cost-analysis fallback, the per-program MFU attribution vs the
ThroughputTimer headline, the anomaly detector (`telemetry/anomaly.py`)
spike/drift/straggler oracles on synthetic timing streams, the hub wiring
(events, flight-recorder dump hook, metrics snapshot), the JSONL rotation +
torn-tail-safe reopen, Prometheus label escaping, the schema registries,
the `telemetry_report.py --compile/--anomalies/--all` sections, and the
bench.py step-time regression mode.
"""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.models import llama
from deepspeed_tpu.telemetry.anomaly import AnomalyConfig, AnomalyDetector
from deepspeed_tpu.telemetry.compile import (CompileMonitor,
                                             CompileMonitorConfig,
                                             MonitoredFunction,
                                             RecompileBudgetExceeded,
                                             _cost_analysis)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPORT = os.path.join(REPO, "scripts", "telemetry_report.py")
BENCH = os.path.join(REPO, "bench.py")


@pytest.fixture(autouse=True)
def _a_peak_for_the_test_device(monkeypatch):
    """The MFU gauges exist only against a published peak, and the CPU these
    tests run on has none: give its ``device_kind`` one for the attribution
    math (``test_mfu_gauge_absent_on_an_unlisted_device`` takes it away)."""
    from deepspeed_tpu.utils import peaks

    monkeypatch.setitem(
        peaks.DEVICE_PEAKS, jax.devices()[0].device_kind,
        peaks.DevicePeaks(bf16_flops=2e12, hbm_bytes_per_s=1e11,
                          hbm_bytes=1 << 34))


def _load_bench():
    spec = importlib.util.spec_from_file_location("_bench_under_test", BENCH)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_bench_under_test"] = mod
    spec.loader.exec_module(mod)
    return mod


# --------------------------------------------------------------------------- #
# CompileMonitor unit behavior
# --------------------------------------------------------------------------- #
def test_mfu_gauge_absent_on_an_unlisted_device(monkeypatch):
    """A device the peaks table does not list raises on lookup, and the
    monitor's MFU gauge is then absent — never computed against a guess."""
    from deepspeed_tpu.utils import peaks

    monkeypatch.delitem(peaks.DEVICE_PEAKS, jax.devices()[0].device_kind)
    with pytest.raises(peaks.UnknownDevice):
        peaks.device_peaks()
    mon = CompileMonitor(CompileMonitorConfig(enabled=True))
    f = mon.jit("matmul", lambda a, b: a @ b)
    x = jnp.ones((8, 8))
    f(x, x)
    f(x, x)
    names = [n for n, _, _ in mon.events()]
    assert "Compile/matmul/cost_flops" in names
    assert not any("/mfu/" in n for n in names)
    assert peaks.DEVICE_PEAKS["TPU v5 lite"].bf16_flops == 197e12


def test_compile_anomaly_config_parses():
    from deepspeed_tpu.inference.config import InferenceConfig
    from deepspeed_tpu.runtime.config import parse_config

    cfg = parse_config({"telemetry": {
        "compile": {"enabled": True, "recompile_budget": 5,
                    "on_budget": "raise", "warmup_signatures": 2},
        "anomaly": {"enabled": True, "window": 32, "spike_mad": 4.0},
        "jsonl_max_mb": 8}})
    assert cfg.telemetry.compile.enabled
    assert cfg.telemetry.compile.recompile_budget == 5
    assert cfg.telemetry.compile.on_budget == "raise"
    assert cfg.telemetry.anomaly.enabled
    assert cfg.telemetry.anomaly.window == 32
    assert cfg.telemetry.jsonl_max_mb == 8
    # default OFF
    dflt = parse_config({})
    assert not dflt.telemetry.compile.enabled
    assert not dflt.telemetry.anomaly.enabled
    assert dflt.telemetry.jsonl_max_mb == 0.0
    icfg = InferenceConfig.from_dict(
        {"compile_monitor": {"enabled": True, "recompile_budget": 3}})
    assert icfg.compile_monitor.enabled
    assert icfg.compile_monitor.recompile_budget == 3
    assert not InferenceConfig.from_dict({}).compile_monitor.enabled


def test_disabled_monitor_returns_plain_jit():
    """Default-OFF pin: the registration helper hands back the exact
    jax.jit object — no wrapper in the dispatch path at all."""
    mon = CompileMonitor(None)
    assert not mon.enabled
    f = mon.jit("f", lambda x: x + 1)
    assert not isinstance(f, MonitoredFunction)
    assert float(f(jnp.ones(()))) == 2.0
    assert mon.stats == {}
    assert mon.events() == []


def test_monitor_records_compiles_hits_and_cost():
    mon = CompileMonitor(CompileMonitorConfig(enabled=True))
    f = mon.jit("matmul", lambda a, b: a @ b)
    assert isinstance(f, MonitoredFunction)
    x = jnp.ones((16, 16))
    for _ in range(3):
        f(x, x)
    s = mon.summary()["matmul"]
    assert s["compiles"] == 1 and s["cache_hits"] == 2
    assert s["recompiles"] == 0
    assert s["lower_ms"] > 0 and s["compile_ms"] > 0
    assert s["cost_flops"] > 0  # CPU XLA reports flops for a matmul
    events = dict((n, v) for n, v, _ in mon.events())
    assert events["Compile/matmul/compiles"] == 1
    assert events["Compile/matmul/cache_hits"] == 2
    assert events["Compile/total/programs"] == 1
    assert "Train/mfu/matmul" in events and events["Train/mfu/matmul"] > 0
    # the drain resets the per-window call counter: no calls → no mfu gauge
    assert not any("/mfu/" in n for n, _, _ in mon.events())


def test_shape_change_triggers_exactly_one_recompile():
    mon = CompileMonitor(CompileMonitorConfig(enabled=True))
    f = mon.jit("sq", lambda a: (a * a).sum())
    a8, a16 = jnp.ones((8,)), jnp.ones((16,))
    f(a8)
    f(a8)
    assert mon.summary()["sq"]["recompiles"] == 0
    f(a16)                         # new shape → exactly one recompile
    s = mon.summary()["sq"]
    assert s["compiles"] == 2 and s["recompiles"] == 1
    f(a8)                          # old shape again → cache hit, no event
    s = mon.summary()["sq"]
    assert s["recompiles"] == 1 and s["cache_hits"] == 2
    # numerics through the monitored path match plain jax
    assert float(f(a16)) == 16.0


def test_recompile_budget_warn_and_raise():
    mon = CompileMonitor(CompileMonitorConfig(
        enabled=True, recompile_budget=1, on_budget="raise"))
    f = mon.jit("g", lambda a: a.sum())
    f(jnp.ones((4,)))
    f(jnp.ones((5,)))              # unexpected recompile #1 — within budget
    with pytest.raises(RecompileBudgetExceeded):
        f(jnp.ones((6,)))          # #2 > budget 1 → raise
    # warn mode never raises, however many signatures arrive
    mon2 = CompileMonitor(CompileMonitorConfig(
        enabled=True, recompile_budget=1, on_budget="warn"))
    g = mon2.jit("g", lambda a: a.sum())
    for n in range(4, 9):
        g(jnp.ones((n,)))
    assert mon2.summary()["g"]["recompiles"] == 4
    assert mon2.unexpected_recompiles == 4
    # warmup_signatures: bucketed programs' expected variants don't count
    mon3 = CompileMonitor(CompileMonitorConfig(
        enabled=True, warmup_signatures=3, recompile_budget=1,
        on_budget="raise"))
    h = mon3.jit("h", lambda a: a.sum())
    for n in range(4, 7):          # 3 signatures = warmup, all expected
        h(jnp.ones((n,)))
    assert mon3.unexpected_recompiles == 0
    assert mon3.summary()["h"]["recompiles"] == 2  # still REPORTED


def test_cost_analysis_fallback():
    """Backends may return None/[]/garbage from cost_analysis — the guard
    degrades to zero flops (no MFU gauge) instead of crashing."""
    class _C:
        def __init__(self, ret=None, raises=False):
            self._ret, self._raises = ret, raises

        def cost_analysis(self):
            if self._raises:
                raise RuntimeError("not implemented on this backend")
            return self._ret

    assert _cost_analysis(_C(None)) == (0.0, 0.0)
    assert _cost_analysis(_C([])) == (0.0, 0.0)
    assert _cost_analysis(_C({})) == (0.0, 0.0)
    assert _cost_analysis(_C(raises=True)) == (0.0, 0.0)
    assert _cost_analysis(_C("bogus")) == (0.0, 0.0)
    assert _cost_analysis(_C([{"flops": 7.0, "bytes accessed": 3.0}])) \
        == (7.0, 3.0)
    assert _cost_analysis(_C({"flops": None})) == (0.0, 0.0)
    # end-to-end: a flops-less program records compiles but emits no gauge
    mon = CompileMonitor(CompileMonitorConfig(enabled=True))
    import deepspeed_tpu.telemetry.compile as cmod
    orig = cmod._cost_analysis
    cmod._cost_analysis = lambda compiled: (0.0, 0.0)
    try:
        f = mon.jit("nof", lambda a: a + 1)
        f(jnp.ones((4,)))
    finally:
        cmod._cost_analysis = orig
    assert mon.summary()["nof"]["cost_flops"] == 0.0
    assert not any("/mfu/" in n for n, _, _ in mon.events())


def test_runtime_errors_propagate_dispatch_errors_degrade():
    """The cached-program call path must NOT swallow runtime execution
    failures (XLA OOM, nan-checks, io_callback errors) — a silent re-run
    via plain jit would mask the failure and double-execute side effects.
    Only pre-dispatch signature mismatches (TypeError/ValueError) degrade
    to the fallback path."""
    mon = CompileMonitor(CompileMonitorConfig(enabled=True))
    f = mon.jit("r", lambda a: a * 2)
    x = jnp.ones((4,))
    f(x)                                   # compile + cache the program
    sig = next(iter(f._compiled))

    class _Boom:
        def __init__(self, exc):
            self.exc = exc

        def __call__(self, *a, **k):
            raise self.exc

    f._compiled[sig] = _Boom(RuntimeError("RESOURCE_EXHAUSTED: OOM"))
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        f(x)
    assert not f._fallback                 # no silent re-execution
    f._compiled[sig] = _Boom(TypeError("argument mismatch"))
    out = f(x)                             # pre-dispatch error → fall back
    assert f._fallback
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x * 2))


def test_shared_monitor_group_scoped_drains():
    """A hub-shared monitor is drained by BOTH the training hub (Train
    group, step-time window) and the serving engine (Serving group, wall
    window): each drain must only emit and reset its own group, or the
    interleaving corrupts both attributions. Compile/total/* stays
    cumulative over every program whichever caller drains."""
    mon = CompileMonitor(CompileMonitorConfig(enabled=True))
    tr = mon.jit("train_step", lambda a, b: a @ b, group="Train")
    sv = mon.jit("decode", lambda a, b: a @ b + 1, group="Serving")
    x = jnp.ones((16, 16))
    tr(x, x)
    tr(x, x)
    sv(x, x)
    train = dict((n, v) for n, v, _
                 in mon.events(window_s=0.01, group="Train"))
    assert train["Compile/train_step/compiles"] == 1
    assert train["Train/mfu/train_step"] > 0
    assert not any(n.startswith(("Compile/decode/", "Serving/"))
                   for n in train)
    assert train["Compile/total/programs"] == 2     # totals stay global
    # the train drain did not consume the serving window's calls
    serving = dict((n, v) for n, v, _ in mon.events(group="Serving"))
    assert serving["Compile/decode/compiles"] == 1
    assert serving["Serving/mfu/decode"] > 0
    assert not any(n.startswith(("Compile/train_step/", "Train/"))
                   for n in serving)
    # and each group's per-window counters reset only on ITS drain
    assert not any("/mfu/" in n for n, _, _
                   in mon.events(window_s=0.01, group="Train"))


# --------------------------------------------------------------------------- #
# schema registries
# --------------------------------------------------------------------------- #
def test_schema_compile_anomaly_mfu_registries():
    from deepspeed_tpu.telemetry.schema import (ANOMALY_SERIES,
                                                COMPILE_METRICS,
                                                validate_events)

    good = [("Compile/train_step/compiles", 1.0, 1),
            ("Compile/prefill/recompiles", 2.0, 1),
            ("Compile/total/compile_ms", 9.0, 1),
            ("Anomaly/step_time/spike", 1.5, 3),
            ("Anomaly/phase/fwd/drift", 0.3, 3),
            ("Anomaly/host/straggler", 0.4, 3),
            ("Train/mfu/train_step", 0.5, 1),
            ("Train/mfu/total", 0.5, 1),
            ("Train/mfu/headline", 0.5, 1),
            ("Serving/mfu/decode", 0.1, 1)]
    assert validate_events(good) == []
    assert "compiles" in COMPILE_METRICS
    assert "Anomaly/step_time/spike" in ANOMALY_SERIES
    # unregistered names must FAIL validation
    for bad in [("Compile/train_step/bogus_metric", 1.0, 1),
                ("Compile/total/bogus", 1.0, 1),
                ("Compile/too/many/segments", 1.0, 1),
                ("Anomaly/bogus/thing", 1.0, 1),
                ("Anomaly/step_time/wiggle", 1.0, 1),
                ("Train/mfu/Bad-Name", 1.0, 1),
                ("Serving/mfu/nested/prog", 1.0, 1)]:
        assert validate_events([bad]), f"{bad[0]} should fail validation"


# --------------------------------------------------------------------------- #
# training engine integration
# --------------------------------------------------------------------------- #
def _train_engine(extra=None):
    from deepspeed_tpu.comm import mesh as mesh_lib

    mesh_lib.set_mesh(None)
    cfg = llama.LlamaConfig.tiny()
    spec = llama.model_spec(cfg, compute_dtype=jnp.float32)
    config = {"train_batch_size": 8,
              "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
              "steps_per_print": 0}
    config.update(extra or {})
    engine, *_ = dst.initialize(model=spec, config=config)
    tokens = np.random.default_rng(7).integers(
        0, cfg.vocab_size, (8, 33), dtype=np.int32)
    return engine, {"tokens": np.asarray(tokens)}


def test_train_default_off_is_plain_jit_and_quiet(devices8, tmp_path):
    """Default-OFF pins: no wrapper on the train step, a disabled monitor
    and detector on the hub, and zero Compile/Anomaly events in the JSONL
    stream of a default run."""
    engine, batch = _train_engine({
        "jsonl_monitor": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "off"}})
    assert not engine.telemetry.compile.enabled
    assert not engine.telemetry.anomaly.enabled
    for _ in range(2):
        engine.train_batch(batch)
    assert not isinstance(engine._train_step, MonitoredFunction)
    assert engine.telemetry.compile_values == {}
    assert engine.telemetry.anomaly_counts == {}
    engine.destroy()
    recs = [json.loads(l) for l in
            open(tmp_path / "off" / "events.jsonl")]
    assert recs
    assert not any(r["name"].startswith(("Compile/", "Anomaly/"))
                   or "/mfu/" in r["name"] for r in recs)


def test_train_compile_on_numerics_and_mfu_attribution(devices8, tmp_path):
    """Monitored dispatch is numerically identical to the default path, the
    sentinel records the train step, and the per-program MFU attribution
    sums to within 10% of the ThroughputTimer headline (acceptance)."""
    engine_off, batch = _train_engine()
    base = [float(engine_off.train_batch(batch).loss) for _ in range(3)]
    engine_off.destroy()
    engine, batch = _train_engine({
        "telemetry": {"compile": {"enabled": True}},
        "jsonl_monitor": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "on"}})
    assert engine.telemetry.compile.enabled
    mon = [float(engine.train_batch(batch).loss) for _ in range(3)]
    assert mon == base  # bit-identical losses through the AOT dispatch
    s = engine.telemetry.compile.summary()["train_step"]
    assert s["compiles"] == 1 and s["cache_hits"] == 2
    assert s["recompiles"] == 0
    assert s["cost_flops"] > 0
    cv = engine.telemetry.compile_values
    assert cv["Compile/train_step/compiles"] == 1.0
    # the analytic cost model fed the ThroughputTimer, so the headline and
    # the attribution share one flops source → the sum matches within 10%
    total, headline = cv["Train/mfu/total"], cv["Train/mfu/headline"]
    assert total > 0 and headline > 0
    assert abs(total / headline - 1.0) < 0.10
    engine.destroy()
    recs = [json.loads(l) for l in open(tmp_path / "on" / "events.jsonl")]
    from deepspeed_tpu.telemetry import validate_jsonl_records
    assert validate_jsonl_records(recs) == []
    names = {r["name"] for r in recs}
    assert "Compile/train_step/compiles" in names
    assert "Train/mfu/train_step" in names
    # acceptance: the report renders recompile counts + MFU attribution
    out = subprocess.run(
        [sys.executable, REPORT, str(tmp_path / "on" / "events.jsonl"),
         "--all"], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    for token in ("compile report", "train_step", "MFU attribution",
                  "ThroughputTimer headline", "anomaly report"):
        assert token in out.stdout, f"--all missing {token!r}"


def test_breakdown_zero2_no_phantom_recompiles(devices8):
    """Sharding-spec spelling must not alias into recompile reports:
    ZeRO-2 breakdown-mode programs see ``PartitionSpec(('data',))`` on the
    placed step-1 state and ``PartitionSpec('data')`` on their own step-2
    outputs — one sharding to jax, so zero recompiles here (pinned)."""
    engine, batch = _train_engine({
        "wall_clock_breakdown": True,
        "zero_optimization": {"stage": 2},
        "telemetry": {"compile": {"enabled": True}}})
    for _ in range(3):
        engine.train_batch(batch)
    summ = engine.telemetry.compile.summary()
    assert set(summ) == {"fwd_step", "bwd_step", "apply_step"}
    for name, s in summ.items():
        assert s["compiles"] == 1 and s["recompiles"] == 0, (name, s)
        assert s["cache_hits"] == 2, (name, s)
    engine.destroy()


# --------------------------------------------------------------------------- #
# serving engine integration
# --------------------------------------------------------------------------- #
def _serving_engine(extra_cfg=None, hub=None):
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.inference.engine_v2 import build_engine_v2

    mesh_lib.set_mesh(None)
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    config = {"dtype": "float32", "prefill_bucket": 16,
              "ragged": {"max_tracked_sequences": 4,
                         "max_ragged_batch_size": 4,
                         "memory_config_blocks": 64, "block_size": 16}}
    config.update(extra_cfg or {})
    return cfg, build_engine_v2(llama, cfg, params, config=config,
                                telemetry_hub=hub)


def test_serving_compile_monitor_and_bucket_recompile(devices8):
    cfg, eng = _serving_engine(
        {"compile_monitor": {"enabled": True}})
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, (12,)).tolist()
               for _ in range(2)]
    outs = eng.generate(prompts, max_new_tokens=4)
    assert all(len(o) == 4 for o in outs)
    summ = eng.compile_monitor.summary()
    assert summ["prefill"]["compiles"] == 1
    assert summ["decode"]["compiles"] == 1
    assert summ["decode"]["cache_hits"] >= 2
    # a longer prompt lands in a new pad bucket: the prefill FAMILY
    # recompiles — exactly the unbucketed-prompt storm signature
    eng.put(7, rng.integers(0, cfg.vocab_size, (20,)).tolist())
    eng.step()
    summ = eng.compile_monitor.summary()
    assert summ["prefill"]["compiles"] == 2
    assert summ["prefill"]["recompiles"] == 1
    evs = dict((n, v) for n, v, _ in eng.compile_events())
    assert evs["Compile/prefill/recompiles"] == 1
    assert any(n.startswith("Serving/mfu/") for n in evs)


def test_serving_compile_off_bit_identical_and_hub_publish(devices8,
                                                           tmp_path):
    """Default-OFF serving parity (monitored vs plain greedy decode emits
    identical tokens) + the hub publish path for a monitor-enabled run."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 64, (12,)).tolist() for _ in range(2)]
    cfg, eng_off = _serving_engine()
    assert not eng_off.compile_monitor.enabled
    base = eng_off.generate(prompts, max_new_tokens=5)
    from deepspeed_tpu.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import parse_config
    from deepspeed_tpu.telemetry import TelemetryHub

    rcfg = parse_config({
        "telemetry": {"compile": {"enabled": True}},
        "jsonl_monitor": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "srv"}})
    hub = TelemetryHub(rcfg, monitor=MonitorMaster(rcfg))
    cfg, eng_on = _serving_engine(hub=hub)
    assert eng_on.compile_monitor is hub.compile  # shared registry
    assert eng_on.generate(prompts, max_new_tokens=5) == base
    assert any(n.startswith("Compile/prefill/")
               for n in hub.compile_values)
    hub.close()
    recs = [json.loads(l) for l in open(tmp_path / "srv" / "events.jsonl")]
    from deepspeed_tpu.telemetry import validate_jsonl_records
    assert validate_jsonl_records(recs) == []
    assert any(r["name"] == "Compile/decode/compiles" for r in recs)


# --------------------------------------------------------------------------- #
# anomaly detector oracles (synthetic timing streams)
# --------------------------------------------------------------------------- #
def test_anomaly_spike_oracle():
    det = AnomalyDetector(AnomalyConfig(enabled=True))
    rng = np.random.default_rng(0)
    findings = []
    for step in range(1, 61):
        v = 10.0 + float(rng.uniform(-0.2, 0.2))
        if step == 50:
            v = 40.0              # one 4x spike
        findings += det.observe("step_time", v, step)
    assert len(findings) == 1
    f = findings[0]
    assert f.series == "step_time/spike" and f.step == 50
    assert 2.5 < f.value < 3.5    # ~300% above the median
    assert "step 50" in f.detail


def test_anomaly_drift_oracle_flags_once_and_rearms():
    cfg = AnomalyConfig(enabled=True, window=32, drift_frac=0.25)
    det = AnomalyDetector(cfg)
    drift, spikes = [], []
    # 64 clean samples freeze the 10ms baseline; then a slow +50% ramp
    for step in range(1, 201):
        v = 10.0 if step <= 64 else min(15.0, 10.0 + (step - 64) * 0.08)
        for f in det.observe("step_time", v, step):
            (drift if f.series.endswith("drift") else spikes).append(f)
    assert len(drift) == 1        # flagged once, not every step
    assert drift[0].value > 0.25
    # recovery below half-threshold re-arms; a second excursion re-flags
    for step in range(201, 320):
        for f in det.observe("step_time", 10.0, step):
            (drift if f.series.endswith("drift") else spikes).append(f)
    for step in range(320, 460):
        for f in det.observe("step_time", 14.0, step):
            (drift if f.series.endswith("drift") else spikes).append(f)
    assert len(drift) == 2


def test_anomaly_quiet_on_noise_and_disabled():
    det = AnomalyDetector(AnomalyConfig(enabled=True))
    rng = np.random.default_rng(3)
    findings = []
    for step in range(1, 301):
        findings += det.observe(
            "step_time", 10.0 * float(1 + rng.uniform(-0.05, 0.05)), step)
    assert findings == []         # ±5% jitter is not an anomaly
    off = AnomalyDetector(None)
    assert not off.enabled
    assert off.observe("step_time", 1e9) == []
    assert off.observe_hosts([1.0, 100.0]) == []


def test_anomaly_straggler_hosts():
    det = AnomalyDetector(AnomalyConfig(enabled=True, straggler_frac=0.25))
    assert det.observe_hosts([10.0, 10.2, 9.9, 10.1], step=5) == []
    findings = det.observe_hosts([10.0, 10.2, 9.9, 14.0], step=6)
    assert len(findings) == 1
    assert findings[0].series == "host/straggler"
    assert "host 3" in findings[0].detail
    assert findings[0].step == 6


def test_anomaly_through_hub_dump_and_metrics(devices8, tmp_path):
    """Hub wiring: findings become Anomaly/* events in the monitor stream,
    a tracer instant + flight-recorder dump fire, and the metrics snapshot
    gains the counters."""
    from deepspeed_tpu.monitor import MonitorMaster
    from deepspeed_tpu.runtime.config import parse_config
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.metrics_server import render_prometheus

    dump = str(tmp_path / "anomaly_dump.json")
    rcfg = parse_config({
        "telemetry": {"anomaly": {"enabled": True, "min_samples": 8},
                      "trace": {"enabled": True, "export_path": dump,
                                "dump_on_crash": False}},
        "jsonl_monitor": {"enabled": True, "output_path": str(tmp_path),
                          "job_name": "anom"}})
    hub = TelemetryHub(rcfg, monitor=MonitorMaster(rcfg))
    assert hub.anomaly.enabled
    for step in range(1, 30):
        evs = hub.observe_step_anomalies(step, step_time_s=0.010,
                                         phase_ms={"fwd": 4.0})
        assert evs == []
    evs = hub.observe_step_anomalies(30, step_time_s=0.080,
                                     phase_ms={"fwd": 30.0})
    names = {n for n, _, _ in evs}
    assert "Anomaly/step_time/spike" in names
    assert "Anomaly/phase/fwd/spike" in names
    assert hub.anomaly_counts["Anomaly/step_time/spike"] == 1
    assert os.path.exists(dump)   # flight-recorder dump hook fired
    assert any(e["name"] == "anomaly" for e in hub.tracer.events())
    body = render_prometheus(hub.metrics_snapshot())
    assert "dstpu_anomaly_step_time_spike 1" in body
    hub.close()
    jsonl = tmp_path / "anom" / "events.jsonl"
    recs = [json.loads(l) for l in open(jsonl)]
    from deepspeed_tpu.telemetry import validate_jsonl_records
    assert validate_jsonl_records(recs) == []
    out = subprocess.run([sys.executable, REPORT, str(jsonl),
                          "--anomalies"], capture_output=True, text=True,
                         timeout=60)
    assert out.returncode == 0, out.stderr
    assert "step_time/spike" in out.stdout
    assert "phase/fwd/spike" in out.stdout


def test_straggler_gather_runs_on_every_process(monkeypatch):
    """The per-host gather is a collective (process_allgather requires ALL
    processes), so step_end must reach it on every rank BEFORE the rank-0
    gate — a rank-0-only gather deadlocks the first monitored step of any
    multi-process job. Non-zero ranks gather and return nothing; rank 0
    gathers and emits the straggler finding."""
    from jax.experimental import multihost_utils

    from deepspeed_tpu.runtime.config import parse_config
    from deepspeed_tpu.telemetry import TelemetryHub

    calls = []

    def fake_allgather(x):
        calls.append(float(x))
        return np.array([10.0, 10.2, 9.9, 14.0])

    hub = TelemetryHub(parse_config(
        {"telemetry": {"anomaly": {"enabled": True}}}))
    monkeypatch.setattr(jax, "process_count", lambda: 4)
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        fake_allgather)
    hub.rank0 = False
    assert hub.step_end(1, step_time_s=0.010) == []
    assert len(calls) == 1        # the collective ran despite the gate
    hub.rank0 = True
    evs = hub.step_end(2, step_time_s=0.010)
    assert len(calls) == 2
    assert any(n == "Anomaly/host/straggler" for n, _, _ in evs)
    hub.close()


def test_anomaly_report_offline_replay(tmp_path):
    """--anomalies replays the detector over Train/Step/*_ms series from a
    run that never enabled it (post-hoc screening)."""
    path = tmp_path / "events.jsonl"
    rng = np.random.default_rng(0)
    with open(path, "w") as f:
        for step in range(1, 81):
            v = 10.0 + float(rng.uniform(-0.2, 0.2))
            if step == 70:
                v = 42.0
            f.write(json.dumps({"name": "Train/Step/train_batch_ms",
                                "value": v, "step": step, "ts": 0.0}) + "\n")
    out = subprocess.run([sys.executable, REPORT, str(path), "--anomalies"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "offline replay" in out.stdout
    assert "1 finding(s)" in out.stdout
    assert "step 70" in out.stdout


# --------------------------------------------------------------------------- #
# satellites: JSONL rotation, Prometheus escaping, bench regression
# --------------------------------------------------------------------------- #
def test_jsonl_rotation_and_torn_tail(tmp_path):
    from deepspeed_tpu.monitor.monitor import JSONLMonitor

    class Cfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "rot"

    mon = JSONLMonitor(Cfg(), max_mb=0.002)   # ~2 KiB cap
    written = 0
    for step in range(120):
        mon.write_events([("Train/Samples/train_loss", 1.0, step)])
        written += 1
    mon.close()
    path = tmp_path / "rot" / "events.jsonl"
    rotated = tmp_path / "rot" / "events.jsonl.1"
    assert rotated.exists(), "cap exceeded → must rotate to .1"
    assert os.path.getsize(path) < 4096
    total = sum(1 for p in (path, rotated) for _ in open(p))
    # one generation is retained: the live file + newest rotation hold the
    # tail of the stream, and every retained line is complete JSON
    assert total <= written
    for p in (path, rotated):
        for line in open(p):
            json.loads(line)
    # torn-tail-safe reopen: a crash-torn final line is newline-terminated
    # before new records append, so it can't glue onto the next record
    with open(path, "a") as f:
        f.write('{"name": "Train/Samples/train_loss", "va')
    mon2 = JSONLMonitor(Cfg(), max_mb=0)
    mon2.write_events([("Train/Samples/train_loss", 2.0, 999)])
    mon2.close()
    lines = [l for l in open(path).read().splitlines() if l.strip()]
    assert json.loads(lines[-1])["step"] == 999
    parsed, torn = 0, 0
    for l in lines:
        try:
            json.loads(l)
            parsed += 1
        except ValueError:
            torn += 1
    assert torn == 1              # the torn line stays ONE bad line


def test_prometheus_label_escaping():
    from deepspeed_tpu.telemetry.metrics_server import (escape_label_value,
                                                        render_prometheus)

    assert escape_label_value('a\\b"c\nd') == 'a\\\\b\\"c\\nd'
    body = render_prometheus([
        ("Compile/compiles", 3.0, "counter", {"program": 'pre\\fill"x\ny'}),
        ("Train/mfu", 0.5, "gauge", {"program": "train_step"}),
        ("Reliability/checkpoint_saved", 2.0, "counter")])
    assert 'dstpu_compile_compiles{program="pre\\\\fill\\"x\\ny"} 3' in body
    assert 'dstpu_train_mfu{program="train_step"} 0.5' in body
    assert "dstpu_reliability_checkpoint_saved 2" in body
    assert "# TYPE dstpu_compile_compiles counter" in body
    # hub snapshot folds per-program series onto labeled rows
    from deepspeed_tpu.runtime.config import parse_config
    from deepspeed_tpu.telemetry import TelemetryHub

    hub = TelemetryHub(parse_config({}))
    hub.compile_event("Compile/train_step/recompiles", 4.0)
    hub.compile_event("Compile/total/recompiles", 4.0)
    hub.compile_event("Serving/mfu/decode", 0.25)
    hub.compile_event("Train/mfu/train_step", 0.4)
    hub.compile_event("Train/mfu/total", 0.5)
    hub.compile_event("Train/mfu/headline", 0.55)
    body = render_prometheus(hub.metrics_snapshot())
    assert 'dstpu_compile_recompiles{program="train_step"} 4' in body
    assert "dstpu_compile_total_recompiles 4" in body
    assert 'dstpu_serving_mfu{program="decode"} 0.25' in body
    # the total/headline rollups export as distinct unlabeled metrics — as
    # program labels they'd double-count any aggregation over the program
    # label against the per-program gauges
    assert 'dstpu_train_mfu{program="train_step"} 0.4' in body
    assert "dstpu_train_mfu_total 0.5" in body
    assert "dstpu_train_mfu_headline 0.55" in body
    assert 'program="total"' not in body
    assert 'program="headline"' not in body


def test_bench_step_time_regression_mode(tmp_path):
    bench = _load_bench()
    # artifact parsing: raw stdout capture AND the round wrapper shape
    fresh = {"metric": "llama_zero3_train_mfu", "value": 0.5,
             "unit": "fraction_of_peak", "vs_baseline": 1.0,
             "detail": {"backend": "cpu", "step_time_s": 0.10}}
    raw = tmp_path / "fresh.json"
    raw.write_text("log line\n" + json.dumps(fresh) + "\n")
    assert bench._bench_result_from_file(str(raw))["detail"][
        "step_time_s"] == 0.10
    ref = dict(fresh, detail={"backend": "cpu", "step_time_s": 0.08})
    (tmp_path / "BENCH_r03.json").write_text(json.dumps(
        {"n": 3, "cmd": "python bench.py", "rc": 0,
         "tail": "noise\n" + json.dumps(ref)}))
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(ref))
    assert bench.find_newest_bench_artifact(str(tmp_path)).endswith(
        "BENCH_r03.json")
    # same-backend compare: +25% vs a 20% threshold → regressed
    row = bench.compare_step_time(fresh, ref, 20.0)
    assert row["status"] == "regressed" and row["fail"]
    assert row["delta_pct"] == 25.0
    ok = bench.compare_step_time(
        dict(fresh, detail={"backend": "cpu", "step_time_s": 0.081}),
        ref, 20.0)
    assert ok["status"] == "ok" and not ok["fail"]
    # runs on different backends are never judged against each other
    tpu = bench.compare_step_time(
        {"detail": {"backend": "tpu", "step_time_s": 0.26}}, ref, 20.0)
    assert tpu["status"].startswith("skipped")
    skip = bench.compare_step_time(
        fresh, {"detail": {"backend": "tpu", "step_time_s": 0.25}}, 20.0)
    assert skip["status"].startswith("skipped")
    # CLI probe: exit 0 on ok, 1 on a confirmed regression
    slow = dict(fresh, detail={"backend": "cpu", "step_time_s": 0.2})
    slow_p = tmp_path / "slow.json"
    slow_p.write_text(json.dumps(slow) + "\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               DSTPU_BENCH_REF_DIR=str(tmp_path))
    out = subprocess.run(
        [sys.executable, BENCH, "--regression-only", str(raw)],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(tmp_path))
    assert out.returncode == 1, out.stdout + out.stderr  # 25% > 20%
    assert "bench_step_time_regression" in out.stdout
    ok_row = json.loads(out.stdout.strip().splitlines()[-1])
    assert ok_row["detail"]["reference_artifact"] == "BENCH_r03.json"
