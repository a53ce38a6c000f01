"""The process-wide compile account (`telemetry/compile.py CompileAccount`):
what `jax.monitoring` publishes of every trace, lowering, backend compile and
persistent-cache request, kept as intervals and answered as the length of
their union - and the benchmark's reader over it. No engine is built here.
"""

import importlib
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.telemetry import compile as compile_mod
from deepspeed_tpu.telemetry.compile import (CompileAccount, CompileMonitor,
                                             CompileMonitorConfig,
                                             process_account)
from deepspeed_tpu.telemetry.schema import (COMPILE_METRICS,
                                            COMPILE_PROCESS_SERIES,
                                            validate_events)
from deepspeed_tpu.telemetry.trace import TraceConfig, Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPORT = os.path.join(REPO, "scripts", "telemetry_report.py")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def account(monkeypatch):
    """A fresh account in the process's place; its listeners, if a test's
    monitor installs them, are taken off `jax.monitoring` again."""
    fresh = CompileAccount()
    monkeypatch.setattr(compile_mod, "_PROCESS_ACCOUNT", fresh)
    yield fresh
    if fresh._installed:
        jax.monitoring.unregister_event_listener(fresh.on_event)
        jax.monitoring.unregister_event_duration_listener(fresh.on_duration)


def _compile_spans(tracer):
    return [e for e in tracer.events()
            if e["ph"] == "X" and e["name"] == "compile"]


def _count_registrations(monkeypatch):
    calls = {"event": 0, "duration": 0}
    for key, name in (("event", "register_event_listener"),
                      ("duration", "register_event_duration_secs_listener")):
        real = getattr(jax.monitoring, name)

        def counted(cb, key=key, real=real):
            calls[key] += 1
            real(cb)

        monkeypatch.setattr(jax.monitoring, name, counted)
    return calls


# --------------------------------------------------------------------------- #
# installing
# --------------------------------------------------------------------------- #
def test_disabled_monitor_registers_nothing_and_the_account_stays_empty(
        account, monkeypatch):
    calls = _count_registrations(monkeypatch)
    mon = CompileMonitor()                      # the default: disabled
    f = mon.jit("f", lambda x: x * 2 + 1)
    f(jnp.ones((4, 4)))
    assert calls == {"event": 0, "duration": 0}
    assert account.events_seen == 0
    assert not any(account.totals().values())
    assert mon.events() == []


def test_two_enabled_monitors_register_one_pair_of_listeners(
        account, monkeypatch):
    calls = _count_registrations(monkeypatch)
    mons = [CompileMonitor(CompileMonitorConfig(enabled=True))
            for _ in range(2)]
    assert calls == {"event": 1, "duration": 1}
    assert all(m.account is account for m in mons)
    assert process_account() is account


# --------------------------------------------------------------------------- #
# hit and miss, on the account, the program's stats and its span
# --------------------------------------------------------------------------- #
HIT_AND_MISS = r"""
import json, sys, time
import jax, jax.numpy as jnp
from deepspeed_tpu.telemetry.compile import (CompileMonitor,
                                             CompileMonitorConfig,
                                             process_account)
from deepspeed_tpu.telemetry.schema import validate_events
from deepspeed_tpu.telemetry.trace import TraceConfig, Tracer

# as benchmark/harness/device.py sets it: every program, however quick
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
account = process_account()
tracer = Tracer(TraceConfig(enabled=True))
fn = lambda x: jnp.sin(x) @ x + 53.0
x = jnp.ones((16, 16))


def compile_once():
    mon = CompileMonitor(CompileMonitorConfig(enabled=True), tracer=tracer)
    mon.jit("prog", fn)(x)
    return mon


out = {"before": account.totals()}
out["first"] = compile_once().summary()["prog"]
out["mid"] = account.totals()
t_mid = time.perf_counter()
jax.clear_caches()
mon = compile_once()
out["second"] = mon.summary()["prog"]
out["after"] = account.totals()
out["mid_again"] = account.totals(before=t_mid)
out["spans"] = [e["args"] for e in tracer.events()
                if e["ph"] == "X" and e["name"] == "compile"]
out["by_program"] = account.by_program(top=100)
events = mon.events()
out["problems"] = validate_events(events)
out["events"] = {n: v for n, v, _ in events}
print(json.dumps(out))
"""


def test_first_compile_misses_and_the_second_hits(tmp_path):
    """In a process of its own: JAX's persistent cache on (a temporary
    directory; `tests/conftest.py` has it off), `jax.clear_caches()` and an
    executable read back from disk are no state to leave in a worker."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, "-c", HIT_AND_MISS, str(tmp_path / "xla")],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    before, first, mid = out["before"], out["first"], out["mid"]
    second, after = out["second"], out["after"]

    assert (first["persistent_cache_hits"],
            first["persistent_cache_misses"]) == (0, 1)
    assert (second["persistent_cache_hits"],
            second["persistent_cache_misses"]) == (1, 0)
    assert mid["cache_misses"] - before["cache_misses"] >= 1
    assert mid["cache_hits"] == before["cache_hits"] == 0
    assert after["cache_hits"] - mid["cache_hits"] == 1
    assert after["cache_retrieval_s"] > 0
    assert after["programs_compiled"] > mid["programs_compiled"] > 0
    assert first["analysis_ms"] > 0 and after["monitor_analysis_s"] > 0
    # the dispatch table's hits are another thing under the same word
    assert first["cache_hits"] == second["cache_hits"] == 0
    # totals(before=t) splits at t
    assert out["mid_again"] == mid
    assert [s["persistent_cache"] for s in out["spans"]] == ["miss", "hit"]
    assert all(s["analysis_ms"] >= 0 for s in out["spans"])
    # the cache's events are filed under the program that met them
    mine = {r["program"]: r for r in out["by_program"]}
    assert mine["<lambda>"]["cache_hits"] == 1
    assert mine["<lambda>"]["cache_misses"] == 1
    assert mine["prog"]["monitor_analysis_s"] > 0
    # and the drain carries the account, under registered names only
    assert out["problems"] == []
    got = out["events"]
    assert got["Compile/process/cache_hits"] == 1.0
    assert got["Compile/prog/persistent_cache_hits"] == 1.0
    assert {n for n in got if n.startswith("Compile/process/")} \
        == COMPILE_PROCESS_SERIES


def test_cache_off_reads_off(account):
    tracer = Tracer(TraceConfig(enabled=True))
    mon = CompileMonitor(CompileMonitorConfig(enabled=True), tracer=tracer)
    mon.jit("prog", lambda x: x - 53.0)(jnp.ones((4,)))
    (span,) = _compile_spans(tracer)
    assert span["args"]["persistent_cache"] == "off"
    assert account.totals()["cache_requests"] == 0
    assert account.totals()["programs_compiled"] >= 1


# --------------------------------------------------------------------------- #
# the arithmetic, on hand-made events
# --------------------------------------------------------------------------- #
def _feed(account, event, t0, t1, fun_name, monkeypatch):
    """One duration event whose callback reads the clock at `t1`."""
    monkeypatch.setattr(compile_mod.time, "perf_counter", lambda: t1)
    account.on_duration(event, t1 - t0, fun_name=fun_name)


def test_nested_traces_count_once(monkeypatch):
    """jit(f) of sin(x) @ x reports sin, matmul and f: f's trace contains
    the other two."""
    acc = CompileAccount()
    for t0, t1, name in ((1.0, 2.0, "sin"), (3.0, 4.0, "matmul"),
                         (0.0, 5.0, "f")):
        _feed(acc, TRACE, t0, t1, name, monkeypatch)
    _feed(acc, LOWER, 5.0, 7.0, "jit(f)", monkeypatch)
    _feed(acc, BACKEND, 7.0, 10.0, "jit(f)", monkeypatch)
    _feed(acc, "/jax/some/other/event", 0.0, 99.0, "g", monkeypatch)
    t = acc.totals()
    assert t["trace_lower_s"] == pytest.approx(7.0)      # not 9.0
    assert t["backend_compile_s"] == pytest.approx(3.0)
    assert t["programs_compiled"] == 1
    assert acc.events_seen == 5
    rows = {r["program"]: r for r in acc.by_program()}
    assert rows["f"]["trace_lower_s"] == pytest.approx(7.0)   # jit(f) is f
    assert rows["f"]["backend_compile_s"] == pytest.approx(3.0)
    assert rows["sin"]["trace_lower_s"] == pytest.approx(1.0)
    assert acc.by_program(top=1)[0]["program"] == "f"
    # a drain every step adds nothing up again; one more event does
    assert acc.totals() is not acc.totals() and acc._totals == t
    _feed(acc, TRACE, 10.0, 11.0, "g", monkeypatch)
    assert acc._totals is None
    assert acc.totals()["trace_lower_s"] == pytest.approx(8.0)
    assert acc.totals(before=10.5) == t
    # an event that had not ended at `before` is not counted
    assert acc.totals(before=4.5)["trace_lower_s"] == pytest.approx(2.0)
    assert acc.totals(before=6.0)["trace_lower_s"] == pytest.approx(5.0)


def test_threads_overlap_into_one_wall_time(monkeypatch):
    acc = CompileAccount()
    _feed(acc, BACKEND, 0.0, 4.0, "jit(a)", monkeypatch)
    monkeypatch.setattr(compile_mod.threading, "get_ident", lambda: -1)
    _feed(acc, BACKEND, 2.0, 6.0, "jit(b)", monkeypatch)
    assert acc.totals()["backend_compile_s"] == pytest.approx(6.0)
    assert acc.totals()["programs_compiled"] == 2


def test_records_stay_bounded_and_totals_right_past_the_cap(monkeypatch):
    """Past the cap the records are folded where a whole program ends (no
    interval of that thread straddles the fold): the totals and the rows by
    program are those of an account that folded nothing, and a `before`
    inside what was folded is refused. Where no program ever ends, twice the
    cap folds all the same."""
    small, whole = CompileAccount(cap=8), CompileAccount(cap=1 << 20)
    events = []
    for p in range(6):                  # six programs of nine events each
        t = 10.0 * p
        events += [(TRACE, t + i + 0.25, t + i + 0.75, f"op{i % 3}")
                   for i in range(6)]
        events += [(TRACE, t, t + 7.0, f"f{p}"),         # contains the six
                   (LOWER, t + 7.0, t + 8.0, f"jit(f{p})"),
                   (BACKEND, t + 8.0, t + 9.5, f"jit(f{p})")]
    most = 0
    for acc in (small, whole):
        for event, t0, t1, name in events:
            _feed(acc, event, t0, t1, name, monkeypatch)
            most = max(most, len(small._records))
        monkeypatch.setattr(compile_mod.time, "perf_counter", lambda: 59.75)
        acc.on_event("/jax/compilation_cache/cache_misses")
    assert most <= 9 and len(whole._records) == 55
    assert small.events_seen == whole.events_seen == 55
    assert small.totals() == pytest.approx(whole.totals())
    assert small.totals()["trace_lower_s"] == pytest.approx(6 * 8.0)
    assert small.totals()["programs_compiled"] == 6
    by = lambda acc: {r["program"]: r for r in acc.by_program(top=100)}
    assert by(small).keys() == by(whole).keys()
    for name, row in by(whole).items():
        assert by(small)[name] == pytest.approx(row), name
    assert small.totals(before=59.6)["cache_misses"] == 0
    assert small.totals(before=59.6)["programs_compiled"] == 6
    for ask in (small.totals, small.by_program):
        with pytest.raises(ValueError, match="folded"):
            ask(before=30.0)
    tracing_only = CompileAccount(cap=8)
    for i in range(100):
        _feed(tracing_only, TRACE, float(i), i + 0.5, "g", monkeypatch)
        assert len(tracing_only._records) <= 16
    assert tracing_only.totals()["trace_lower_s"] == pytest.approx(50.0)


# --------------------------------------------------------------------------- #
# names and the report
# --------------------------------------------------------------------------- #
def test_schema_takes_the_account_and_refuses_a_stranger():
    assert validate_events(
        [(name, 1.0, 1) for name in sorted(COMPILE_PROCESS_SERIES)]
        + [("Compile/decode/analysis_ms", 2.0, 1),
           ("Compile/decode/persistent_cache_hits", 1.0, 1),
           ("Compile/decode/persistent_cache_misses", 0.0, 1)]) == []
    assert {"analysis_ms", "persistent_cache_hits",
            "persistent_cache_misses"} <= COMPILE_METRICS
    for bad in ("Compile/process/bogus", "Compile/process/compiles",
                "Compile/process/a/b"):
        problems = validate_events([(bad, 1.0, 1)])
        assert problems and "COMPILE_PROCESS_SERIES" in problems[0], bad


def test_report_prints_where_start_up_went(tmp_path):
    events = [("Compile/process/trace_lower_s", 7.5),
              ("Compile/process/backend_compile_s", 0.5),
              ("Compile/process/cache_retrieval_s", 0.25),
              ("Compile/process/monitor_analysis_s", 1.25),
              ("Compile/process/cache_hits", 40.0),
              ("Compile/process/cache_misses", 2.0),
              ("Compile/process/cache_requests", 42.0),
              ("Compile/process/programs_compiled", 42.0),
              ("Compile/total/programs", 12.0)]
    for i in range(12):
        events += [(f"Compile/prog{i:02d}/lower_ms", 100.0 * i),
                   (f"Compile/prog{i:02d}/compile_ms", 10.0),
                   (f"Compile/prog{i:02d}/analysis_ms", 5.0),
                   (f"Compile/prog{i:02d}/persistent_cache_misses",
                    float(i == 11))]
    path = tmp_path / "events.jsonl"
    path.write_text("".join(json.dumps({"name": n, "value": v, "step": 1})
                            + "\n" for n, v in events))
    out = subprocess.run([sys.executable, REPORT, str(path), "--compile"],
                         capture_output=True, text=True, check=True).stdout
    block = out[out.index("start-up, whole process"):]
    for token in ("tracing + lowering:", "7.50 s", "backend compile:",
                  "monitor's own analysis:", "1.25 s",
                  "40 hits, 2 misses of 42 requests", "42 programs"):
        assert token in block, (token, out)
    rows = [ln.split()[0] for ln in block.splitlines()
            if ln.strip().startswith("prog")]
    assert rows == [f"prog{i:02d}" for i in range(11, 1, -1)]   # ten
    assert "0 hit 1 miss" in block
    assert "process" not in out[:out.index("start-up")]  # no program row


def test_metrics_endpoint_keeps_the_account_apart_from_the_programs():
    from deepspeed_tpu.telemetry.hub import TelemetryHub

    hub = TelemetryHub.__new__(TelemetryHub)
    for field in ("reliability_counts", "serving_values", "train_values",
                  "memory_tier_values", "fleet_values", "tenant_values",
                  "tune_values", "anomaly_counts"):
        setattr(hub, field, {})
    hub.tracer = Tracer(TraceConfig(enabled=False))
    hub.compile_values = {"Compile/process/cache_hits": 3.0,
                          "Compile/decode/cache_hits": 9.0}
    rows = hub.metrics_snapshot()
    assert ("Compile/process/cache_hits", 3.0, "counter") in rows
    assert ("Compile/cache_hits", 9.0, "counter",
            {"program": "decode"}) in rows


# --------------------------------------------------------------------------- #
# the benchmark's reader
# --------------------------------------------------------------------------- #
SERIES = {
    "closed_loop": {"kind": "closed_loop",
                    "tick_completion_s": [10.0, 20.0, 30.0, 40.0]},
    "train": {"kind": "train", "step_completion_s": [20.0, 30.0, 40.0]},
}
WINDOWS = {"closed_loop": (1, 3), "train": (0, 2)}       # both open at 20.0
WANT = {"trace_lower_s": 4.0, "backend_compile_s": 3.0, "cache_misses": 1,
        "programs_compiled": 1, "monitor_analysis_s": 1.5}


@pytest.mark.parametrize("what", sorted(WANT))
@pytest.mark.parametrize("kind", sorted(SERIES))
def test_reader_takes_what_ended_before_the_window(account, monkeypatch,
                                                   kind, what):
    monkeypatch.syspath_prepend(REPO)
    reader = importlib.import_module("benchmark.readers.setup_account")
    ctx = {"series": SERIES[kind], "window": WINDOWS[kind]}
    assert reader.read(ctx, what) is None          # an empty account
    _feed(account, TRACE, 1.0, 3.0, "f", monkeypatch)
    _feed(account, LOWER, 3.0, 5.0, "jit(f)", monkeypatch)
    monkeypatch.setattr(compile_mod.time, "perf_counter", lambda: 6.0)
    account.on_event("/jax/compilation_cache/cache_misses")
    _feed(account, BACKEND, 5.0, 8.0, "jit(f)", monkeypatch)
    account.record("monitor_analysis", "f", 8.0, 9.5)
    # the window's own: a compile that ends after it opened, whole
    _feed(account, TRACE, 19.0, 21.0, "g", monkeypatch)
    _feed(account, BACKEND, 21.0, 25.0, "jit(g)", monkeypatch)
    account.on_event("/jax/compilation_cache/cache_misses")
    assert reader.read(ctx, what) == pytest.approx(WANT[what])
    metric = json.load(open(os.path.join(
        REPO, "benchmark", "metrics", f"setup_{what}.json")))
    assert metric == {"name": f"setup_{what}", "reader": "setup_account",
                      "params": {"what": what}}
