"""Self-tuning runtime tests (docs/tuning.md): the tunable registry +
dot-path config walkers, the centralized `.dstpu_tuned.json` persistence
(atomic write, torn-tolerant read, env override) now shared with the
flash-attention lookup and `scripts/attn_sweep.py`, the guard board, the
online A/B tuner's full state machine (seeded convergence to a planted
optimum, noise-delta non-acceptance, revert-on-regression, guard veto,
min-sample starvation, drift-triggered retune, persist/reload-no-research),
the knob-coverage lint (every score series closed-schema, every apply
round-tripping through a real config tree), the `Tune/*` schema/hub/
Prometheus surface, the `telemetry_report.py --tuning` section, the
offline autotuner's registry-sourced space — and the default-OFF pins:
no tuner attached anywhere, train step HLO byte-identical, served token
streams identical."""

import dataclasses
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import (ReplicaRouter, Request, RouterConfig,
                                     SchedulerConfig, ServingScheduler,
                                     build_engine_v2)
from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.inference.serving import DONE
from deepspeed_tpu.models import llama
from deepspeed_tpu.runtime.config import parse_config
from deepspeed_tpu.telemetry.schema import (SCORE_SERIES, TRACER_INSTANTS,
                                            TRAIN_STEP_SERIES,
                                            TUNE_KNOB_METRICS,
                                            TUNE_TOTAL_SERIES,
                                            validate_events)
from deepspeed_tpu.tuning import (GuardBoard, OnlineTuner, Tunable,
                                  TunableRegistry, TunerOptions, config_get,
                                  config_set, default_registry, load_tuned,
                                  tuned_path, update_tuned, write_tuned)
from deepspeed_tpu.tuning.guards import GUARD_NAMES


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


@pytest.fixture(autouse=True)
def _isolate_tuned_file(tmp_path, monkeypatch):
    """Every test gets a private `.dstpu_tuned.json` — nothing in this
    module may touch the repo-root artifact."""
    monkeypatch.setenv("DSTPU_TUNED_PATH", str(tmp_path / "tuned.json"))
    yield


# --------------------------------------------------------------------------- #
# persistence (tuning/persist.py) — satellite: ONE resolver + atomic write
# --------------------------------------------------------------------------- #
def test_tuned_path_resolution(tmp_path, monkeypatch):
    # explicit arg beats the env override beats the repo-root default
    assert tuned_path("/x/y.json") == "/x/y.json"
    assert tuned_path() == str(tmp_path / "tuned.json")
    monkeypatch.delenv("DSTPU_TUNED_PATH")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert tuned_path() == os.path.join(repo, ".dstpu_tuned.json")


def test_load_tolerates_missing_torn_and_nonobject(tmp_path):
    assert load_tuned() == {}                       # missing
    p = tmp_path / "tuned.json"
    p.write_text('{"flash_block": 25')              # torn mid-write shape
    assert load_tuned() == {}
    p.write_text("[1, 2, 3]")                       # not an object
    assert load_tuned() == {}


def test_write_update_roundtrip_preserves_unknown_keys(tmp_path):
    write_tuned({"flash_block": 256})
    # the online tuner's winners merge without clobbering the sweep's keys
    merged = update_tuned({"train.prefetch_depth": 4})
    assert merged == {"flash_block": 256, "train.prefetch_depth": 4}
    assert load_tuned() == merged
    assert update_tuned({"flash_block": 512})["train.prefetch_depth"] == 4
    # the atomic write leaves no temp droppings behind
    stray = [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    assert stray == []


def test_flash_attention_lookup_through_persist(tmp_path):
    """Satellite pin: the kernel's tuned-block lookup reads the SAME file
    the resolver names, with bit-identical fallback semantics."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    def reset():
        fa._TUNED_CACHE.clear()

    reset()
    assert fa._tuned_default() == 512               # missing file → default
    write_tuned({"flash_block": 256, "flash_block_g2": 64})
    reset()
    assert fa._tuned_default() == 256
    assert fa._block(4096) == 256
    assert fa._block_gqa(4096, 2) == 64             # per-group key wins
    write_tuned({"flash_block": 257})               # not %8 → ignored
    reset()
    assert fa._tuned_default() == 512
    reset()                                         # leave no cross-test state


# --------------------------------------------------------------------------- #
# registry + dot-path walkers
# --------------------------------------------------------------------------- #
def test_config_walkers_dict_and_attr_trees():
    d = {"a": {"b": 1}}
    assert config_get(d, "a.b") == 1
    assert config_get(d, "a.z", default=7) == 7
    config_set(d, "a.c.d", 5)                       # creates dict interiors
    assert d["a"]["c"]["d"] == 5
    obj = types.SimpleNamespace(x=types.SimpleNamespace(y=2))
    assert config_get(obj, "x.y") == 2
    config_set(obj, "x.y", 3)
    assert obj.x.y == 3
    with pytest.raises(AttributeError, match="x.zz"):
        config_set(obj, "x.zz", 1)                  # typo'd path fails loudly
    # mixed tree: attr object holding a dict leaf
    obj2 = types.SimpleNamespace(cfg={"k": 0})
    config_set(obj2, "cfg.k", 9)
    assert obj2.cfg["k"] == 9


def test_tunable_validation_and_apply():
    mk = lambda **kw: Tunable(**dict(  # noqa: E731
        dict(name="t", path="p", choices=(1, 2),
             score_series="Train/Step/step_ms", mode="min",
             boundary="train_step"), **kw))
    for bad in (dict(mode="p99"), dict(boundary="anywhere"),
                dict(root="nowhere"), dict(choices=())):
        with pytest.raises(ValueError):
            mk(**bad)
    t = mk()
    d = {}
    t.apply(d, 2)
    assert t.get(d) == 2
    with pytest.raises(ValueError, match="not in"):
        t.apply(d, 3)                               # off-catalog value


def test_registry_filtering_and_errors():
    reg = default_registry()
    assert len(reg) >= 6
    assert reg.names() == sorted(reg.names())
    train = reg.for_boundary("train_step")
    sched = reg.for_boundary("sched_tick")
    offline = reg.for_boundary("offline")
    assert len(train) >= 3 and len(sched) >= 3 and len(offline) >= 2
    only = reg.for_boundary("train_step", ["train.remat_policy"])
    assert [t.name for t in only] == ["train.remat_policy"]
    with pytest.raises(KeyError, match="train.remat_polcy"):
        reg.for_boundary("train_step", ["train.remat_polcy"])
    with pytest.raises(ValueError, match="duplicate"):
        TunableRegistry(list(reg.all()) + [reg.all()[0]])


def test_knob_coverage_lint():
    """Satellite (tier-1 lint): every registered knob scores against a
    CLOSED-schema series, declares only known guards, names a legal event
    segment, and its every choice round-trips through a real config tree
    of its declared root."""
    mesh_lib.set_mesh(None)
    roots = {
        "train_config": parse_config({}),
        "train_dict": {},
        "inference_config": InferenceConfig(),
        "sched_config": SchedulerConfig(),
    }
    for t in default_registry().all():
        assert t.score_series in SCORE_SERIES, \
            f"{t.name}: score series {t.score_series!r} is not in a " \
            f"closed schema registry — nothing guarantees it is emitted"
        assert set(t.guards) <= set(GUARD_NAMES), t.name
        assert validate_events(
            [(f"Tune/knob/{t.name}/trials", 0.0, 0)]) == [], \
            f"{t.name} is not a legal Tune/knob event segment"
        root = roots[t.root]
        original = t.get(root)
        for choice in t.choices:
            t.apply(root, choice)
            assert t.get(root) == choice, (t.name, choice)
        if original is not None and any(original == c for c in t.choices):
            t.apply(root, original)                 # leave shared roots tidy


# --------------------------------------------------------------------------- #
# schema + hub + Prometheus surface
# --------------------------------------------------------------------------- #
def test_tune_schema_families_closed():
    ok = [(n, 1.0, 0) for n in sorted(TUNE_TOTAL_SERIES)]
    ok += [(f"Tune/knob/train.prefetch_depth/{m}", 1.0, 0)
           for m in sorted(TUNE_KNOB_METRICS)]
    assert validate_events(ok) == []
    for bad in ("Tune/total/bogus", "Tune/knob/x/bogus",
                "Tune/knob/missing_metric", "Tune/lonely"):
        assert validate_events([(bad, 1.0, 0)]), f"{bad} must be rejected"
    assert {"tune_step", "tune_revert"} <= TRACER_INSTANTS
    # Train/Step is now a closed family too (the tuner scores against it)
    assert validate_events([(n, 1.0, 0) for n in sorted(TRAIN_STEP_SERIES)]) \
        == []
    assert validate_events([("Train/Step/bogus_ms", 1.0, 0)])
    assert "Train/Step/step_ms" in SCORE_SERIES


def test_hub_tune_event_and_prometheus_fold():
    from deepspeed_tpu.telemetry import TelemetryHub
    from deepspeed_tpu.telemetry.metrics_server import render_prometheus

    hub = TelemetryHub(parse_config({}))
    hub.tune_event("Tune/total/trials", 3.0, step=7)
    hub.tune_event("Tune/knob/train.prefetch_depth/value", 1.0, step=7)
    hub.tune_event("Tune/knob/train.prefetch_depth/active", 0.0, step=7)
    assert hub.tune_values["Tune/total/trials"] == 3.0
    body = render_prometheus(hub.metrics_snapshot())
    assert "dstpu_tune_total_trials 3" in body
    assert 'dstpu_tune_value{knob="train.prefetch_depth"} 1' in body


# --------------------------------------------------------------------------- #
# guard board
# --------------------------------------------------------------------------- #
def _fake_hub(recompiles=0, spikes=0, enabled=True):
    st = types.SimpleNamespace(recompiles=recompiles)
    compile_mon = types.SimpleNamespace(enabled=enabled, stats={"p": st})
    return types.SimpleNamespace(
        compile=compile_mon, anomaly_counts={
            "Anomaly/Train/Step/step_ms/spike": spikes}), st


def test_guard_recompile_allowance_and_veto():
    hub, st = _fake_hub(recompiles=1)
    g = GuardBoard(hub=hub, recompile_allowance=2)
    g.arm(("recompile",))
    st.recompiles += 2                              # planned: within allowance
    assert g.verdict() is None
    g.arm(("recompile",))
    st.recompiles += 3                              # storm: past allowance
    v = g.verdict()
    assert v is not None and "recompile" in v
    # a DISABLED compile monitor contributes nothing (source passes)
    hub2, st2 = _fake_hub(recompiles=5, enabled=False)
    g2 = GuardBoard(hub=hub2)
    g2.arm(("recompile",))
    st2.recompiles += 50
    assert g2.verdict() is None


def test_guard_anomaly_and_slo_burn_zero_allowance():
    hub, _ = _fake_hub(spikes=2)
    obs = types.SimpleNamespace(accountant=types.SimpleNamespace(alerts=[]))
    g = GuardBoard(hub=hub, obs=obs)
    g.arm(GUARD_NAMES)
    assert g.verdict() is None                      # pre-existing counts OK
    hub.anomaly_counts["Anomaly/Train/Step/step_ms/spike"] += 1
    assert "anomaly" in g.verdict()
    g.arm(GUARD_NAMES)
    obs.accountant.alerts.append({"tenant": "bad"})
    assert "slo_burn" in g.verdict()
    # guards on a fully-unwired tuner pass (hub=None, obs=None)
    g3 = GuardBoard()
    g3.arm(GUARD_NAMES)
    assert g3.verdict() is None
    assert dict(g3.breakdown()) == {"recompile": 0.0, "anomaly": 0.0,
                                    "slo_burn": 0.0}
    with pytest.raises(KeyError, match="no_such_guard"):
        g3.arm(("no_such_guard",))


# --------------------------------------------------------------------------- #
# the online tuner state machine (synthetic knob, injected clock)
# --------------------------------------------------------------------------- #
def _mk_synth(mode="max", choices=(1, 2, 4), opts=None, hub=None, obs=None):
    """A tuner over ONE synthetic knob on a plain namespace root, scored on
    the serving goodput series, with a fully-injected clock."""
    reg = TunableRegistry([Tunable(
        "synth.lanes", "lanes", tuple(choices),
        "Serving/sched/goodput_frac", mode, "sched_tick",
        root="sched_config")])
    ns = types.SimpleNamespace(lanes=choices[0])
    clk = FakeClock()
    tuner = OnlineTuner(
        reg, opts or TunerOptions(enabled=True, steps_per_arm=5,
                                  min_samples=3, seed=0),
        boundary="sched_tick", roots={"sched_config": ns},
        hub=hub, obs=obs, clock=clk)
    return tuner, ns, clk


def _drive(tuner, ns, clk, score, steps=40):
    for step in range(steps):
        clk.advance(1.0)
        tuner.observe("Serving/sched/goodput_frac", score(ns.lanes, step))
        tuner.advance(step)


def test_convergence_to_planted_optimum_and_persist():
    planted = {1: 0.55, 2: 0.72, 4: 0.91}
    tuner, ns, clk = _mk_synth()
    _drive(tuner, ns, clk,
           lambda v, s: planted[v] + 0.004 * ((s * 7) % 5 - 2))
    assert ns.lanes == 4                            # planted winner applied
    st = tuner.states["synth.lanes"]
    assert st.phase == "closed" and st.incumbent == 4
    assert tuner.totals == {"trials": 2, "accepts": 1, "reverts": 0,
                            "vetoes": 0, "retunes": 0}
    assert load_tuned()["synth.lanes"] == 4         # atomic persisted winner
    ev = tuner.events(step=40)
    assert validate_events(ev) == []
    names = {n for n, _, _ in ev}
    assert f"Tune/knob/synth.lanes/value" in names
    assert tuner.tune_values["Tune/knob/synth.lanes/value"] == 2.0  # INDEX
    assert tuner.tune_values["Tune/total/closed_knobs"] == 1.0
    assert tuner.tune_values["Tune/knob/synth.lanes/score_delta"] > 0.0
    s = tuner.summary()
    assert s["knobs"]["synth.lanes"]["value"] == 4


def test_noise_delta_is_never_accepted():
    """Identical planted means + jitter: the MAD/min_rel_delta gate must
    keep the incumbent — an online tuner that chases noise is worse than
    no tuner."""
    tuner, ns, clk = _mk_synth()
    _drive(tuner, ns, clk,
           lambda v, s: 0.7 + 0.003 * ((s * 13) % 7 - 3))   # knob-blind
    st = tuner.states["synth.lanes"]
    assert st.phase == "closed"
    assert ns.lanes == 1 and st.incumbent == 1      # reverted to incumbent
    assert tuner.totals["accepts"] == 0
    assert tuner.totals["reverts"] >= 1             # last arm rolled back
    assert "synth.lanes" not in load_tuned()        # nothing persisted


def test_revert_on_regression():
    """Every arm strictly worse than the incumbent: the tuner must revert
    and close on the incumbent."""
    planted = {1: 0.9, 2: 0.5, 4: 0.3}
    tuner, ns, clk = _mk_synth()
    _drive(tuner, ns, clk, lambda v, s: planted[v])
    st = tuner.states["synth.lanes"]
    assert st.phase == "closed" and ns.lanes == 1 and st.incumbent == 1
    assert tuner.totals["accepts"] == 0 and tuner.totals["reverts"] == 1


def test_guard_veto_rejects_best_scoring_arm():
    """The planted-best arm trips the anomaly guard mid-window: it must be
    vetoed (reverted, unscored) and never win, regardless of its score."""
    hub, _ = _fake_hub()
    planted = {1: 0.5, 2: 0.6, 4: 0.95}
    tuner, ns, clk = _mk_synth(hub=hub)

    def score(v, step):
        if v == 4:                                  # the too-good-to-be-true
            hub.anomaly_counts["Anomaly/Train/Step/step_ms/spike"] += 1
        return planted[v]

    _drive(tuner, ns, clk, score)
    st = tuner.states["synth.lanes"]
    assert tuner.totals["vetoes"] == 1
    assert st.idx(4) not in st.results              # vetoed arm not scored
    assert ns.lanes == 2 and st.incumbent == 2      # clean runner-up won
    assert load_tuned()["synth.lanes"] == 2


def test_silent_series_closes_without_trials():
    """No samples ever arrive: after max_dwell the knob closes quietly —
    dwelling forever on a dead series would pin the tuner."""
    tuner, ns, clk = _mk_synth()
    for step in range(40):
        clk.advance(1.0)
        tuner.advance(step)                         # observe() never called
    st = tuner.states["synth.lanes"]
    assert st.phase == "closed" and tuner.totals["trials"] == 0
    assert ns.lanes == 1                            # untouched


def test_drift_reopens_closed_knob_and_retunes():
    """PR-10-style anomaly drift findings re-open a settled search, and the
    re-search converges on the NEW optimum."""
    hub, _ = _fake_hub()
    hub.anomaly_counts["Anomaly/Train/Step/step_ms/drift"] = 0
    planted = {1: 0.9, 2: 0.6, 4: 0.3}
    tuner, ns, clk = _mk_synth(hub=hub)
    _drive(tuner, ns, clk, lambda v, s: planted[v])
    assert tuner.states["synth.lanes"].phase == "closed" and ns.lanes == 1
    # the workload moves: drift counter rises → knob re-opens
    hub.anomaly_counts["Anomaly/Train/Step/step_ms/drift"] += 1
    tuner._drift_from_counters(hub.anomaly_counts,
                               lambda k: k.endswith("/drift"), "drift test")
    st = tuner.states["synth.lanes"]
    assert st.phase == "baseline" and st.counts["retunes"] == 1
    assert tuner.totals["retunes"] == 1
    # ... and the planted optimum has moved too: the retune finds it
    planted.update({1: 0.3, 4: 0.95})
    _drive(tuner, ns, clk, lambda v, s: planted[v])
    assert st.phase == "closed" and ns.lanes == 4
    assert load_tuned()["synth.lanes"] == 4


def test_on_train_step_drift_hook():
    """The optimizer-step seam picks drift findings straight off the hub's
    anomaly counters."""
    hub, _ = _fake_hub()
    hub.anomaly_counts["Anomaly/Train/Step/step_ms/drift"] = 0
    reg = TunableRegistry([Tunable(
        "synth.depth", "depth", (1, 2), "Train/Step/step_ms", "min",
        "train_step", root="train_config")])
    ns = types.SimpleNamespace(depth=1)
    clk = FakeClock()
    tuner = OnlineTuner(reg, TunerOptions(enabled=True, steps_per_arm=4,
                                          min_samples=2, seed=0),
                        boundary="train_step", roots={"train_config": ns},
                        hub=hub, clock=clk)
    planted = {1: 10.0, 2: 4.0}
    for step in range(30):
        clk.advance(1.0)
        tuner.on_train_step(step, step_time_s=planted[ns.depth] / 1e3)
    st = tuner.states["synth.depth"]
    assert st.phase == "closed" and ns.depth == 2   # min mode: faster wins
    hub.anomaly_counts["Anomaly/Train/Step/step_ms/drift"] = 1
    tuner.on_train_step(31, step_time_s=0.004)
    assert st.phase != "closed" and st.counts["retunes"] == 1


def test_persist_reload_skips_research_and_ignores_stale():
    tuner, ns, clk = _mk_synth()
    planted = {1: 0.5, 2: 0.6, 4: 0.95}
    _drive(tuner, ns, clk, lambda v, s: planted[v])
    assert load_tuned()["synth.lanes"] == 4
    # a FRESH process: winner reloads applied + closed, zero trials burned
    fresh, ns2, _ = _mk_synth()
    assert ns2.lanes == 4
    assert fresh.states["synth.lanes"].phase == "closed"
    assert fresh.totals["trials"] == 0
    # a stale persisted value outside the catalog is ignored → re-search
    update_tuned({"synth.lanes": 999})
    stale, ns3, _ = _mk_synth()
    assert ns3.lanes == 1                           # untouched default
    assert stale.states["synth.lanes"].phase == "baseline"
    # reload=False opts out entirely
    update_tuned({"synth.lanes": 4})
    opts = TunerOptions(enabled=True, steps_per_arm=5, min_samples=3,
                        reload=False)
    noreload, ns4, _ = _mk_synth(opts=opts)
    assert ns4.lanes == 1
    assert noreload.states["synth.lanes"].phase == "baseline"


def test_tuner_options_from_any_and_config_block():
    with pytest.raises(ValueError, match="unknown tuning option"):
        TunerOptions.from_dict({"steps_per_arms": 4})
    o = TunerOptions.from_dict({"enabled": True, "knobs": ["a"],
                                "accept_mads": 2.5})
    assert o.enabled and o.knobs == ("a",) and o.accept_mads == 2.5
    # the runtime config block carries the same fields through parse_config
    cfg = parse_config({"tuning": {"enabled": True, "steps_per_arm": 9,
                                   "knobs": ["train.remat_policy"]}})
    assert cfg.tuning.enabled and cfg.tuning.steps_per_arm == 9
    o2 = TunerOptions.from_any(cfg.tuning)
    assert o2.steps_per_arm == 9 and o2.knobs == ("train.remat_policy",)
    assert parse_config({}).tuning.enabled is False
    # unknown knob names fail loudly at tuner construction
    reg = default_registry()
    with pytest.raises(KeyError, match="train.nope"):
        reg.for_boundary("train_step", ["train.nope"])


# --------------------------------------------------------------------------- #
# offline autotuner rides the same catalog (satellite)
# --------------------------------------------------------------------------- #
def test_autotuner_space_sourced_from_registry():
    from deepspeed_tpu.autotuning.autotuner import (DEFAULT_MICRO_BATCHES,
                                                    DEFAULT_STAGES,
                                                    Autotuner)

    reg = default_registry()
    assert tuple(DEFAULT_MICRO_BATCHES) == reg.choices("train.micro_batch")
    assert tuple(DEFAULT_STAGES) == reg.choices("train.zero_stage")
    a = Autotuner.__new__(Autotuner)
    a.base_config = {"train_batch_size": 8, "bf16": {"enabled": True}}
    cfg = a._trial_config({"micro_batch": 2, "gas": 4, "zero_stage": 3,
                           "remat": True})
    # byte-for-byte the shape the seed autotuner always produced
    assert cfg == {"bf16": {"enabled": True},
                   "train_micro_batch_size_per_gpu": 2,
                   "gradient_accumulation_steps": 4,
                   "zero_optimization": {"stage": 3},
                   "activation_checkpointing": {"policy": "full"},
                   "steps_per_print": 0}
    assert a._trial_config({"micro_batch": 1, "gas": 8, "zero_stage": 0,
                            "remat": False}
                           )["activation_checkpointing"]["policy"] == "none"


# --------------------------------------------------------------------------- #
# telemetry_report --tuning (offline section)
# --------------------------------------------------------------------------- #
def test_telemetry_report_tuning_section(tmp_path):
    from deepspeed_tpu.monitor.monitor import JSONLMonitor

    class Cfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "job"

    mon = JSONLMonitor(Cfg())
    mon.write_events([
        ("Tune/total/trials", 2.0, 5),
        ("Tune/total/accepts", 1.0, 5),
        ("Tune/total/reverts", 0.0, 5),
        ("Tune/total/vetoes", 0.0, 5),
        ("Tune/total/retunes", 0.0, 5),
        ("Tune/total/open_knobs", 0.0, 5),
        ("Tune/total/closed_knobs", 1.0, 5),
        ("Tune/knob/train.prefetch_depth/trials", 2.0, 5),
        ("Tune/knob/train.prefetch_depth/accepts", 1.0, 5),
        ("Tune/knob/train.prefetch_depth/value", 2.0, 5),
        ("Tune/knob/train.prefetch_depth/active", 0.0, 5),
        ("Tune/knob/train.prefetch_depth/score_delta", 1.75, 5)])
    mon.close()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "scripts", "telemetry_report.py")
    events = str(tmp_path / "job" / "events.jsonl")
    out = subprocess.run([sys.executable, script, events, "--tuning"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "self-tuning runtime" in out.stdout
    assert "totals: trials=2  accepts=1" in out.stdout
    assert "train.prefetch_depth" in out.stdout
    assert "closed" in out.stdout
    assert "accept #1" in out.stdout                # accepted-winner history
    # --all carries the section too
    out = subprocess.run([sys.executable, script, events, "--all"],
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "self-tuning runtime" in out.stdout


# --------------------------------------------------------------------------- #
# serving integration + default-OFF token identity
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(max_seq_len=256)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _build_serving(tiny, **kw):
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    return build_engine_v2(
        llama, cfg, params,
        config=dict({"dtype": "float32", "prefill_bucket": 16,
                     "ragged": {"max_tracked_sequences": 4,
                                "max_ragged_batch_size": 4,
                                "memory_config_blocks": 64,
                                "block_size": 16}}, **kw))


@pytest.fixture(scope="module")
def seng2(tiny):
    return [_build_serving(tiny), _build_serving(tiny)]


def test_router_config_tuning_block():
    rc = RouterConfig.from_dict({"tuning": {"enabled": True,
                                            "knobs": ["serving.sched_lookahead"],
                                            "steps_per_arm": 4}})
    assert rc.tuning.enabled and rc.tuning.steps_per_arm == 4
    assert RouterConfig.from_dict(None).tuning.enabled is False
    assert RouterConfig.from_dict({}).tuning.enabled is False
    with pytest.raises(ValueError, match="unknown tuning option"):
        RouterConfig.from_dict({"tuning": {"step_per_arm": 4}})


def test_serving_default_off_no_tuner_token_identity(tiny, seng2):
    """Default config: no tuner object exists anywhere on the serving path
    and routed token streams match a plain single-scheduler run exactly."""
    cfg, _ = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, (12,)).tolist()
               for _ in range(4)]
    oracle = ServingScheduler(seng2[0])
    assert oracle.tuning is None
    want = [oracle.submit(Request(prompt=list(p), max_new_tokens=6))
            for p in prompts]
    oracle.run()
    scheds = [ServingScheduler(e) for e in seng2]
    router = ReplicaRouter(scheds, RouterConfig(load_slack=100))
    assert all(s.tuning is None for s in scheds)
    got = [router.submit(Request(prompt=list(p), max_new_tokens=6))
           for p in prompts]
    router.run()
    for h, w in zip(got, want):
        assert h.state == DONE and h.tokens == w.tokens


def test_serving_tuner_attaches_and_searches(tiny, seng2):
    """Router with ``tuning.enabled``: per-replica tuners attach at the
    tick seam, score windowed goodput, search the lookahead knob, and the
    fleet still completes every request with the knob inside its catalog."""
    cfg, _ = tiny
    clk = FakeClock(100.0)
    scheds = [ServingScheduler(e, SchedulerConfig(clock=clk))
              for e in seng2]
    router = ReplicaRouter(scheds, RouterConfig(
        load_slack=100,
        tuning=TunerOptions(enabled=True,
                            knobs=("serving.sched_lookahead",),
                            steps_per_arm=3, min_samples=1, seed=0,
                            persist=False)))
    assert all(s.tuning is not None for s in scheds)
    reg = default_registry()
    rng = np.random.default_rng(5)
    handles = []
    for i in range(12):
        handles.append(router.submit(Request(
            prompt=rng.integers(0, cfg.vocab_size, (10,)).tolist(),
            max_new_tokens=4)))
        clk.advance(1.0)
        router.step()
    for _ in range(60):
        if all(h.state == DONE for h in handles):
            break
        clk.advance(1.0)
        router.step()
    assert all(h.state == DONE for h in handles)
    for s in scheds:
        assert s.cfg.admission_lookahead in \
            reg.choices("serving.sched_lookahead")
        assert "serving.sched_lookahead" in s.tuning.states
        assert validate_events(s.tuning.events(step=0)) == []
    # at least one replica saw completions → recorded goodput samples
    assert any(
        s.tuning.tsdb.summary("Serving/sched/goodput_frac")["count"] > 0
        for s in scheds)


# --------------------------------------------------------------------------- #
# training engine integration + default-OFF byte identity
# --------------------------------------------------------------------------- #
V = 64


def _llama_cfg():
    return llama.LlamaConfig(vocab_size=V, hidden_size=32,
                             intermediate_size=64, num_layers=2, num_heads=4,
                             num_kv_heads=2, max_seq_len=64)


def _mk_engine(extra=None):
    mesh_lib.set_mesh(None)
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 2},
           "steps_per_print": 0, "seed": 7}
    cfg.update(extra or {})
    spec = llama.model_spec(_llama_cfg(), compute_dtype=jnp.float32)
    engine, *_ = dst.initialize(model=spec, config=cfg)
    return engine


def _batch(seed=0, b=8, s=33):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(0, V, (b, s)).astype(np.int32)}


def _lowered(e):
    if e._train_step is None:
        e._build_train_step()
    sb = e._shard_batch(_batch(seed=1), with_gas_dim=True)
    with e.mesh_mgr.activate():
        return e._train_step.lower(e.state, sb, e._lr_override).as_text()


def test_train_default_off_byte_identical(devices8):
    """Default-OFF pin: no ``tuning`` block, an explicitly-disabled block,
    and the pre-tuning build all lower the SAME train step — and no tuner
    object hangs off the engine."""
    e_def = _mk_engine()
    e_off = _mk_engine({"tuning": {"enabled": False}})
    assert e_def.tuning is None and e_off.tuning is None
    assert _lowered(e_def) == _lowered(e_off)


def test_train_engine_tuner_end_to_end():
    """Engine with the ``tuning`` block on the remat knob: the tuner runs
    real trial arms at the optimizer-step seam (invalidating the compiled
    step once per apply), scores them off last_step_time, never trips a
    guard, and training stays healthy throughout."""
    e = _mk_engine({"tuning": {"enabled": True,
                               "knobs": ["train.remat_policy"],
                               "steps_per_arm": 3, "min_samples": 2,
                               "max_dwell_factor": 2, "seed": 0}})
    assert e.tuning is not None
    assert set(e.tuning.states) == {"train.remat_policy"}
    losses = []
    for i in range(16):
        losses.append(float(e.train_batch(_batch(seed=i)).loss))
    assert all(np.isfinite(losses))
    t = e.tuning
    st = t.states["train.remat_policy"]
    assert t.totals["trials"] >= 1                  # real arms ran
    assert t.totals["vetoes"] == 0                  # no guard violations
    assert e.config.activation_checkpointing.policy in \
        ("none", "dots_saveable", "full")
    assert validate_events(t.events(step=16)) == []
    # the hub carried the Tune/* gauges out through telemetry
    assert any(k.startswith("Tune/total/")
               for k in e.telemetry.tune_values)
    # winners (if any) landed in the isolated tuned file, not the repo root
    for k in load_tuned():
        assert k == "train.remat_policy"
    if st.phase == "closed" and t.totals["accepts"]:
        assert load_tuned()["train.remat_policy"] == st.incumbent
