"""Elastic preempt→reshard→resume drill (the elastic training runtime's
acceptance harness; docs/reliability.md "Elastic training & universal
checkpoint").

``elastic_drill`` proves the tentpole guarantee end to end, on the CPU mesh,
with seeded determinism: train a reference run uninterrupted, then replay the
SAME run through a sequence of topology phases — train, get killed (a
scheduled preemption or an injected host loss), save a universal checkpoint
with a reshard hint, come back at a DIFFERENT (chips, ZeRO stage, optimizer
tier), fast-forward the dataloader, and keep going — asserting the drilled
loss trajectory equals the uninterrupted one to ``tol`` at every step. Each
phase is one (topology, stage, tier) combination, so a 3-phase drill covers
3 matrix cells.

Also runnable standalone::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m deepspeed_tpu.testing.drill
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import faults


@dataclasses.dataclass
class DrillPhase:
    """One incarnation of the job: its topology and how it ends."""

    chips: int
    zero_stage: int = 0
    optimizer_tier: str = "none"   # none | host
    hpz: int = 1                   # zero_hpz_partition_size (stage 3 only)
    steps: int = 2                 # steps before the injected kill
    fault: str = "preempt"         # preempt | host_loss

    def label(self) -> str:
        t = f"/{self.optimizer_tier}" if self.optimizer_tier != "none" else ""
        h = f"/hpz{self.hpz}" if self.hpz > 1 else ""
        return f"chips{self.chips}/z{self.zero_stage}{t}{h}"


def _drill_spec(dim: int = 8):
    """A tiny deterministic regression model whose loss is a mean over the
    batch dim — so every (micro, gas, dp) split of the same global batch
    computes the identical trajectory up to fp reassociation."""
    import jax
    import jax.numpy as jnp

    from ..runtime.engine import ModelSpec

    def loss_fn(p, b):
        pred = b["x"] @ p["w"]
        return jnp.mean(jnp.sum((pred - b["y"]) ** 2, axis=-1)), {}

    def init_fn(key):
        return {"w": jax.random.normal(key, (dim, dim), jnp.float32) * 0.3}

    return ModelSpec(loss_fn=loss_fn, init_fn=init_fn,
                     pipeline_capable=False, name="drill")


def _drill_dataset(n: int, dim: int = 8, seed: int = 0) -> List[Dict]:
    rng = np.random.default_rng(seed)
    return [{"x": rng.standard_normal(dim).astype(np.float32),
             "y": rng.standard_normal(dim).astype(np.float32)}
            for _ in range(n)]


def _phase_config(phase: DrillPhase, elastic: Dict, seed: int) -> Dict:
    cfg: Dict[str, Any] = {
        "elasticity": dict(elastic),
        "optimizer": {"type": "adamw", "params": {"lr": 0.05}},
        "zero_optimization": {"stage": int(phase.zero_stage)},
        "checkpoint": {"engine": "fast"},
        "steps_per_print": 0,
        "seed": int(seed),
    }
    if phase.hpz > 1:
        cfg["zero_optimization"]["zero_hpz_partition_size"] = int(phase.hpz)
    if phase.optimizer_tier == "host":
        cfg["memory"] = {"tiering": {"enabled": True,
                                     "optimizer_tier": "host"}}
    if phase.fault == "host_loss":
        cfg["watchdog"] = {"enabled": True, "heartbeat": True,
                           "heartbeat_max_missed": 2}
    return cfg


def _reset_process_state() -> None:
    """Engines publish process-wide state (global mesh, layer-prefetch
    routing); a drill builds several in one process, so each phase starts
    from a clean slate exactly like a fresh incarnation would."""
    from ..comm import mesh as mesh_mod

    mesh_mod.set_mesh(None)


def elastic_drill(workdir: str, phases: Optional[Sequence[DrillPhase]] = None,
                  total_steps: int = 6, seed: int = 0, global_batch: int = 8,
                  micro_batch_sizes: Sequence[int] = (1, 2, 4),
                  dim: int = 8, tol: float = 1e-6,
                  assert_equal: bool = True) -> Dict[str, Any]:
    """Run the seeded train→kill→reshard→resume cycle and compare against an
    uninterrupted run. Returns a result dict; with ``assert_equal`` (the
    default) an out-of-tolerance trajectory raises ``AssertionError``."""
    import jax

    from ..elasticity import PreemptionGuard, read_reshard_hint, run_elastic

    if phases is None:
        # the default matrix: shrink with a stage change, then grow with
        # another — three (topology, stage, tier) cells in one drill
        phases = [DrillPhase(chips=8, zero_stage=2, steps=2),
                  DrillPhase(chips=4, zero_stage=1, steps=2),
                  DrillPhase(chips=8, zero_stage=3)]
    phases = list(phases)
    if len(phases) < 2:
        raise ValueError("elastic_drill needs >= 2 phases (train → resume)")
    n_avail = len(jax.devices())
    if any(p.chips > n_avail for p in phases):
        raise ValueError(f"drill phase wants more chips than the "
                         f"{n_avail}-device mesh provides")
    elastic = {"enabled": True, "max_train_batch_size": int(global_batch),
               "micro_batch_sizes": [int(m) for m in micro_batch_sizes],
               "min_gpus": 1, "max_gpus": n_avail,
               "prefer_larger_batch": True}
    spec = _drill_spec(dim)
    dataset = _drill_dataset(global_batch * (total_steps + 2), dim, seed)
    ckpt = os.path.join(workdir, "elastic_ckpt")

    def _train(engine, loader, guard, budget, fault, hb_cm):
        losses = []
        exited = False
        cm = faults.preempt_at_step(guard, engine.global_steps + budget) \
            if fault == "preempt" else None
        try:
            if cm is not None:
                cm.__enter__()
            for batch in loader:
                out = engine.train_batch(batch)
                losses.append(float(out.loss))
                if guard.step_boundary(engine):
                    exited = True
                    break
                if fault is None and len(losses) >= budget:
                    break
                if len(losses) >= budget + 5:
                    break  # injected fault never fired — fail below, no hang
        finally:
            if cm is not None:
                cm.__exit__(None, None, None)
            if hb_cm is not None:
                hb_cm.__exit__(None, None, None)
        return losses, exited

    # ---- uninterrupted reference at the FIRST phase's topology ----
    _reset_process_state()
    engine, _, loader, _ = run_elastic(spec, _phase_config(
        phases[0], elastic, seed), checkpoint_dir=None,
        n_chips=phases[0].chips, training_data=dataset)
    baseline: List[float] = []
    for batch in loader:
        baseline.append(float(engine.train_batch(batch).loss))
        if len(baseline) >= total_steps:
            break
    engine.destroy()

    # ---- the drill: kill → reshard → resume through the phases ----
    drill: List[float] = []
    phase_meta: List[Dict[str, Any]] = []
    events: Dict[str, int] = {}
    for i, ph in enumerate(phases):
        _reset_process_state()
        engine, _, loader, _ = run_elastic(
            spec, _phase_config(ph, elastic, seed), checkpoint_dir=ckpt,
            n_chips=ph.chips, training_data=dataset)
        guard = PreemptionGuard(ckpt, signals=(), universal=True,
                                watchdog=engine.watchdog)
        if i > 0 and engine.global_steps != len(drill):
            raise AssertionError(
                f"phase {i} resumed at step {engine.global_steps}, expected "
                f"{len(drill)}")
        last = i == len(phases) - 1
        budget = (total_steps - len(drill)) if last else ph.steps
        fault = None if last else ph.fault
        hb_cm = None
        if fault == "host_loss":
            hb = getattr(engine.watchdog, "heartbeat", None)
            if hb is None:
                raise RuntimeError("host_loss phase needs watchdog.heartbeat")
            # heartbeat_max_missed=2: the peer freezes so its second stale
            # gather — and the exit — lands exactly at step `budget`
            hb_cm = faults.host_loss(hb, peer=1, world=2,
                                     after_beats=max(0, budget - 2))
            hb_cm.__enter__()
        try:
            losses, exited = _train(engine, loader, guard, budget, fault,
                                    hb_cm)
        finally:
            guard.uninstall()
        if fault is not None and not exited:
            raise AssertionError(
                f"phase {i} ({ph.label()}) never exited on its injected "
                f"{fault}")
        drill.extend(losses)
        phase_meta.append({"phase": ph.label(), "steps": len(losses),
                           "fault": fault,
                           "resumed_at": engine.global_steps - len(losses)})
        if not last:
            tel = getattr(engine, "telemetry", None)
            if tel is not None:
                for k, v in getattr(tel, "reliability_counts", {}).items():
                    events[k] = events.get(k, 0) + int(v)
            engine.destroy()

    hint = read_reshard_hint(ckpt)
    base = np.asarray(baseline)
    got = np.asarray(drill)
    ok = len(got) == len(base)
    max_err = float("inf")
    if ok:
        denom = np.maximum(1.0, np.abs(base))
        max_err = float(np.max(np.abs(got - base) / denom)) if len(base) \
            else 0.0
        ok = max_err <= tol
    # the verdict itself is telemetry (Reliability/elastic/drill_pass) —
    # emitted through the final incarnation's hub before it closes
    tel = getattr(engine, "telemetry", None)
    if tel is not None and hasattr(tel, "reliability_event"):
        tel.reliability_event("elastic/drill_pass", 1.0 if ok else 0.0,
                              int(engine.global_steps))
        for k, v in getattr(tel, "reliability_counts", {}).items():
            events[k] = events.get(k, 0) + int(v)
    engine.destroy()
    _reset_process_state()
    result = {
        "pass": bool(ok),
        "max_rel_err": max_err,
        "tol": tol,
        "steps": len(got),
        "baseline_losses": baseline,
        "drill_losses": drill,
        "phases": phase_meta,
        "reshard_hint": hint,
        "reliability_events": events,
    }
    if assert_equal and not ok:
        raise AssertionError(
            f"elastic drill trajectory diverged: max_rel_err={max_err:.3e} "
            f"(tol={tol:g}) over {len(got)}/{len(base)} steps; phases="
            f"{[p['phase'] for p in phase_meta]}")
    return result


def _sdc_config(elastic: Dict, seed: int, integrity: Dict) -> Dict:
    return {
        "elasticity": dict(elastic),
        "optimizer": {"type": "adamw", "params": {"lr": 0.05}},
        "zero_optimization": {"stage": 2},
        "checkpoint": {"engine": "fast"},
        "steps_per_print": 0,
        "seed": int(seed),
        "reliability": {"integrity": dict(integrity)},
    }


def sdc_drill(workdir: str, sites: Sequence[str] = ("grad", "param",
                                                    "opt_moment"),
              world: int = 4, bad_host: int = 2, total_steps: int = 8,
              seed: int = 0, global_batch: int = 8, dim: int = 8,
              check_interval: int = 2, tol: float = 1e-6,
              assert_equal: bool = True) -> Dict[str, Any]:
    """Silent-data-corruption drill (docs/reliability.md "Numerics
    integrity & SDC"): inject → detect → attribute → quarantine → reshard →
    resume, asserting the resumed loss trajectory rejoins the clean
    reference to ``tol`` at every step.

    Three legs, all seeded, all on the CPU mesh:

    1. **detection**: for each corruption ``site`` (post-reduce grad,
       replicated param, optimizer moment), a real bit flip on simulated
       host ``bad_host`` of ``world`` must be caught by the cross-replica
       vote within ``check_interval`` steps and attributed to that host;
    2. **quarantine**: repeated attribution crosses the threshold → durable
       universal save + ``reshard_hint.json`` with ``excluded_hosts`` →
       ``run_elastic`` reshards onto the surviving hosts' devices and the
       trajectory continues exactly on the clean reference;
    3. **walk-back**: an all-replica compute fault (``mode="compute"``) is
       invisible to the vote but caught by the shadow recompute audit —
       resume must walk BACK to the newest verified tag (never the newer,
       suspect one) and replay forward on the clean trajectory.
    """
    import jax

    import deepspeed_tpu as dst

    from ..elasticity import PreemptionGuard, read_reshard_hint, run_elastic

    n_avail = len(jax.devices())
    elastic = {"enabled": True, "max_train_batch_size": int(global_batch),
               "micro_batch_sizes": [1, 2, 4], "min_gpus": 1,
               "max_gpus": n_avail, "prefer_larger_batch": True}
    spec = _drill_spec(dim)
    dataset = _drill_dataset(global_batch * (total_steps + 2), dim, seed)
    host_of = lambda d: int(d.id) % int(world)  # noqa: E731 — sim fleet

    # ---- clean reference: per-step losses, integrity ON, no faults ----
    _reset_process_state()
    engine, _, loader, _ = run_elastic(
        spec, _sdc_config(elastic, seed, {"enabled": True,
                                          "check_interval": check_interval}),
        checkpoint_dir=None, n_chips=n_avail, training_data=dataset)
    baseline: List[float] = []
    for batch in loader:
        baseline.append(float(engine.train_batch(batch).loss))
        if len(baseline) >= total_steps:
            break
    engine.destroy()

    obs: List[Any] = []  # every drilled (step, loss) incl. walk-back replays

    def _run(engine, loader, guard, budget, cm) -> bool:
        exited = False
        try:
            for batch in loader:
                out = engine.train_batch(batch)
                obs.append((int(engine.global_steps), float(out.loss)))
                if guard is not None and guard.step_boundary(engine):
                    exited = True
                    break
                budget -= 1
                if budget <= 0:
                    break
        finally:
            if cm is not None:
                cm.__exit__(None, None, None)
        return exited

    # ---- leg 1: detection + attribution at every corruption site ----
    detections: List[Dict[str, Any]] = []
    for site in sites:
        _reset_process_state()
        engine, _, loader, _ = dst.initialize(
            model=spec,
            config=_sdc_config(elastic, seed, {
                "enabled": True, "check_interval": check_interval,
                "quarantine_threshold": 0, "on_corruption": "warn"}),
            training_data=dataset)
        it = iter(loader)
        for _ in range(check_interval):  # a clean check round first
            engine.train_batch(next(it))
        plane = engine.integrity
        if plane.last_report is None or plane.last_report["mismatched_hosts"]:
            raise AssertionError(f"site {site}: clean run failed its own "
                                 f"digest vote: {plane.last_report}")
        cm = faults.bit_flip(engine, site=site, host=bad_host, world=world,
                             index=3, bit=23)
        inj = cm.__enter__()
        try:
            for _ in range(check_interval):
                engine.train_batch(next(it))
        finally:
            cm.__exit__(None, None, None)
        rep = plane.last_report or {}
        delay = rep.get("step", 1 << 30) - (inj["first_step"] or 0)
        ok = rep.get("mismatched_hosts") == [bad_host] and \
            0 <= delay < check_interval
        detections.append({"site": site, "ok": bool(ok), "delay": int(delay),
                           "report": rep})
        engine.destroy()
        if not ok:
            break

    # ---- leg 2: quarantine → excluded_hosts reshard → resume ----
    ckpt = os.path.join(workdir, "sdc_quarantine")
    _reset_process_state()
    integ = {"enabled": True, "check_interval": check_interval,
             "quarantine_threshold": 2, "on_corruption": "exit"}
    engine, _, loader, _ = run_elastic(
        spec, _sdc_config(elastic, seed, integ), checkpoint_dir=ckpt,
        n_chips=n_avail, training_data=dataset, device_host_fn=host_of)
    guard = PreemptionGuard(ckpt, signals=(), universal=True)
    cm = faults.bit_flip(engine, site="param", host=bad_host, world=world,
                         index=3, bit=23)
    cm.__enter__()
    quarantined = _run(engine, loader, guard, budget=total_steps, cm=cm)
    guard.uninstall()
    exit_step = int(engine.global_steps)
    engine.destroy()
    hint = read_reshard_hint(ckpt)
    quarantine_ok = bool(
        quarantined and hint
        and hint.get("excluded_hosts") == [int(bad_host)]
        and not hint.get("walkback_to_verified"))
    resumed_chips = None
    if quarantine_ok:
        _reset_process_state()
        engine, _, loader, _ = run_elastic(
            spec, _sdc_config(elastic, seed, integ), checkpoint_dir=ckpt,
            training_data=dataset, device_host_fn=host_of)
        resumed_chips = int(engine.mesh_mgr.world_size)
        quarantine_ok = engine.global_steps == exit_step and \
            resumed_chips < n_avail
        guard = PreemptionGuard(ckpt, signals=(), universal=True)
        _run(engine, loader, guard, budget=total_steps - exit_step, cm=None)
        guard.uninstall()
        engine.destroy()

    # ---- leg 3: audit-confirmed compute fault → checkpoint walk-back ----
    ckpt2 = os.path.join(workdir, "sdc_walkback")
    _reset_process_state()
    integ2 = {"enabled": True, "check_interval": 0, "audit_interval": 2,
              "quarantine_threshold": 0, "on_corruption": "exit"}
    engine, _, loader, _ = run_elastic(
        spec, _sdc_config(elastic, seed, integ2), checkpoint_dir=ckpt2,
        n_chips=n_avail, training_data=dataset)
    guard = PreemptionGuard(ckpt2, signals=(), universal=True)
    it = iter(loader)
    verified_tag_step = 3
    for _ in range(verified_tag_step):
        out = engine.train_batch(next(it))
        obs.append((int(engine.global_steps), float(out.loss)))
    engine.save_universal_checkpoint(ckpt2)  # the verified tag to walk to
    out = engine.train_batch(next(it))  # step 4: audit verifies
    obs.append((int(engine.global_steps), float(out.loss)))
    last_verified = int(engine.integrity.last_verified_step)
    cm = faults.bit_flip(engine, site="param", mode="compute", world=1,
                         host=0, index=3, bit=23)
    cm.__enter__()
    walked = False
    try:
        for _ in range(2 * 2 + 1):  # next audit round must catch it
            out = engine.train_batch(next(it))
            obs.append((int(engine.global_steps), float(out.loss)))
            if guard.step_boundary(engine):
                walked = True
                break
    finally:
        cm.__exit__(None, None, None)
        guard.uninstall()
    suspect_step = int(engine.global_steps)
    engine.destroy()
    hint2 = read_reshard_hint(ckpt2)
    walkback_ok = bool(
        walked and hint2 and hint2.get("walkback_to_verified")
        and int(hint2.get("last_verified_step", -1)) == last_verified
        and suspect_step > verified_tag_step)
    if walkback_ok:
        _reset_process_state()
        engine, _, loader, _ = run_elastic(
            spec, _sdc_config(elastic, seed, integ2), checkpoint_dir=ckpt2,
            n_chips=n_avail, training_data=dataset)
        # resumed BEHIND the suspect save, at the verified tag
        walkback_ok = engine.global_steps == verified_tag_step
        _run(engine, loader, None, budget=total_steps - verified_tag_step,
             cm=None)
        events = dict(getattr(engine.telemetry, "reliability_counts", {}))
        engine.destroy()
    else:
        events = {}
    _reset_process_state()

    # ---- verdict: every drilled observation rejoins the reference ----
    max_err = 0.0
    covered = set()
    for step, loss in obs:
        if not 1 <= step <= len(baseline):
            max_err = float("inf")
            continue
        ref = baseline[step - 1]
        max_err = max(max_err, abs(loss - ref) / max(1.0, abs(ref)))
        covered.add(step)
    traj_ok = max_err <= tol and covered == set(range(1, total_steps + 1))
    ok = (traj_ok and quarantine_ok and walkback_ok
          and all(d["ok"] for d in detections)
          and len(detections) == len(list(sites)))
    result = {
        "pass": bool(ok),
        "max_rel_err": float(max_err),
        "tol": tol,
        "detections": detections,
        "quarantine": {"ok": quarantine_ok, "exit_step": exit_step,
                       "hint": hint, "resumed_chips": resumed_chips},
        "walkback": {"ok": walkback_ok, "suspect_step": suspect_step,
                     "hint": hint2, "last_verified": last_verified},
        "steps": len(obs),
        "baseline_losses": baseline,
        "reliability_events": events,
    }
    if assert_equal and not ok:
        raise AssertionError(
            f"sdc drill failed: detections="
            f"{[(d['site'], d['ok']) for d in detections]} "
            f"quarantine_ok={quarantine_ok} walkback_ok={walkback_ok} "
            f"max_rel_err={max_err:.3e} (tol={tol:g})")
    return result


def main(argv=None) -> int:
    """Standalone entry: run a drill on a temp dir and print a one-line
    verdict."""
    import argparse
    import json
    import tempfile

    p = argparse.ArgumentParser(prog="python -m deepspeed_tpu.testing.drill")
    p.add_argument("--steps", type=int, default=6)
    p.add_argument("--tol", type=float, default=1e-6)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--sdc", action="store_true",
                   help="run the SDC integrity drill instead of the "
                        "elastic topology drill")
    p.add_argument("--json", action="store_true",
                   help="dump the full result dict as JSON")
    args = p.parse_args(argv)
    with tempfile.TemporaryDirectory() as d:
        try:
            if args.sdc:
                res = sdc_drill(d, total_steps=max(args.steps, 8),
                                seed=args.seed, tol=args.tol,
                                assert_equal=False)
            else:
                res = elastic_drill(d, total_steps=args.steps,
                                    seed=args.seed, tol=args.tol,
                                    assert_equal=False)
        except Exception as e:  # a crash is a failed drill, not a traceback
            print(f"[drill] pass=False error={type(e).__name__}: {e}")
            return 1
    if args.sdc:
        print(f"[sdc-drill] pass={res['pass']} "
              f"max_rel_err={res['max_rel_err']:.3e} tol={res['tol']:g} "
              f"detections={[(d['site'], d['ok'], d['delay']) for d in res['detections']]} "
              f"quarantine_ok={res['quarantine']['ok']} "
              f"walkback_ok={res['walkback']['ok']}")
    else:
        print(f"[drill] pass={res['pass']} steps={res['steps']} "
              f"max_rel_err={res['max_rel_err']:.3e} tol={res['tol']:g} "
              f"phases={[p['phase'] for p in res['phases']]} "
              f"saves={res['reliability_events'].get('Reliability/elastic/saves', 0)} "
              f"resumes={res['reliability_events'].get('Reliability/elastic/resumes', 0)}")
    if args.json:
        print(json.dumps(res, indent=2, default=str))
    return 0 if res["pass"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
