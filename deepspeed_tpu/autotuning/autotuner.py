"""Autotuner: memory-model pruning + trial runs over sharding/micro-batch
configurations.

Reference parity: ``deepspeed/autotuning/autotuner.py:42`` — profiles the
model (param/activation memory, ``autotuning_profile_model_info``), prunes the
ZeRO-stage search space with a memory model, then runs grid/random/model-based
tuners over (micro_batch, GAS, zero_stage) with each trial a real short run.
TPU-first: a "trial" is N ``train_batch`` steps of a freshly-initialized
engine on the CURRENT devices (jit caching makes repeat trials cheap), the
memory model counts HBM bytes per chip under each ZeRO stage's sharding specs,
and the search adds TPU-specific knobs (remat policy) to the space.
"""

from __future__ import annotations

import dataclasses
import itertools
import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ..tuning.registry import config_set, default_registry
# plain logger, never log_dist: that asks JAX for the process index, which
# starts the backend — and the subprocess-trial parent must stay off the chip
from ..utils.logging import logger
from .tuner import GridSearchTuner, ModelBasedTuner, RandomTuner

TUNERS = {"gridsearch": GridSearchTuner, "random": RandomTuner,
          "model_based": ModelBasedTuner}


@dataclasses.dataclass
class TrialResult:
    config: Dict[str, Any]
    samples_per_sec: float
    step_time_s: float
    error: Optional[str] = None


def estimate_memory_per_chip(num_params: int, zero_stage: int, n_chips: int,
                             micro_batch: int, seq_len: int, hidden: int,
                             num_layers: int, remat: bool = False,
                             optimizer_factor: int = 2,
                             compute_bytes: int = 2) -> int:
    """HBM bytes/chip under a ZeRO stage (reference memory model
    ``autotuning/utils.py`` + ZeRO stage arithmetic):

    - master params fp32 + optimizer states (Adam: 2 slots fp32)
    - compute-dtype param copy (bf16) at use time
    - gradients fp32
    - activations ≈ micro_batch × seq × hidden × layers × compute_bytes
      (× ~4 ops/layer without remat, ×1 with remat — scan keeps one block)
    """
    fp32 = 4
    opt = num_params * fp32 * optimizer_factor
    master = num_params * fp32
    grads = num_params * fp32
    if zero_stage >= 1:
        opt //= n_chips
    if zero_stage >= 2:
        grads //= n_chips
    live_params = num_params * compute_bytes
    if zero_stage >= 3:
        master //= n_chips
        live_params //= max(1, n_chips // 2)  # gathered layer-by-layer
    act_factor = 1 if remat else 4
    acts = micro_batch * seq_len * hidden * num_layers * compute_bytes * act_factor
    return int(master + opt + grads + live_params + acts)


# Search-space ladders come from the shared tunable catalog
# (tuning/registry.py) so the offline grid and the online tuner search the
# SAME space — hand-rolled tuples here are deprecated; register/adjust
# knobs in the catalog instead.
DEFAULT_MICRO_BATCHES = default_registry().choices("train.micro_batch")
DEFAULT_STAGES = default_registry().choices("train.zero_stage")


def describe_devices() -> Dict[str, Any]:
    """``{"n_chips", "hbm_bytes"}`` of THIS process's devices (touches JAX).
    ``hbm_bytes`` is what the runtime reports, else the published size from
    ``utils/peaks.py``, else None — never an assumed default."""
    import jax

    from ..utils.peaks import UnknownDevice, device_peaks

    dev = jax.devices()[0]
    stats = dev.memory_stats()
    if stats and "bytes_limit" in stats:
        hbm = int(stats["bytes_limit"])
    else:
        try:
            hbm = device_peaks(dev).hbm_bytes
        except UnknownDevice:
            hbm = None
    return {"n_chips": len(jax.devices()), "hbm_bytes": hbm}


def _describe_devices_in_child(timeout_s: float) -> Dict[str, Any]:
    r = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.autotuning.trial_worker",
         "--describe-devices"],
        capture_output=True, text=True, timeout=timeout_s)
    if r.returncode != 0:
        raise RuntimeError(
            f"device description child failed (rc={r.returncode}): "
            f"{r.stderr[-500:]}")
    return json.loads(r.stdout.strip().splitlines()[-1])


class Autotuner:
    """Find the fastest feasible (zero_stage, micro_batch, gas, remat) for a
    model + target global batch on the current devices."""

    def __init__(self, model_spec, base_config: Dict[str, Any], *,
                 model_info: Optional[Dict[str, int]] = None,
                 hbm_bytes_per_chip: Optional[int] = None,
                 trial_steps: int = 3,
                 tuner_type: str = "model_based",
                 micro_batches: Sequence[int] = DEFAULT_MICRO_BATCHES,
                 zero_stages: Sequence[int] = DEFAULT_STAGES,
                 remat_options: Sequence[bool] = (False,),
                 model_desc: Optional[Dict[str, Any]] = None,
                 trial_timeout_s: float = 900.0,
                 seq_len: Optional[int] = None):
        self.model_spec = model_spec
        self.base_config = dict(base_config)
        self.trial_steps = trial_steps
        self.tuner_type = tuner_type
        self.model_info = model_info or {}
        self.micro_batches = micro_batches
        self.zero_stages = zero_stages
        self.remat_options = remat_options
        # model_desc = {"family": ..., "config": {...}}: when given, each
        # trial runs in a SUBPROCESS (trial_worker) — fresh XLA client and
        # jit cache per trial, an OOM kills only that trial, and timings
        # are not skewed by cross-trial cache warmth (reference
        # autotuning/scheduler.py launches real jobs for the same reasons)
        self.model_desc = model_desc
        self.trial_timeout_s = trial_timeout_s
        self.seq_len = seq_len
        self.results: List[TrialResult] = []
        if model_spec is None and model_desc is None:
            raise ValueError("need model_spec (in-process trials) or "
                             "model_desc (subprocess trials)")
        # The chip belongs to one process at a time. With subprocess trials
        # THIS process must never touch JAX (each trial child needs the
        # chip), so the device facts come from a short-lived child that has
        # exited before the first trial starts.
        facts = (_describe_devices_in_child(trial_timeout_s)
                 if model_desc is not None else describe_devices())
        self.n_chips = int(facts["n_chips"])
        self.hbm = hbm_bytes_per_chip or facts["hbm_bytes"]

    # ------------------------------------------------------------------ #
    def build_space(self) -> List[Dict[str, Any]]:
        """Enumerate + memory-prune (reference prunes ZeRO stages whose
        estimated requirement exceeds available memory)."""
        gbs = int(self.base_config.get("train_batch_size", 8))
        info = self.model_info
        space = []
        for mb, stage, remat in itertools.product(self.micro_batches,
                                                  self.zero_stages,
                                                  self.remat_options):
            dp = self.n_chips  # trials run data-parallel over local chips
            if gbs % (mb * dp) != 0:
                continue
            if info.get("num_params"):
                est = estimate_memory_per_chip(
                    info["num_params"], stage, self.n_chips, mb,
                    info.get("seq_len", 2048), info.get("hidden_size", 4096),
                    info.get("num_layers", 32), remat=remat)
                if self.hbm is None:
                    raise ValueError(
                        "memory pruning needs the chip's HBM size, but the "
                        "device reports none and is not in utils/peaks.py; "
                        "pass hbm_bytes_per_chip")
                if est > self.hbm:
                    continue
            space.append({"zero_stage": stage, "micro_batch": mb,
                          "gas": gbs // (mb * dp), "remat": remat})
        return space

    def _trial_config(self, point: Dict[str, Any]) -> Dict[str, Any]:
        cfg = json.loads(json.dumps(self.base_config))  # deep copy
        # knob writes go through the catalog's declared dot-paths —
        # config_set walks/creates nested dict blocks the same way the
        # online tuner walks the live typed config tree
        reg = default_registry()
        config_set(cfg, reg.get("train.micro_batch").path,
                   point["micro_batch"])
        cfg["gradient_accumulation_steps"] = point["gas"]
        cfg.pop("train_batch_size", None)
        config_set(cfg, reg.get("train.zero_stage").path,
                   point["zero_stage"])
        config_set(cfg, reg.get("train.remat_policy").path,
                   "full" if point["remat"] else "none")
        cfg["steps_per_print"] = 0
        return cfg

    def run_trial_subprocess(self, point: Dict[str, Any]) -> TrialResult:
        """One trial in an isolated worker process (fresh jit cache; an OOM
        or wedge is contained by the process boundary + timeout)."""
        job = {"model": self.model_desc,
               "trial_config": self._trial_config(point),
               "trial_steps": self.trial_steps}
        if self.seq_len:
            job["seq_len"] = self.seq_len
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as f:
            json.dump(job, f)
            job_path = f.name
        try:
            r = subprocess.run(
                [sys.executable, "-m",
                 "deepspeed_tpu.autotuning.trial_worker", job_path],
                capture_output=True, text=True,
                timeout=self.trial_timeout_s)
            tail = r.stdout.strip().splitlines()[-1] if r.stdout.strip() \
                else "{}"
            d = json.loads(tail)
            res = TrialResult(point, float(d.get("samples_per_sec", 0.0)),
                              float(d.get("step_time_s", float("inf"))),
                              error=d.get("error") or (
                                  None if r.returncode == 0
                                  else f"rc={r.returncode} "
                                       f"{r.stderr[-300:]}"))
        except subprocess.TimeoutExpired:
            res = TrialResult(point, 0.0, float("inf"),
                              error=f"timeout after {self.trial_timeout_s}s")
        except Exception as e:
            res = TrialResult(point, 0.0, float("inf"), error=str(e)[-300:])
        finally:
            try:
                os.unlink(job_path)
            except OSError:
                pass
        self.results.append(res)
        logger.info(f"autotuning trial {point} [subprocess]: "
                    f"{res.samples_per_sec:.2f} samples/s"
                    + (f" ({res.error})" if res.error else ""))
        return res

    def run_trial(self, point: Dict[str, Any],
                  data_fn: Callable[[int], Any]) -> TrialResult:
        import jax

        import deepspeed_tpu as dst
        from ..comm.mesh import set_mesh

        cfg = self._trial_config(point)
        try:
            set_mesh(None)  # each trial builds its mesh fresh
            engine, *_ = dst.initialize(model=self.model_spec, config=cfg)
            batch = data_fn(engine.train_batch_size())
            engine.train_batch(batch)  # compile + warmup
            t0 = time.perf_counter()
            for _ in range(self.trial_steps):
                out = engine.train_batch(batch)
            jax.block_until_ready(out.loss)
            dt = (time.perf_counter() - t0) / self.trial_steps
            res = TrialResult(point, engine.train_batch_size() / dt, dt)
        except Exception as e:  # OOM / bad config — score 0, keep tuning
            logger.warning(f"autotuning trial {point} failed: {e}")
            res = TrialResult(point, 0.0, float("inf"), error=str(e))
        self.results.append(res)
        logger.info(f"autotuning trial {point}: "
                    f"{res.samples_per_sec:.2f} samples/s")
        return res

    def tune(self, data_fn: Optional[Callable[[int], Any]] = None,
             max_trials: Optional[int] = None) -> TrialResult:
        space = self.build_space()
        if not space:
            raise ValueError("autotuning space is empty after memory pruning")
        if self.model_desc is not None:
            trial = lambda p: self.run_trial_subprocess(p).samples_per_sec  # noqa: E731
        else:
            if data_fn is None:
                raise ValueError("in-process tuning needs a data_fn")
            trial = lambda p: self.run_trial(p, data_fn).samples_per_sec  # noqa: E731
        tuner = TUNERS[self.tuner_type](space, trial)
        best_cfg, best_metric = tuner.tune(max_trials)
        best = next(r for r in self.results
                    if r.config == best_cfg and r.samples_per_sec == best_metric)
        logger.info(f"autotuning best: {best.config} "
                    f"({best.samples_per_sec:.2f} samples/s over "
                    f"{len(self.results)} trials)")
        return best

    def best_ds_config(self) -> Dict[str, Any]:
        best = max(self.results, key=lambda r: r.samples_per_sec)
        return self._trial_config(best.config)
