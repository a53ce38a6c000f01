"""Training engine: config → sharded, jit-compiled train step.

Capability parity with the reference's ``DeepSpeedEngine``
(``runtime/engine.py:208``) and ``deepspeed.initialize``
(``deepspeed/__init__.py:80``) — redesigned TPU-first:

- the reference orchestrates forward/backward/step at Python runtime with
  hooks, bucketed allreduce streams and loss-scale bookkeeping; here the whole
  micro-step loop (GAS accumulation, loss scaling, overflow skip, grad
  clipping, optimizer update, LR schedule) is ONE jit-compiled function with
  donated buffers — XLA overlaps the ZeRO collectives it implies with compute;
- ZeRO stages are sharding specs from ``runtime/partitioning.py`` — no
  partitioning code in the hot path at all;
- ``forward()/backward()/step()`` are provided as API-parity shims over the
  compiled step (they stage micro-batches and execute at the GAS boundary).

The engine still owns the runtime-side concerns that XLA cannot: dataloading,
checkpoint save/load, monitoring, timers, elasticity.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm import comm as dist
from ..comm.mesh import BATCH_AXES, MeshManager, init_mesh
from ..ops.optimizers import Optimizer, get_optimizer
from ..utils.compile_cache import enable_compile_cache
from ..utils.logging import log_dist, logger
from ..utils.timer import (BACKWARD_GLOBAL_TIMER, BACKWARD_MICRO_TIMER,
                           FORWARD_GLOBAL_TIMER, FORWARD_MICRO_TIMER,
                           STEP_GLOBAL_TIMER, STEP_MICRO_TIMER,
                           TRAIN_BATCH_TIMER, SynchronizedWallClockTimer,
                           ThroughputTimer)
from .config import DeepSpeedTPUConfig, parse_config
from .lr_schedules import LRScheduler, Schedule, constant, get_schedule
from .partitioning import Partitioner, shapes_of
from .precision import (LossScaleState, PrecisionPolicy, grads_finite,
                        make_loss_scaler, scale_loss, unscale_grads,
                        update_loss_scale)


# --------------------------------------------------------------------------- #
# model description — what the engine needs from a user model
# --------------------------------------------------------------------------- #
@dataclass
class ModelSpec:
    """The JAX-native counterpart of passing an ``nn.Module`` to
    ``deepspeed.initialize``: a pure loss function over a param pytree, plus
    optional init / logical-sharding metadata."""

    loss_fn: Callable[..., Tuple[jnp.ndarray, Dict[str, Any]]]
    init_fn: Optional[Callable[[jax.Array], Any]] = None
    params: Optional[Any] = None
    logical_axes: Optional[Any] = None
    apply_fn: Optional[Callable[..., Any]] = None
    name: str = "model"
    # whether the model routes its stacked layers through pipeline_apply when
    # the mesh has a pipe axis — keeps the partitioner's 'layers'->'pipe' rule
    # in sync with the model's actual execution path
    pipeline_capable: bool = True
    # optional 1F1B train-step grads: (params, batch, loss_scale) ->
    # (grads_of_scaled_loss, unscaled_loss, aux). Used instead of jax.grad
    # when the mesh has pipe >= 2 (runtime/pipe/one_f_one_b.py)
    pipeline_grad_fn: Optional[Callable[..., Any]] = None
    # optional fused unembed+CE loss: (params, batch, *, shards) ->
    # (loss, aux) that never materializes the [B, S, V] logits tensor.
    # Routed instead of loss_fn when config.sequence.tiled_loss is on
    # (sequence/tiled.py tiled_fused_logits_loss).
    tiled_loss_fn: Optional[Callable[..., Any]] = None
    # optional: (param shapes, ONE device's micro-batch shapes) -> the
    # layer block the model rematerializes
    # (activation_checkpointing.RematProbe), or None where the model leaves
    # the engine nothing to choose. With it, ``remat: true`` and no policy
    # named means the richest rung of the ladder that fits the device.
    remat_probe: Optional[Callable[..., Any]] = None

    def materialize(self, rng: jax.Array):
        if self.params is not None:
            return self.params
        if self.init_fn is None:
            raise ValueError("ModelSpec needs params or init_fn")
        return self.init_fn(rng)


class TrainState(NamedTuple):
    """The full jit-carried state (a pytree)."""

    step: jnp.ndarray
    params: Any            # fp32 master params
    opt_state: Any
    loss_scale: LossScaleState
    skipped_steps: jnp.ndarray
    # LoCo error-feedback residuals (comms_overlap.loco + qgZ): one fp32
    # array of global shape [dp_world, *leaf.shape] per quantized-reduce
    # leaf, sharded over the batch axes so each device carries ITS OWN
    # quantization error. Empty tuple (no pytree leaves) when disabled, so
    # the default step's compiled program is unchanged.
    loco_residual: Any = ()


class StepOutput(NamedTuple):
    loss: jnp.ndarray
    grad_norm: jnp.ndarray
    lr: jnp.ndarray
    loss_scale: jnp.ndarray
    overflow: jnp.ndarray
    aux: Dict[str, Any]


from .utils import global_norm as _global_norm  # shared with runtime.utils


def _agreed_max(values) -> List[int]:
    """Whole numbers as every process of the job agrees on them: the largest
    each has (what hosts of ONE program must decide alike - the program they
    lower - they decide from these). The values themselves where the job is
    one process."""
    if jax.process_count() == 1:
        return [int(v) for v in values]
    from jax.experimental import multihost_utils

    return np.max(multihost_utils.process_allgather(
        np.asarray(values, np.int64)), axis=0).tolist()


class DeepSpeedTPUEngine:
    """See module docstring. Construct via :func:`initialize`."""

    def __init__(self, model: ModelSpec, config: DeepSpeedTPUConfig,
                 mesh_mgr: MeshManager, optimizer: Optional[Optimizer] = None,
                 lr_schedule: Optional[Schedule] = None,
                 training_data: Optional[Iterable] = None,
                 rng: Optional[jax.Array] = None):
        self.model = model
        self.config = config
        self.mesh_mgr = mesh_mgr
        log_dist(f"persistent compilation cache: {enable_compile_cache()}")
        self.global_steps = 0
        self.skipped_steps = 0
        self.micro_steps = 0
        # host-side token counter (universal checkpoint v2 carries it so an
        # elastic resume keeps the token budget accounting exact)
        self.global_tokens = 0
        self._staged_batches: List[Any] = []
        self._staged_loss: Optional[jnp.ndarray] = None
        self.training_dataloader = None

        # --- precision ---
        self.precision = PrecisionPolicy.from_config(config)

        # --- optimizer (reference _configure_optimizer :1597) ---
        # one construction site: a config with param_groups defers building
        # until params materialize (leaf names drive the group match); a
        # user-supplied optimizer always wins, but dropping the config's
        # param_groups silently would be a trap — warn.
        config_groups = config.optimizer.param_groups
        if optimizer is not None and config_groups:
            logger.warning(
                "optimizer.param_groups in the config are IGNORED because an "
                "optimizer object was passed to initialize()")
        build_grouped = optimizer is None and bool(config_groups)
        if optimizer is None and not build_grouped:
            optimizer = get_optimizer(config.optimizer.type or "adamw",
                                      **config.optimizer.params)

        # --- params + sharding ---
        rng = rng if rng is not None else jax.random.PRNGKey(config.seed)
        params = model.materialize(rng)
        params = jax.tree.map(
            lambda p: p.astype(self.precision.param_dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params)

        if build_grouped:
            # param-group analog (reference torch param_groups): per-group
            # hyper overrides by leaf-path pattern — needs the materialized
            # tree for leaf names, hence after materialize
            from ..ops.optimizers import grouped_optimizer

            optimizer = grouped_optimizer(
                config.optimizer.type or "adamw", params,
                config_groups, **config.optimizer.params)
            # abstract leaves only — the wrapper needs paths/structure, and
            # holding real arrays here would pin the initial params forever
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
            self._grouped_ctor = (config.optimizer.type or "adamw",
                                  [dict(g) for g in config_groups],
                                  dict(config.optimizer.params), abstract)
        self.optimizer = optimizer
        self.base_lr = float(optimizer.hyperparams.get("lr", 1.0)) or 1.0
        if lr_schedule is None:
            lr_schedule = get_schedule(config.scheduler.type,
                                       config.scheduler.params,
                                       base_lr=self.base_lr)
        self.lr_schedule = lr_schedule
        self.lr_scheduler = LRScheduler(lr_schedule)
        # set_lr pin, fed to the compiled step as a TRACED scalar (< 0 =
        # follow the schedule) so changing the LR never triggers a recompile
        self._lr_override = jnp.asarray(-1.0, jnp.float32)

        if config.zero_config.zero_quantized_gradients and \
                config.zero_config.stage not in (2,):
            raise ValueError(
                "zero_quantized_gradients (qgZ) requires ZeRO stage 2 — the "
                "quantized reduce-scatter produces grads in the stage-2 "
                "sharded layout (stage 3 param gathering is a separate path)")
        if config.zero_config.zero_quantized_weights and \
                config.zero_config.stage >= 3 and \
                int(config.zero_config.zero_hpz_partition_size) <= 1 and \
                not (config.comms_overlap.enabled
                     and config.comms_overlap.layer_prefetch):
            logger.warning(
                "zero_quantized_weights at ZeRO-3 has no quantization "
                "boundary without comms_overlap.layer_prefetch (per-layer "
                "quantized gathers) or zero_hpz_partition_size > 1 "
                "(quantized primary gather) — params gather at use in full "
                "precision")

        # --- comms_overlap: gradient-comm overlap engine (comm/overlap.py) ---
        co = config.comms_overlap
        self.comms_overlap_flags: List[str] = []
        self._overlap_plan_cache = None
        if co.enabled:
            if config.zero_config.stage >= 3 and not co.layer_prefetch:
                raise ValueError(
                    "comms_overlap requires ZeRO stage <= 2: stage 3's "
                    "gather-on-use parameter sharding conflicts with the "
                    "manual data-parallel reduction region (set "
                    "comms_overlap.layer_prefetch for the ZeRO-3 per-layer "
                    "all-gather prefetch instead)")
            if config.zero_config.stage >= 3:
                log_dist("comms_overlap: ZeRO-3 — gradient-reduction overlap "
                         "engine disabled (params gather on use); per-layer "
                         "all-gather prefetch + XLA flags active")
            if mesh_mgr.pp_world_size > 1:
                log_dist("comms_overlap: pipeline axis active — the overlap "
                         "engine is disabled (1F1B owns its own reduction); "
                         "XLA flags still apply")
            if co.loco and not (config.zero_config.zero_quantized_gradients
                                or co.quantized_all_reduce):
                logger.warning(
                    "comms_overlap.loco has no effect without "
                    "zero_quantized_gradients (qgZ) or quantized_all_reduce "
                    "— there is no quantizer to error-compensate")
            from ..comm.overlap import apply_xla_overlap_flags

            self.comms_overlap_flags = apply_xla_overlap_flags(co)

        from ..comm.mesh import ZERO_AXES as _ZERO_AXES

        zero_axes = _ZERO_AXES
        secondary_axes = None
        if mesh_mgr.mics_shard_size > 1:
            hpz = int(config.zero_config.zero_hpz_partition_size) > 1 and \
                int(config.zero_config.mics_shard_size) <= 1
            if hpz and config.zero_config.stage >= 3:
                # ZeRO++ hpZ: PRIMARY partition (masters / opt state / grad
                # reduce-scatter) over the full ZeRO axes — no memory is
                # given back — plus a SECONDARY parameter partition inside
                # the 'zero_shard' (ICI island) sub-axis, so every fwd/bwd
                # all-gather resolves intra-island and only the once-per-
                # step primary gather crosses 'data' (the DCN tier).
                secondary_axes = tuple(a for a in _ZERO_AXES if a != "data")
                log_dist(
                    "ZeRO++ hpZ: secondary param partition over "
                    f"{secondary_axes} (size {mesh_mgr.mics_shard_size}); "
                    "primary partition keeps the full ZeRO axes")
            else:
                # MiCS: shard within the 'zero_shard' group, replicate
                # across 'data' groups (reference runtime/zero/mics.py:63).
                # hpZ below stage 3 also lands here: without gather-on-use
                # params there is no secondary gather to keep intra-island.
                zero_axes = tuple(a for a in _ZERO_AXES if a != "data")
                if hpz:
                    log_dist("zero_hpz_partition_size below ZeRO stage 3: "
                             "falling back to MiCS semantics (shard within "
                             "the group, replicate across 'data')")
        self.partitioner = Partitioner(
            mesh_mgr, zero_stage=config.zero_config.stage,
            zero_axes=zero_axes, secondary_axes=secondary_axes,
            tensor_parallel=mesh_mgr.tp_world_size > 1,
            pipeline_layers=model.pipeline_capable)
        shapes = shapes_of(params)
        if model.logical_axes is not None:
            axes = model.logical_axes
        elif mesh_mgr.tp_world_size > 1:
            # un-annotated model on a TP mesh: infer row/col-parallel rules
            # from param names (AutoTP — module_inject/auto_tp.py:194 analog)
            from ..module_inject import infer_logical_axes

            axes = infer_logical_axes(params)
            log_dist("AutoTP: inferred tensor-parallel sharding rules from "
                     "param names (no logical_axes on the ModelSpec)")
        else:
            # no metadata, no TP: replicate params (ZeRO still shards
            # masters/opt state over the largest divisible dim of each leaf)
            axes = jax.tree.map(lambda s: tuple([None] * len(s)), shapes,
                                is_leaf=lambda x: isinstance(x, tuple))
        # compute-time specs (TP always; +ZeRO at stage 3 — gather-on-use)
        param_specs = self.partitioner.param_specs(axes, shapes)
        # gradient specs: reduce-scattered from stage 2 (reference
        # stage_1_and_2.py:126 grad partitioning)
        grad_specs = self.partitioner.grad_specs(axes, shapes)
        # fp32 master + optimizer-state specs: sharded from stage 1
        # (reference bf16_optimizer.py:36 sharded fp32 masters)
        opt_specs = self.partitioner.opt_state_specs(axes, shapes)
        self.param_specs = param_specs
        self.grad_specs = grad_specs
        self.opt_param_specs = opt_specs
        # gathered (TP-only) layout — the target of the ZeRO all-gather:
        # feeds the layer-prefetch shardings AND the qwZ per-layer quantize
        # descriptors (_layer_prefetch_quant)
        self._qw_gather_specs = self.partitioner.gathered_param_specs(
            axes, shapes)
        self._param_shardings = self.partitioner.shardings(param_specs)
        self._grad_shardings = self.partitioner.shardings(grad_specs)
        self._master_shardings = self.partitioner.shardings(opt_specs)
        self._log_zero_sharding_summary(shapes, opt_specs)

        # --- ZeRO-Infinity: NVMe-streamed optimizer tier (reference
        # stage3.py:2412 sub-group swap cycle; offload_config device=nvme,
        # also reachable via memory.tiering.optimizer_tier=nvme) ---
        mt = config.memory.tiering
        self._nvme_opt = None
        if config.zero_config.offload_optimizer.device == "nvme" or \
                (mt.enabled and mt.optimizer_tier == "nvme"):
            self._configure_nvme_optimizer(params)
        # --- tiered memory: host-resident optimizer state with prefetch
        # overlapped under fwd/bwd (memory.tiering; docs/memory.md) ---
        self._tiered_opt = bool(mt.enabled and mt.optimizer_tier == "host")
        self._tiered_grad_step = None
        if self._tiered_opt:
            if self._nvme_opt is not None:
                raise ValueError("memory.tiering.optimizer_tier=host and an "
                                 "nvme optimizer tier are mutually exclusive")
            if jax.process_count() > 1:
                raise ValueError(
                    "memory.tiering.optimizer_tier=host is single-host for "
                    "now: the host tier materializes full numpy leaves, "
                    "which fails on non-addressable multi-host arrays")

        with mesh_mgr.activate():
            if self._nvme_opt is not None:
                # fp32 masters + moments live on NVMe; the device holds ONLY
                # the bf16/compute copy (stage layout — ZeRO-sharded at 3)
                params = jax.jit(
                    self.precision.cast_to_compute,
                    out_shardings=self._param_shardings)(params)
                opt_state = ()
                self.opt_state_specs = ()
            else:
                # masters live ZeRO-sharded from stage 1 up; the bf16 compute
                # copy is gathered per step in _loss (cast + constraint)
                params = jax.jit(
                    lambda p: p, out_shardings=self._master_shardings)(params)
                opt_state = self._init_opt_state(params)
            # scalars go through a jitted identity with explicit replicated
            # out_shardings: freshly-built uncommitted scalars would otherwise
            # differ from the step outputs' committed NamedSharding avals and
            # the SECOND train_batch would re-lower + re-COMPILE the whole
            # step (15 s at two layers on the chip). Measured: 2 step_fn
            # XLA compilations without this, 1 with it.
            loss_scale = make_loss_scaler(config.fp16)
            repl = NamedSharding(mesh_mgr.mesh, P())
            step0, loss_scale, skipped0 = jax.jit(
                lambda s: s,
                out_shardings=jax.tree.map(lambda _: repl,
                                           (0, loss_scale, 0)))(
                (jnp.zeros((), jnp.int32), loss_scale,
                 jnp.zeros((), jnp.int32)))
            self.state = TrainState(
                step=step0,
                params=params,
                opt_state=opt_state,
                loss_scale=loss_scale,
                skipped_steps=skipped0,
            )

        # --- tiered store (deepspeed_tpu/memory): owns the transfer worker,
        # tier byte accounting and the Memory/tier/* telemetry. Cheap when
        # tiering is off (no thread until a tier is used); offload_states()
        # routes through it either way. ---
        from ..memory import TieredStore

        self.tiered_store = TieredStore(mt)
        if self._tiered_opt:
            # the optimizer state leaves the device between steps from the
            # very first train_batch (restored under the step's grad phase)
            self.state = self.state._replace(
                opt_state=self.tiered_store.offload(
                    self.state.opt_state, "host", name="optim_states"))
            log_dist("memory.tiering: optimizer state host-resident "
                     f"(pin_memory={mt.pin_memory}); H2D prefetch overlaps "
                     "fwd/bwd, D2H writeback overlaps the next step")

        if self._overlap_active():
            self._overlap_setup()  # static routing, cached for engine life
            if co.loco and (config.zero_config.zero_quantized_gradients
                            or co.quantized_all_reduce):
                self._init_loco_residuals()

        # --- comms_overlap.layer_prefetch: ZeRO-3 per-layer all-gather
        # prefetch (T3). Published process-wide (latest engine wins, like
        # activation_checkpointing.configure) so the model families' stacked
        # -layer scans pick it up at the next train-step trace. ---
        from ..comm.overlap import configure_layer_prefetch

        self._layer_prefetch_on = bool(
            co.enabled and co.layer_prefetch
            and config.zero_config.stage >= 3
            and mesh_mgr.pp_world_size <= 1)
        if co.enabled and co.layer_prefetch and not self._layer_prefetch_on:
            log_dist("comms_overlap.layer_prefetch has no effect here: it "
                     "needs ZeRO stage 3 (gather-on-use params) and no "
                     "pipeline axis — plain scan retained")
        # the per-layer gathers resolve over the axes the compute-param
        # layout is sharded on: the hpZ secondary (ICI) axes when set, the
        # full ZeRO axes otherwise — feeds the prefetch telemetry link class
        _gaxes = tuple(
            a for a in (self.partitioner.secondary_axes
                        if self.partitioner.secondary_axes is not None
                        else self.partitioner.zero_axes)
            if mesh_mgr.axis_size(a) > 1)
        # memory.tiering.param_tier=host composes here: the stacked layer
        # shards park in host memory and each layer's host→HBM copy-in is
        # issued by the SAME prefetch pipeline as the all-gather (identity
        # on single-memory backends — docs/memory.md compose rules)
        _param_host = bool(mt.enabled and mt.param_tier == "host"
                           and self._layer_prefetch_on)
        if mt.enabled and mt.param_tier == "host" and not _param_host:
            log_dist("memory.tiering.param_tier=host has no effect here: it "
                     "rides the comms_overlap.layer_prefetch pipeline "
                     "(ZeRO stage 3 + layer_prefetch required)")
        configure_layer_prefetch(
            self._layer_prefetch_on,
            depth=max(1, int(co.prefetch_depth)),
            shardings=(self._layer_prefetch_shardings()
                       if self._layer_prefetch_on else None),
            quantize=(self._layer_prefetch_quant()
                      if self._layer_prefetch_on else None),
            gather_axes=_gaxes if self._layer_prefetch_on else (),
            host_tier=_param_host)
        if self._layer_prefetch_on:
            log_dist(f"comms_overlap: per-layer all-gather prefetch armed "
                     f"(depth={max(1, int(co.prefetch_depth))}"
                     + (", qwZ int8 gathers"
                        if config.zero_config.zero_quantized_weights
                        else "") + ")")
        # ZeRO-3 gather-at-use: pin each PLAIN-scan layer slice to the
        # gathered compute layout. Without the pin, GSPMD may repartition
        # the stacked-layer scan when it fuses the backward in — which has
        # produced a numerically wrong forward for pure-DP ZeRO-3 (the
        # forward-only program is correct; the grads-live one is not). The
        # constraint states what stage 3 means anyway — all-gather the
        # layer at use — so TP/SP layouts are preserved and the prefetch
        # path (which already pins the same layout) is unchanged.
        from ..comm.overlap import configure_scan_slice_layout

        _scan_slice_on = bool(
            config.zero_config.stage >= 3 and mesh_mgr.pp_world_size <= 1
            and any(mesh_mgr.axis_size(a) > 1
                    for a in self.partitioner.zero_axes))
        configure_scan_slice_layout(
            self._layer_prefetch_shardings() if _scan_slice_on else None)

        # --- compiled steps ---
        self._train_step = None
        self._grad_step = None
        self._apply_step = None
        # breakdown-mode phase steps (wall_clock_breakdown: true)
        self._fwd_step = None
        self._bwd_step = None
        self._flops_estimated = False

        # --- dataloader ---
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        # make the config's remat policy the process-wide default for
        # activation_checkpointing.checkpoint() (reference engine wires
        # checkpointing.configure at init, runtime/engine.py:395-408 region)
        from .activation_checkpointing import checkpointing as _ac

        # where the config names no policy and the model shows its block
        # (ModelSpec.remat_probe), every batch signature's first lowering of
        # the step chooses a rung from the device's memory: _remat_for
        self._remat_choices = {}      # batch signature -> RematChoice
        self._remat_choice = None     # the latest one published
        self._remat_unreported = None
        named = config.activation_checkpointing.policy != "none" or \
            config.activation_checkpointing.cpu_checkpointing
        if named:
            _ac.configure(deepspeed_config=config)
        else:
            # the latest engine wins: what an earlier one was named, or
            # chose for ITS memory, is not ours
            _ac.reset()
        self._remat_chooses = not named and model.remat_probe is not None

        # --- attention.gqa_native: publish the native-GQA kernel gate
        # process-wide (latest engine wins, same contract as the remat
        # registry above; docs/performance.md "Native GQA attention").
        # Default OFF → every attention program stays byte-identical to
        # the K/V-widening path.
        from ..ops.attention import configure_gqa_native

        configure_gqa_native(bool(config.attention.gqa_native))
        if config.attention.gqa_native:
            log_dist("attention.gqa_native: narrow-KV flash kernels armed "
                     "(KV HBM traffic scales with kv_heads, not num_heads)")

        # --- sequence.ring: publish the ring-attention schedule knobs
        # process-wide (same latest-engine-wins contract as the gate above;
        # sequence/ring.py). Defaults (contiguous, no overlap) leave every
        # ring program unchanged.
        from ..sequence.ring import configure_ring

        configure_ring(layout=config.sequence.ring.layout,
                       overlap=bool(config.sequence.ring.overlap))
        if config.sequence.ring.layout != "contiguous" or \
                config.sequence.ring.overlap:
            log_dist(
                f"sequence.ring: layout={config.sequence.ring.layout} "
                f"overlap={config.sequence.ring.overlap}")
        if config.sequence.tiled_loss:
            if getattr(model, "tiled_loss_fn", None) is None:
                log_dist("sequence.tiled_loss: ON but model spec has no "
                         "tiled_loss_fn — falling back to dense loss_fn")
            else:
                log_dist("sequence.tiled_loss: fused unembed+CE armed "
                         f"(shards={config.sequence.tiled_loss_shards}; "
                         "[B, S, V] logits never materialized)")

        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=config.steps_per_print)

        # --- monitoring + flops profiler (reference MonitorMaster :293,
        # flops_profiler engine hooks :2278,:2850) ---
        from ..monitor import MonitorMaster

        self.monitor = MonitorMaster(config)
        from ..profiling import FlopsProfiler

        self.flops_profiler = FlopsProfiler(config.flops_profiler, engine=self)

        # --- telemetry hub: step breakdown + comms logger + HBM memory +
        # trace sessions, fanned out through the monitor (telemetry/hub.py) ---
        from ..telemetry import TelemetryHub

        self.timers = SynchronizedWallClockTimer()
        self.telemetry = TelemetryHub(config, monitor=self.monitor,
                                      timers=self.timers,
                                      tput_timer=self.tput_timer)

        # Train/overlap/* gauges (registered in telemetry/schema.py): the
        # prefetch configuration + per-step gathered bytes, so the comm-
        # efficiency report can attribute hidden comm to the prefetch
        if self._layer_prefetch_on:
            depth = max(1, int(co.prefetch_depth))
            self.telemetry.train_event("overlap/prefetch_depth", depth)
            lp = self.state.params.get("layers") \
                if isinstance(self.state.params, dict) else None
            if lp is not None:
                leaves = jax.tree.leaves(lp)
                if leaves:
                    itemsize = jnp.dtype(self.precision.compute_dtype).itemsize
                    self.telemetry.train_event(
                        "overlap/prefetch_layers", float(leaves[0].shape[0]))
                    self.telemetry.train_event(
                        "overlap/prefetch_bytes",
                        float(sum(l.size for l in leaves) * itemsize))

        # --- online self-tuning (tuning/tuner.py; docs/tuning.md): the
        # telemetry-scored knob search stepping at the optimizer-step seam.
        # Opt-in: with the block disabled (the default) no tuner exists and
        # the train step program is byte-identical to pre-tuning behavior
        # (pinned by tests/test_tuning.py) ---
        self.tuning = None
        if getattr(config, "tuning", None) is not None and \
                config.tuning.enabled:
            from ..tuning import OnlineTuner

            self.tuning = OnlineTuner.for_engine(self, config.tuning)

        # --- training watchdog (runtime/watchdog.py): consecutive-skip /
        # non-finite-loss / stall detection on host-visible step outputs.
        # Opt-in: its observe() forces a host sync on the loss, so the
        # default step must never pay for it ---
        self.watchdog = None
        if config.watchdog.enabled:
            from .watchdog import TrainingWatchdog

            self.watchdog = TrainingWatchdog(config.watchdog,
                                             telemetry=self.telemetry)

        # --- numerics integrity plane (reliability/integrity.py): SDC
        # detection via cross-replica digest votes + shadow recompute
        # audits. Opt-in: with the block disabled the step program carries
        # no digest computation — byte-identical to the pre-integrity
        # program (pinned by tests/test_integrity.py) ---
        self.integrity = None
        if config.reliability.integrity.enabled:
            from ..reliability.integrity import IntegrityPlane

            self.integrity = IntegrityPlane(config,
                                            telemetry=self.telemetry)

        # --- curriculum learning (reference engine hooks :395-408 wire the
        # curriculum scheduler into the forward prologue) ---
        self.curriculum_scheduler = None
        de = config.data_efficiency or {}
        cl = de.get("data_sampling", {}).get("curriculum_learning") or \
            de.get("curriculum_learning", {})
        if cl.get("enabled"):
            from .data_pipeline import CurriculumScheduler

            self.curriculum_scheduler = CurriculumScheduler(cl)
        log_dist(
            f"engine ready: zero_stage={config.zero_config.stage} "
            f"dtype={config.compute_dtype} mesh={dict(mesh_mgr.mesh.shape)} "
            f"micro_batch={self.train_micro_batch_size_per_gpu()} "
            f"gas={self.gradient_accumulation_steps()}")

    def _configure_nvme_optimizer(self, params) -> None:
        """ZeRO-Infinity optimizer tier: fp32 masters + Adam moments live on
        NVMe and STREAM through the step per sub-group (reference
        ``stage3.py:2412`` swap_in → update → swap_out; ``:679``
        ``_configure_tensor_swapping``). The training flow becomes: device
        jit computes grads → host clip/overflow check → streamed host Adam →
        updated bf16 copies return to the device. save/load_checkpoint
        stream-copy the NVMe state files alongside the TrainState
        (``saver.py`` → ``save_state_files``/``load_state_files``)."""
        import tempfile

        from .swap_tensor.streaming_optimizer import NVMeStreamingOptimizer

        cfg = self.config
        if jax.process_count() > 1:
            raise ValueError(
                "offload_optimizer device=nvme is single-host for now: the "
                "streamed tier gathers grads to host numpy (fails on "
                "non-addressable multi-host arrays) and writes state files "
                "on process 0 only — per-host sharded streaming is not "
                "implemented")
        if cfg.fp16.enabled:
            raise ValueError(
                "offload_optimizer device=nvme supports bf16/fp32 training "
                "(dynamic fp16 loss scaling is not wired through the host "
                "optimizer tier)")
        opt_type = (cfg.optimizer.type or "adamw").lower()
        if opt_type not in ("adam", "adamw"):
            raise ValueError(
                f"offload_optimizer device=nvme streams Adam state; got "
                f"optimizer type '{opt_type}'")
        hp = dict(cfg.optimizer.params)
        swap_dir = cfg.zero_config.offload_optimizer.nvme_path or \
            cfg.memory.tiering.nvme_path or \
            os.path.join(tempfile.gettempdir(), "dstpu_nvme_opt")
        leaves, self._nvme_treedef = jax.tree_util.tree_flatten(params)
        # leaves pass through unconverted — the optimizer converts to fp32
        # per sub-group inside its init loop, keeping bring-up bounded too
        self._nvme_opt = NVMeStreamingOptimizer(
            leaves,
            os.path.join(swap_dir, "opt_state"),
            lr=float(hp.get("lr", 1e-3)),
            betas=tuple(hp.get("betas", (0.9, 0.999))),
            eps=float(hp.get("eps", 1e-8)),
            weight_decay=float(hp.get("weight_decay", 0.0)),
            adamw_mode=(opt_type == "adamw"),
            sub_group_size=int(cfg.zero_config.sub_group_size))

    def _train_batch_nvme(self, batch) -> StepOutput:
        """train_batch when the optimizer state streams through NVMe."""
        import ml_dtypes

        cfg = self.config
        if not hasattr(self, "_nvme_grad_step"):
            def grad_fn(params, b, ls):
                return self._accumulate(params, b, ls)

            self._nvme_grad_step = self._jit("nvme_grad_step", grad_fn)
        self.tput_timer.start()
        self.telemetry.step_begin(self.global_steps + 1)
        if self.watchdog is not None:
            self.watchdog.step_started()
        breakdown = self.wall_clock_breakdown()
        if self.curriculum_scheduler is not None:
            batch = self.curriculum_scheduler.truncate(batch,
                                                       self.global_steps)
        batch = self._shard_batch(batch, with_gas_dim=True)
        if breakdown:
            self.timers(BACKWARD_GLOBAL_TIMER).start(sync=True)
        with self.telemetry.tracer.span("train/bwd", cat="train",
                                        step=self.global_steps + 1):
            grads, loss, aux = self._nvme_grad_step(self.state.params, batch,
                                                    self.state.loss_scale)
        if breakdown:
            self.timers(BACKWARD_GLOBAL_TIMER).stop(sync=True)
            self.timers(STEP_GLOBAL_TIMER).start()
        g_dev = jax.tree.leaves(grads)
        for g in g_dev:  # start ALL D2H copies before the first blocking
            if hasattr(g, "copy_to_host_async"):  # np.asarray (overlapped
                g.copy_to_host_async()  # transfers, not one full-tree sync)
        g_leaves = [np.asarray(g, np.float32) for g in g_dev]
        sq = sum(float(np.vdot(g, g)) for g in g_leaves)
        grad_norm = float(np.sqrt(sq))
        finite = np.isfinite(grad_norm)
        # schedule driven by state.step (like the compiled path) so a
        # skipped non-finite step does not advance the LR
        lr_t = float(self.lr_schedule(jnp.asarray(int(self.state.step),
                                                  jnp.float32)))
        if float(self._lr_override) >= 0:
            lr_t = float(self._lr_override)
        if finite:
            if cfg.gradient_clipping and cfg.gradient_clipping > 0:
                coef = min(1.0, float(cfg.gradient_clipping) /
                           (grad_norm + 1e-6))
                if coef < 1.0:
                    g_leaves = [g * np.float32(coef) for g in g_leaves]
            bf16 = self.precision.compute_dtype == jnp.bfloat16
            flat_shardings = jax.tree.leaves(
                self._param_shardings,
                is_leaf=lambda x: isinstance(x, NamedSharding))
            new_leaves: list = [None] * len(g_leaves)

            def h2d_group(leaf_ids, outs):
                # fires per finished sub-group INSIDE the streamed step:
                # device_put dispatch is async, so these H2D transfers run
                # while the later sub-groups are still reading/updating
                # (reference pipelined_optimizer_swapper.py:52 overlap)
                for lid, u in zip(leaf_ids, outs):
                    if bf16:
                        u = u.view(ml_dtypes.bfloat16)
                    new_leaves[lid] = jax.device_put(u, flat_shardings[lid])

            self._nvme_opt.step(
                g_leaves, lr=lr_t,
                out_dtype="bfloat16" if bf16 else "float32",
                on_group=h2d_group)
            new_params = jax.tree_util.tree_unflatten(self._nvme_treedef,
                                                      new_leaves)
            self.state = self.state._replace(
                params=new_params,
                step=self.state.step + 1)
        else:
            self.skipped_steps += 1
            self.state = self.state._replace(
                skipped_steps=self.state.skipped_steps + 1)
        out = StepOutput(loss=loss, grad_norm=jnp.float32(grad_norm),
                         lr=jnp.float32(lr_t),
                         loss_scale=jnp.float32(1.0),
                         overflow=jnp.asarray(not finite),
                         aux=aux)
        self.global_steps += 1
        self._last_grad_norm = grad_norm
        self.lr_scheduler.last_step = self.global_steps
        if breakdown:
            self.timers(STEP_GLOBAL_TIMER).stop(sync=True)
        self.tput_timer.stop()
        self._write_monitor_events(out)
        self.telemetry.step_end(self.global_steps,
                                step_time_s=self.tput_timer.avg_step_time()
                                or None)
        if cfg.steps_per_print and \
                self.global_steps % cfg.steps_per_print == 0:
            log_dist(f"step={self.global_steps} loss={float(out.loss):.4f} "
                     f"lr={lr_t:.3e} gnorm={grad_norm:.3f} [nvme-opt]")
        if self.watchdog is not None:
            self.watchdog.observe(self, out)
        return out

    def _train_batch_tiered(self, batch) -> StepOutput:
        """train_batch when the optimizer state lives on the HOST tier
        (``memory.tiering.optimizer_tier=host``; docs/memory.md).

        Between steps the opt-state leaves are host-resident (off the device
        allocator). Per step: (1) the H2D restore is enqueued on the
        transfer worker FIRST, (2) the grad computation dispatches — the
        copies stream under it, (3) the jitted apply consumes the restored
        state, (4) the updated state's D2H writeback is enqueued and
        overlaps the NEXT step's compute. The store's compute window
        brackets (2)-(3) so ``Memory/tier/overlap_frac`` measures how much
        of the transfer time was actually hidden."""
        store = self.tiered_store
        if self._tiered_grad_step is None:
            def grad_fn(params, b, ls):
                return self._accumulate(params, b, ls)

            self._tiered_grad_step = self._jit("tiered_grad_step", grad_fn)
            self._ensure_apply_step()
        self.tput_timer.start()
        self.telemetry.step_begin(self.global_steps + 1)
        if self.watchdog is not None:
            self.watchdog.step_started()
        if self.curriculum_scheduler is not None:
            batch = self.curriculum_scheduler.truncate(batch,
                                                       self.global_steps)
        batch = self._shard_batch(batch, with_gas_dim=True)
        with self.telemetry.tracer.span("train/train_batch", cat="train",
                                        step=self.global_steps + 1):
            store.worker.compute_begin()
            try:
                # (1) H2D prefetch of the host-resident optimizer state —
                # HostBuffer leaves carry their exact shardings, so no
                # override tree is needed
                handle = store.prefetch(self.state.opt_state)
                # (2) grads dispatch; the prefetch copies run under them
                grads, loss, aux = self._tiered_grad_step(
                    self.state.params, batch, self.state.loss_scale)
                opt_dev = handle.wait()
                # (3) optimizer apply over the restored state
                new_state, out = self._apply_step(
                    self.state._replace(opt_state=opt_dev), grads, loss,
                    self._lr_override)
                jax.block_until_ready(out.loss)
            finally:
                store.worker.compute_end()
        # (4) async D2H writeback — overlaps the next step's compute
        self.state = new_state._replace(
            opt_state=store.offload(new_state.opt_state, "host",
                                    name="optim_states"))
        self.global_steps += 1
        self._last_grad_norm = out.grad_norm
        self.lr_scheduler.last_step = self.global_steps
        self.tput_timer.stop()
        self._write_monitor_events(out)
        self.telemetry.memory_tier_events(store, self.global_steps)
        self.telemetry.step_end(self.global_steps,
                                step_time_s=self.tput_timer.avg_step_time()
                                or None)
        if self.config.steps_per_print and \
                self.global_steps % self.config.steps_per_print == 0:
            log_dist(f"step={self.global_steps} loss={float(out.loss):.4f} "
                     f"lr={float(out.lr):.3e} "
                     f"gnorm={float(out.grad_norm):.3f} [tiered-opt "
                     f"overlap={store.overlap_frac():.2f}]")
        if self.watchdog is not None:
            self.watchdog.observe(self, out)
        return out

    def _log_zero_sharding_summary(self, shapes, opt_specs) -> None:
        """One bring-up line saying how much master/optimizer state actually
        got ZeRO-sharded — indivisible leaves silently stay replicated
        (`_add_zero_axes`), which at scale is exactly the class of memory
        regression the reference's partitioner errors on. Make it visible."""
        part = self.partitioner
        if self.config.zero_config.stage < 1 or part.zero_size <= 1:
            return
        zero_axes = set(part.zero_axes)
        is_p = lambda x: isinstance(x, P)  # noqa: E731
        shape_leaves = jax.tree.leaves(
            shapes, is_leaf=lambda x: isinstance(x, tuple))
        spec_paths = jax.tree_util.tree_flatten_with_path(
            opt_specs, is_leaf=is_p)[0]
        n_zero = n_model = n_repl = 0
        bytes_zero = bytes_model = bytes_repl = 0
        repl_names: List[str] = []
        for shape, (path, spec) in zip(shape_leaves, spec_paths):
            axes_used = set()
            for e in spec:
                axes_used.update(e if isinstance(e, tuple) else (e,))
            axes_used.discard(None)
            nbytes = int(np.prod(shape or (1,))) * 4  # fp32 master
            if axes_used & zero_axes:
                n_zero += 1
                bytes_zero += nbytes
            elif axes_used:  # TP/expert/pipe-sharded, just not over ZeRO axes
                n_model += 1
                bytes_model += nbytes
            else:
                n_repl += 1
                bytes_repl += nbytes
                if len(repl_names) < 5:
                    repl_names.append(jax.tree_util.keystr(path))
        msg = (f"ZeRO-{self.config.zero_config.stage} partitioning over "
               f"{tuple(part.zero_axes)} (world {part.zero_size}): "
               f"{n_zero} leaves ZeRO-sharded "
               f"({bytes_zero / 2**20:.1f} MiB fp32)")
        if n_model:
            msg += (f", {n_model} model-parallel-sharded only "
                    f"({bytes_model / 2**20:.1f} MiB fp32)")
        msg += f", {n_repl} replicated ({bytes_repl / 2**20:.1f} MiB fp32)"
        if n_repl:
            msg += (f" — replicated (indivisible or rule-pinned): "
                    f"{', '.join(repl_names)}"
                    + (", …" if n_repl > len(repl_names) else ""))
        log_dist(msg)

    # ------------------------------------------------------------------ #
    # reference property accessors (engine.py:770-1252 parity, abridged)
    # ------------------------------------------------------------------ #
    def train_batch_size(self) -> int:
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self) -> int:
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self) -> int:
        return self.config.gradient_accumulation_steps

    def zero_optimization_stage(self) -> int:
        return self.config.zero_config.stage

    def get_lr(self) -> List[float]:
        return [float(self.lr_schedule(jnp.asarray(self.global_steps, jnp.float32)))]

    def get_global_grad_norm(self) -> float:
        return getattr(self, "_last_grad_norm", 0.0)

    @property
    def loss_scale(self) -> float:
        return float(self.state.loss_scale.scale)

    # --- further reference accessors (engine.py:770-1252) ---
    def get_batch_info(self):
        """(train_batch_size, micro_batch_per_gpu, gradient_accumulation)."""
        return (self.train_batch_size(),
                self.train_micro_batch_size_per_gpu(),
                self.gradient_accumulation_steps())

    @property
    def global_samples(self) -> int:
        return self.global_steps * self.train_batch_size()

    def zero_optimization(self) -> bool:
        return self.config.zero_config.stage > 0

    def bfloat16_enabled(self) -> bool:
        return self.config.bf16.enabled

    def fp16_enabled(self) -> bool:
        return self.config.fp16.enabled

    def gradient_clipping_value(self) -> float:
        return float(self.config.gradient_clipping or 0.0)

    def steps_per_print(self) -> int:
        return self.config.steps_per_print

    def wall_clock_breakdown(self) -> bool:
        return bool(getattr(self.config, "wall_clock_breakdown", False))

    @property
    def module(self):
        """The user model (reference returns the wrapped nn.Module)."""
        return self.model

    def set_lr(self, lr: float) -> None:
        """Pin the LR to a constant (reference ``engine.set_lr`` writes the
        value into EVERY param group). base_lr must stay the optimizer's
        factory lr — the step computes ``lr_scale = sched(t)/base_lr`` and
        the optimizer multiplies by its own lr, so resetting base_lr here
        would cancel the scale and silently keep the old rate.

        The pinned value flows into the compiled step as a traced scalar
        (``self._lr_override``), so per-interval set_lr (the RLHF pattern)
        never thrashes recompiles."""
        self.lr_schedule = constant(float(lr))
        self.lr_scheduler = LRScheduler(self.lr_schedule)
        if getattr(self, "_grouped_ctor", None) is not None:
            # grouped optimizers have per-group lrs; reference semantics are
            # uniform after set_lr → rebuild with every group pinned to lr.
            # This changes the optimizer itself, so the cached steps must go.
            from ..ops.optimizers import grouped_optimizer

            name, groups, kwargs, ptree = self._grouped_ctor
            kwargs = {**kwargs, "lr": float(lr)}
            groups = [{k: v for k, v in g.items() if k != "lr"}
                      for g in groups]
            self.optimizer = grouped_optimizer(name, ptree, groups, **kwargs)
            # guard lr=0 (freeze): base_lr=0 would make lr_scale 0/0 = NaN
            self.base_lr = float(lr) or 1.0
            self._train_step = None
            self._apply_step = None
        self._lr_override = jnp.asarray(float(lr), jnp.float32)

    def get_mom(self) -> List[float]:
        b = self.optimizer.hyperparams.get("betas", (0.9, 0.999))
        return [float(b[0] if isinstance(b, (tuple, list)) else b)]

    def dp_world_size(self) -> int:
        return self.mesh_mgr.dp_world_size

    def mp_world_size(self) -> int:
        return self.mesh_mgr.tp_world_size

    # ------------------------------------------------------------------ #
    # opt state init (sharded)
    # ------------------------------------------------------------------ #
    def _init_opt_state(self, params):
        opt_shapes = jax.eval_shape(self.optimizer.init, params)
        # optimizer state leaves mirror param structure inside (mu/nu/...).
        # We shard any leaf whose shape matches a param leaf's shape with that
        # param's opt-state spec; scalars stay replicated.
        param_leaves = jax.tree.leaves(params)
        spec_leaves = jax.tree.leaves(self.opt_param_specs,
                                      is_leaf=lambda x: isinstance(x, P))
        shape_to_spec = {}
        for pl, sp in zip(param_leaves, spec_leaves):
            shape_to_spec.setdefault(tuple(pl.shape), sp)

        def leaf_spec(l):
            return shape_to_spec.get(tuple(l.shape), P())

        opt_specs = jax.tree.map(leaf_spec, opt_shapes)
        self.opt_state_specs = opt_specs
        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh_mgr.mesh, s), opt_specs,
                                 is_leaf=lambda x: isinstance(x, P))
        return jax.jit(self.optimizer.init, out_shardings=shardings)(params)

    # ------------------------------------------------------------------ #
    # the compiled train step
    # ------------------------------------------------------------------ #
    def _cast_gather(self, params):
        """Compute-cast + gather-to-compute-layout.

        ZeRO stages 1/2: masters are sharded over the ZeRO axes but compute
        wants the TP-only layout — the constraint makes XLA all-gather the
        low-precision copy (the reference's post-step allgather of updated
        partitions, stage_1_and_2.py:2223, moved to gather-on-compute-cast).
        At stage 3 the constraint keeps params sharded; XLA gathers at use —
        except under hpZ (``zero_hpz_partition_size``), where the constraint
        is the once-per-step PRIMARY gather from the full master partition
        into the intra-island secondary partition (the only collective that
        crosses the 'data'/DCN tier; fwd/bwd gathers then resolve over the
        secondary axes only).

        ZeRO++ qwZ (``zero_quantized_weights``, reference
        ``runtime/zero/config.py:309`` + ``csrc/quantization/
        swizzled_quantize.cu``): wherever the master layout differs from the
        compute-param layout — a real gather boundary — the tensor that
        crosses it is int8 with per-row fp32 scales
        (``compressed.quantized_gather``), quartering the fp32 wire bytes.
        At stage 3 the per-layer use-site gathers quantize through
        ``overlap.prefetch_scan`` instead (the explicit gather seam)."""
        compute = self.precision.cast_to_compute(params)
        zc = self.config.zero_config
        mm = self.mesh_mgr
        part = self.partitioner
        secondary = tuple(getattr(part, "secondary_axes", None) or ())
        qwz = bool(zc.zero_quantized_weights and mm.zero_world_size > 1)
        is_p = lambda x: isinstance(x, P)  # noqa: E731
        pspec_leaves = jax.tree.leaves(self.param_specs, is_leaf=is_p)
        mspec_leaves = jax.tree.leaves(self.opt_param_specs, is_leaf=is_p)

        def quantizes(leaf, pspec, mspec):
            # quantize only where a gather boundary actually exists (the
            # master/opt layout differs from the compute-param layout) — at
            # stage 0, or for leaves ZeRO left unsharded (indivisible dims),
            # the int8 roundtrip would cost precision and save zero wire
            # bytes. Plain stage 3 has no boundary HERE (params stay sharded,
            # gather-at-use); hpZ's primary gather is one.
            return (qwz and isinstance(leaf, jnp.ndarray)
                    and jnp.issubdtype(leaf.dtype, jnp.floating)
                    and leaf.ndim >= 2 and mspec != pspec)

        # comms-logger: the constraint below makes XLA all-gather the
        # ZeRO-sharded low-precision params — record that implied collective
        # at trace time. The gather crosses the primary axes NOT covered by
        # the secondary partition ('data' only, under hpZ); qwZ leaves ride
        # the wire as int8 + per-row fp32 scales, recorded as such so
        # algo_bytes reflects the actual quantized wire volume.
        tel = dist.get_telemetry()
        if tel.enabled and zc.stage >= 1 and mm.zero_world_size > 1:
            gather_axes = tuple(a for a in part.zero_axes
                                if mm.axis_size(a) > 1
                                and a not in secondary)
            q_leaves, plain = [], []
            for leaf, ps, ms in zip(jax.tree.leaves(compute), pspec_leaves,
                                    mspec_leaves):
                (q_leaves if quantizes(leaf, ps, ms) else plain).append(leaf)
            if gather_axes:
                if plain:
                    tel.record("all_gather_params", gather_axes, plain)
                if q_leaves:
                    payload = [
                        (jax.ShapeDtypeStruct(l.shape, jnp.int8),
                         jax.ShapeDtypeStruct(l.shape[:-1] + (1,),
                                              jnp.float32))
                        for l in q_leaves]
                    tel.record("all_gather_params_q", gather_axes, payload,
                               fp32_equiv=sum(l.size for l in q_leaves) * 4)
            if secondary and zc.stage >= 3 and \
                    not getattr(self, "_layer_prefetch_on", False):
                # hpZ: the at-use fwd/bwd gathers resolve inside the
                # secondary (ICI) island — trace-time estimate of their
                # volume (with layer_prefetch on, prefetch_scan records the
                # explicit per-layer gathers instead)
                tel.record("all_gather_params_secondary", secondary, compute)

        if not qwz:
            return jax.lax.with_sharding_constraint(
                compute, self._param_shardings)

        from ..comm.compressed import quantized_gather

        def one(leaf, param_sharding, pspec, mspec):
            if not quantizes(leaf, pspec, mspec):
                return jax.lax.with_sharding_constraint(leaf, param_sharding)
            sspec = list(pspec)[:leaf.ndim]
            sspec += [None] * (leaf.ndim - len(sspec))
            if sspec:
                sspec[-1] = None  # scales' trailing dim is size 1
            scale_sharding = mm.sharding(*sspec)
            return quantized_gather(leaf, param_sharding, scale_sharding)

        # tree.map follows `compute`'s structure, so the P leaves of the
        # spec trees are taken whole (not flattened as tuples). Matrix
        # leaves with a real gather boundary land in the compute-param
        # layout via the int8 wire; everything else keeps the normal
        # constraint (plain stage-3 gather-on-use included).
        return jax.tree.map(one, compute, self._param_shardings,
                            self.param_specs, self.opt_param_specs)

    def _raw_loss(self, compute_params, batch):
        """Model loss on already-cast/gathered compute params. Routes
        through the tiled fused logits+loss head when
        ``sequence.tiled_loss`` is on — the [B, S, V] logits tensor is
        never materialized (sequence/tiled.py). With the knob off (the
        default) this is exactly ``model.loss_fn``: the trace, and hence
        the compiled train step, is byte-identical to before."""
        seq = self.config.sequence
        if seq.tiled_loss and self.model.tiled_loss_fn is not None:
            return self.model.tiled_loss_fn(compute_params, batch,
                                            shards=seq.tiled_loss_shards)
        return self.model.loss_fn(compute_params, batch)

    def _loss(self, params, batch):
        compute_params = self._cast_gather(params)
        out = self._raw_loss(compute_params, batch)
        if isinstance(out, tuple):
            loss, aux = out
        else:
            loss, aux = out, {}
        return loss.astype(jnp.float32), aux

    def _grads_one_micro(self, params, batch, loss_scale):
        from ..comm.mesh import BATCH_AXES as _BA

        if self.config.zero_config.zero_quantized_gradients and \
                self.mesh_mgr.pp_world_size <= 1 and \
                any(self.mesh_mgr.axis_size(a) > 1 for a in _BA):
            return self._qgz_one_micro(params, batch, loss_scale)
        if self.model.pipeline_grad_fn is not None and \
                self.mesh_mgr.pp_world_size > 1:
            # 1F1B pipeline schedule (bounded activations) — the model owns
            # the stage decomposition; the engine supplies the compute cast
            compute_params = self._cast_gather(params)
            grads, loss, aux = self.model.pipeline_grad_fn(
                compute_params, batch, loss_scale.scale)
            return grads, loss.astype(jnp.float32), aux

        def scaled_loss(p):
            loss, aux = self._loss(p, batch)
            return scale_loss(loss, loss_scale), (loss, aux)

        grads, (loss, aux) = jax.grad(scaled_loss, has_aux=True)(params)
        return grads, loss, aux

    def _qgz_one_micro(self, params, batch, loss_scale):
        """ZeRO++ qgZ (``zero_quantized_gradients``): per-device LOCAL grads,
        reduced with a hierarchical int8 quantize → reduce-scatter →
        dequantize over the batch axes (reference
        ``runtime/comm/coalesced_collectives.py:31 all_to_all_quant_reduce``,
        ``csrc/quantization/quant_reduce.cu``). The wire moves int8 (+ tiny
        fp32 group scales) instead of fp32 — the DCN-crossing story. Leaves
        whose target spec is replicated (and the 'data' axis under MiCS, which
        replicates) reduce with a plain fp32 psum."""
        from ..comm import overlap as ov
        from ..comm.mesh import BATCH_AXES
        from ..comm.compressed import quantized_reduce_scatter_dim

        mm = self.mesh_mgr
        manual = tuple(a for a in BATCH_AXES if mm.axis_size(a) > 1)
        assert manual, "qgZ dispatch requires a >1 batch axis (see caller)"
        n_total = int(np.prod([mm.axis_size(a) for a in manual]))

        # cast + TP-layout gather OUTSIDE the manual region: compute params
        # carry no batch-axis sharding below stage 3
        compute = self._cast_gather(params)

        is_p = lambda x: isinstance(x, P)  # noqa: E731
        flat_specs = jax.tree.leaves(self.grad_specs, is_leaf=is_p)
        param_leaves = jax.tree.leaves(params)  # grad shapes == param shapes

        # per-leaf plan (shared with the comms_overlap engine — overlap.py)
        plans = ov.make_reduce_plans(param_leaves, flat_specs, manual,
                                     mm.axis_size)

        gdef_template = jax.tree_util.tree_structure(params)
        out_gspecs = jax.tree_util.tree_unflatten(
            gdef_template,
            [ov.plan_out_spec(leaf.ndim, plan)
             for leaf, plan in zip(param_leaves, plans)])
        batch_specs = jax.tree.map(lambda x: P(manual), batch)

        def local(compute_params, lbatch):
            def scaled(p):
                out = self._raw_loss(p, lbatch)
                loss, aux = out if isinstance(out, tuple) else (out, {})
                loss = loss.astype(jnp.float32)
                return scale_loss(loss, loss_scale), (loss, aux)

            grads, (loss, aux) = jax.grad(scaled, has_aux=True)(compute_params)
            gleaves, gdef = jax.tree_util.tree_flatten(grads)
            red = []
            for g, (d, scatter, residual) in zip(gleaves, plans):
                g = g.astype(jnp.float32)
                if d is not None:
                    g = quantized_reduce_scatter_dim(g, d, scatter)
                if residual:
                    g = jax.lax.psum(g, residual)
                red.append(g / n_total)
            grads = jax.tree_util.tree_unflatten(gdef, red)
            loss = jax.lax.psum(loss, manual) / n_total
            aux = jax.tree.map(
                lambda a: jax.lax.psum(a.astype(jnp.float32), manual) / n_total
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact)
                else jax.lax.psum(jnp.asarray(a), manual), aux)
            return grads, loss, aux

        return dist.shard_map(
            local, mesh=mm.mesh, axis_names=set(manual),
            in_specs=(P(), batch_specs),
            out_specs=(out_gspecs, P(), P()),
            check_vma=False)(compute, batch)

    def _constrain_grads(self, grads, record: bool = True,
                         repeats: int = 1):
        """Apply the stage's gradient sharding (reduce-scatter from stage 2 —
        reference stage_1_and_2.py:126): XLA fuses the implied psum over the
        data axes with this placement into a reduce-scatter.

        ``repeats``: execution count of the enclosing trace region (a scan
        body over GAS micros executes per micro) so the telemetry's per-step
        volume stays honest; ``record=False`` for constraints that imply no
        reduction (placing a fresh zeros accumulator)."""
        # comms-logger: the batch-sharded loss implies a grad reduction over
        # the batch axes — record it at trace time so data-parallel volume
        # shows up in the per-op summary even though XLA inserts the op
        tel = dist.get_telemetry()
        if tel.enabled and record:
            axes = tuple(a for a in BATCH_AXES
                         if self.mesh_mgr.axis_size(a) > 1)
            if axes:
                op = ("reduce_scatter_grads"
                      if self.config.zero_config.stage >= 2
                      else "all_reduce_grads")
                tel.record(op, axes, grads, repeats=repeats)
        return jax.lax.with_sharding_constraint(grads, self._grad_shardings)

    def _accumulate(self, params, batch, loss_scale):
        """GAS micro-batch loop under lax.scan; batch leading dim = gas.
        The PER-MICRO reduction path: each micro's implied grad reduce fires
        inside the scan body (gas collectives per step). The comms_overlap
        config block swaps this for :meth:`_accumulate_overlap`."""
        gas = self.gradient_accumulation_steps()
        if gas == 1:
            grads, loss, aux = self._grads_one_micro(params, batch, loss_scale)
            return self._constrain_grads(grads), loss, aux

        def body(carry, micro):
            acc = carry
            grads, loss, aux = self._grads_one_micro(params, micro, loss_scale)
            acc = jax.tree.map(lambda a, g: a + g.astype(jnp.float32), acc, grads)
            # keep the accumulator in the stage's grad layout between micros
            # (stage>=2: sharded — the API-parity path stays O(params/N));
            # the body executes once per micro → repeats=gas
            return self._constrain_grads(acc, repeats=gas), (loss, aux)

        zeros = self._constrain_grads(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params),
            record=False)
        acc, (losses, auxes) = jax.lax.scan(body, zeros, batch)
        grads = jax.tree.map(lambda g: g / gas, acc)
        # aux: mean over micros for floats, sum otherwise (token counts etc.)
        aux = jax.tree.map(
            lambda a: jnp.mean(a, axis=0) if jnp.issubdtype(a.dtype, jnp.inexact)
            else jnp.sum(a, axis=0), auxes)
        return grads, jnp.mean(losses), aux

    # ------------------------------------------------------------------ #
    # comms_overlap: deferred / bucketed / LoCo gradient reduction
    # ------------------------------------------------------------------ #
    def _overlap_active(self) -> bool:
        """The comms_overlap reduction engine replaces ``_accumulate`` when
        the block is enabled, a data-parallel axis exists, and no pipeline
        schedule owns the backward."""
        co = self.config.comms_overlap
        if not co.enabled:
            return False
        if self.config.zero_config.stage >= 3:
            return False  # stage 3: only layer_prefetch + XLA flags apply
        if self.mesh_mgr.pp_world_size > 1:
            return False  # 1F1B owns its reduction (logged at init)
        return any(self.mesh_mgr.axis_size(a) > 1 for a in BATCH_AXES)

    def _overlap_setup(self):
        """Static per-leaf routing for the overlap engine, computed once:
        (manual axes, world, reduce plans, flat buckets, bucketed set, LoCo
        leaf indices). Shapes only — safe to cache for the engine's life."""
        if self._overlap_plan_cache is not None:
            return self._overlap_plan_cache
        from ..comm import overlap as ov

        co = self.config.comms_overlap
        mm = self.mesh_mgr
        manual = tuple(a for a in BATCH_AXES if mm.axis_size(a) > 1)
        n_total = int(np.prod([mm.axis_size(a) for a in manual]))
        is_p = lambda x: isinstance(x, P)  # noqa: E731
        flat_specs = jax.tree.leaves(self.grad_specs, is_leaf=is_p)
        leaves = jax.tree.leaves(self.state.params)
        plans = ov.make_reduce_plans(leaves, flat_specs, manual, mm.axis_size)
        buckets: List[List[int]] = []
        bucketed: frozenset = frozenset()
        if co.coalesce_buckets:
            bucket_bytes = max(int(co.bucket_size_mb * 2 ** 20), 4 * n_total)
            small = [i for i, l in enumerate(leaves)
                     if ov.padded_rows(l.size, n_total) * 4 <= bucket_bytes]
            buckets = ov.plan_buckets(small, [l.size for l in leaves],
                                      n_total, bucket_bytes)
            bucketed = frozenset(i for b in buckets for i in b)
        loco_idx: Tuple[int, ...] = ()
        if co.loco:
            # error feedback exists where quantization does: the int8
            # scatter-planned leaves under qgZ, and the psum-planned leaves
            # under the EQuARX-style quantized all-reduce (bucketed small
            # leaves reduce in exact fp32 and need no compensation)
            qgz_ = self.config.zero_config.zero_quantized_gradients
            loco_idx = tuple(
                i for i, p in enumerate(plans) if i not in bucketed
                and ((p.dim is not None and qgz_)
                     or (p.dim is None and p.psum_axes
                         and co.quantized_all_reduce)))
        self._overlap_plan_cache = (manual, n_total, plans, buckets,
                                    bucketed, loco_idx)
        return self._overlap_plan_cache

    def _layer_prefetch_shardings(self):
        """Per-layer GATHERED-layout shardings for the model's stacked
        ``layers`` subtree (leading stacked dim dropped from each spec) —
        the constraint :func:`overlap.prefetch_scan` pins each sliced layer
        to, so XLA starts the ZeRO all-gather at slice time. Models whose
        param tree has no ``layers`` dict get no constraint (the prefetch
        ordering barrier still applies)."""
        params = self.state.params
        if not (isinstance(params, dict) and "layers" in params):
            return None
        is_p = lambda x: isinstance(x, P)  # noqa: E731
        sub = self._qw_gather_specs["layers"]
        mesh = self.mesh_mgr.mesh

        def drop_stacked(spec):
            return NamedSharding(mesh, P(*list(spec)[1:]))

        return jax.tree.map(drop_stacked, sub, is_leaf=is_p)

    def _layer_prefetch_quant(self):
        """ZeRO++ qwZ descriptors for the prefetch gathers: a pair of trees
        matching the model's ``layers`` subtree — per-leaf bool (quantize
        this leaf's gather) and the per-leaf SCALE sharding in the gathered
        layout. ``overlap.prefetch_scan`` routes flagged leaves through
        ``compressed.quantized_gather`` so each per-layer all-gather moves
        int8 + per-row fp32 scales instead of full-width bytes. None when
        qwZ is off or the param tree has no ``layers`` dict."""
        if not self.config.zero_config.zero_quantized_weights:
            return None
        params = self.state.params
        if not (isinstance(params, dict) and "layers" in params):
            return None
        is_p = lambda x: isinstance(x, P)  # noqa: E731
        gathered = self._qw_gather_specs["layers"]
        sharded = self.param_specs["layers"]
        mesh = self.mesh_mgr.mesh

        def flag(leaf, gspec, pspec):
            # a sliced layer leaf drops the stacked dim; quantize where the
            # stacked (ZeRO-sharded) layout differs from the gathered one —
            # a real per-layer gather boundary — on float matrix leaves
            return bool(jnp.issubdtype(leaf.dtype, jnp.floating)
                        and leaf.ndim - 1 >= 2
                        and P(*list(gspec)[1:]) != P(*list(pspec)[1:]))

        def scale_shard(leaf, gspec):
            nd = leaf.ndim - 1  # stacked dim dropped
            ents = list(gspec)[1:][:nd]
            ents += [None] * (nd - len(ents))
            if ents:
                ents[-1] = None  # scales' trailing dim is size 1
            return NamedSharding(mesh, P(*ents))

        flags = jax.tree.map(flag, params["layers"], gathered, sharded)
        scales = jax.tree.map(scale_shard, params["layers"], gathered)
        return flags, scales

    def _init_loco_residuals(self) -> None:
        """Allocate the per-leaf LoCo quantization-error residuals into
        ``TrainState``: global shape [dp_world, *leaf.shape] fp32, sharded
        over the batch axes (each device owns its own error)."""
        manual, n_total, _, _, _, loco_idx = self._overlap_setup()
        if not loco_idx:
            return
        leaves = jax.tree.leaves(self.state.params)
        res = []
        for i in loco_idx:
            leaf = leaves[i]
            sharding = NamedSharding(self.mesh_mgr.mesh, P(manual))
            res.append(jax.device_put(
                jnp.zeros((n_total,) + tuple(leaf.shape), jnp.float32),
                sharding))
        self.state = self.state._replace(loco_residual=tuple(res))
        log_dist(f"comms_overlap LoCo: carrying {len(res)} error-feedback "
                 f"residual leaves (err_beta="
                 f"{self.config.comms_overlap.loco_err_beta})")

    def _accumulate_overlap(self, params, batch, loss_scale, residuals):
        """The comms_overlap replacement for :meth:`_accumulate`: gradients
        reduce with EXPLICIT collectives in a manual (shard_map) region —

        - small leaves coalesce into flat buckets → one reduce-scatter +
          all-gather per bucket instead of one collective per leaf;
        - large leaves reduce-scatter straight into the stage's sharded grad
          layout (int8-quantized when qgZ is on, with optional LoCo error
          feedback);
        - with ``deferred_gradient_reduce``, micro-batch grads accumulate in
          the local (unreduced, full-shape fp32) layout and the collectives
          fire ONCE per optimizer step instead of once per micro.

        Returns ``(grads, loss, aux, new_residuals)``."""
        from ..comm import compressed as cc
        from ..comm import overlap as ov

        co = self.config.comms_overlap
        mm = self.mesh_mgr
        gas = self.gradient_accumulation_steps()
        manual, n_total, plans, buckets, bucketed, loco_idx = \
            self._overlap_setup()
        qgz = self.config.zero_config.zero_quantized_gradients
        qar = co.quantized_all_reduce
        deferred = co.deferred_gradient_reduce and gas > 1
        err_beta = float(co.loco_err_beta)
        # collectives in a non-deferred scan body run once per micro
        reps = gas if (gas > 1 and not deferred) else 1
        res_pos = {leaf_i: k for k, leaf_i in enumerate(loco_idx)}

        compute = self._cast_gather(params)
        param_leaves = jax.tree.leaves(params)
        gdef = jax.tree_util.tree_structure(params)

        def scatter_world(plan):
            return int(np.prod([mm.axis_size(a) for a in plan.scatter]))

        def reduced_shape(leaf, i):
            plan = plans[i]
            if i in bucketed or plan.dim is None:
                return tuple(leaf.shape)
            shape = list(leaf.shape)
            shape[plan.dim] //= scatter_world(plan)
            return tuple(shape)

        out_gspecs = jax.tree_util.tree_unflatten(
            gdef,
            [P() if i in bucketed else ov.plan_out_spec(leaf.ndim, plans[i])
             for i, leaf in enumerate(param_leaves)])
        batch_specs = jax.tree.map(
            lambda x: P(None, manual) if gas > 1 else P(manual), batch)
        res_specs = tuple(P(manual) for _ in loco_idx)

        # bucket-flush spans fire at TRACE time (collectives are compile-time
        # constructs on TPU — one record describes every execution of the
        # compiled step, like the comms logger's per-trace records)
        _hub = getattr(self, "telemetry", None)
        tracer = _hub.tracer if _hub is not None else None

        def reduce_all(gleaves, res_leaves):
            """One full explicit reduction of the (local) grad leaves."""
            red: List[Any] = [None] * len(gleaves)
            new_res = list(res_leaves)
            for bucket in buckets:
                if tracer is not None and tracer.enabled:
                    tracer.instant(
                        "overlap/bucket_flush", cat="comm", trace_time=True,
                        leaves=len(bucket), deferred=deferred, repeats=reps,
                        bytes=int(sum(gleaves[i].size for i in bucket)) * 4)
                outs = ov.coalesced_reduce([gleaves[i] for i in bucket],
                                           manual, repeats=reps)
                for i, o in zip(bucket, outs):
                    red[i] = o
            for i, (g, plan) in enumerate(zip(gleaves, plans)):
                if red[i] is not None:
                    continue
                g = g.astype(jnp.float32)
                if plan.dim is not None:
                    if qgz:
                        if i in res_pos:
                            g, nr = cc.loco_quantized_reduce_scatter_dim(
                                g, plan.dim, plan.scatter,
                                new_res[res_pos[i]], err_beta=err_beta)
                            new_res[res_pos[i]] = nr
                        else:
                            g = cc.quantized_reduce_scatter_dim(
                                g, plan.dim, plan.scatter)
                    else:
                        g = ov.reduce_scatter_dim(g, plan.dim, plan.scatter,
                                                  repeats=reps)
                if plan.psum_axes:
                    if qar and plan.dim is None:
                        # EQuARX-style quantized all-reduce: the non-ZeRO DP
                        # path (replicated grad layout) — int8 RS + int8 AG
                        # instead of a full-width psum
                        if i in res_pos:
                            g, nr = cc.quantized_all_reduce_ef(
                                g, plan.psum_axes, new_res[res_pos[i]],
                                err_beta=err_beta, repeats=reps)
                            new_res[res_pos[i]] = nr
                        else:
                            g = cc.quantized_all_reduce(g, plan.psum_axes,
                                                        repeats=reps)
                    else:
                        dist.get_telemetry().record(
                            "all_reduce_grads", plan.psum_axes, g,
                            repeats=reps)
                        g = jax.lax.psum(g, plan.psum_axes)
                red[i] = g
            return red, new_res

        def local(compute_params, lbatch, res_in):
            res_leaves = [r[0] for r in res_in]  # drop the device dim

            def grads_of(mb):
                def scaled(p):
                    out = self.model.loss_fn(p, mb)
                    loss, aux = out if isinstance(out, tuple) else (out, {})
                    loss = loss.astype(jnp.float32)
                    return scale_loss(loss, loss_scale), (loss, aux)

                return jax.grad(scaled, has_aux=True)(compute_params)

            denom = float(n_total * gas)
            if gas == 1:
                grads, (loss, aux) = grads_of(lbatch)
                red, new_res = reduce_all(jax.tree.leaves(grads), res_leaves)
                losses, auxes = loss, aux
            elif deferred:
                # local-layout accumulation: full-shape fp32 partial grads
                # per device, ONE reduction at the step boundary
                def body(acc, mb):
                    grads, (loss, aux) = grads_of(mb)
                    acc = jax.tree.map(
                        lambda a, g: a + g.astype(jnp.float32), acc, grads)
                    return acc, (loss, aux)

                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), compute_params)
                acc, (losses, auxes) = jax.lax.scan(body, zeros, lbatch)
                red, new_res = reduce_all(jax.tree.leaves(acc), res_leaves)
            else:
                # per-micro explicit reduction (the collectives run inside
                # the scan body, but bucketed/quantized/LoCo still apply)
                def body(carry, mb):
                    acc, res = carry
                    grads, (loss, aux) = grads_of(mb)
                    red, res = reduce_all(jax.tree.leaves(grads), res)
                    acc = [a + r for a, r in zip(acc, red)]
                    return (acc, res), (loss, aux)

                zeros = [jnp.zeros(reduced_shape(leaf, i), jnp.float32)
                         for i, leaf in enumerate(param_leaves)]
                (red, new_res), (losses, auxes) = jax.lax.scan(
                    body, (zeros, res_leaves), lbatch)

            red = [g / denom for g in red]
            grads = jax.tree_util.tree_unflatten(gdef, red)
            loss = jax.lax.psum(jnp.mean(losses), manual) / n_total
            aux = jax.tree.map(
                lambda a: jax.lax.psum(
                    jnp.mean(a, axis=0).astype(jnp.float32)
                    if jnp.asarray(a).ndim and gas > 1 else
                    jnp.asarray(a).astype(jnp.float32), manual) / n_total
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.inexact)
                else jax.lax.psum(
                    jnp.sum(jnp.asarray(a), axis=0)
                    if jnp.asarray(a).ndim and gas > 1
                    else jnp.asarray(a), manual), auxes)
            return (grads, loss, aux,
                    tuple(r[None] for r in new_res))

        grads, loss, aux, new_residuals = dist.shard_map(
            local, mesh=mm.mesh, axis_names=set(manual),
            in_specs=(P(), batch_specs, res_specs),
            out_specs=(out_gspecs, P(), P(), res_specs),
            check_vma=False)(compute, batch, residuals)
        # place (bucketed leaves: a local slice; planned leaves: no-op) into
        # the stage's grad layout — no additional comm is implied here
        grads = jax.lax.with_sharding_constraint(grads, self._grad_shardings)
        return grads, loss, aux, new_residuals

    @jax.named_scope("optimizer")   # unscale, clip, the optimizer's update
    def _apply_update(self, state: TrainState, grads, loss, aux=None,
                      lr_override=None,
                      loco_residual=None) -> Tuple[TrainState, StepOutput]:
        cfg = self.config
        finite = grads_finite(grads)
        grads = unscale_grads(grads, state.loss_scale)

        grad_norm = _global_norm(grads)
        if cfg.gradient_clipping and cfg.gradient_clipping > 0:
            clip_coef = jnp.minimum(1.0, cfg.gradient_clipping / (grad_norm + 1e-6))
            grads = jax.tree.map(lambda g: g * clip_coef, grads)

        lr_t = self.lr_schedule(state.step.astype(jnp.float32))
        if lr_override is not None:
            lr_t = jnp.where(lr_override >= 0, lr_override, lr_t)
        lr_scale = lr_t / self.base_lr

        new_params, new_opt = self.optimizer.update(state.params, grads,
                                                    state.opt_state, lr_scale=lr_scale)
        # masters keep their ZeRO-sharded layout across the update
        new_params = jax.lax.with_sharding_constraint(
            new_params, self._master_shardings)
        # overflow → skip update (reference: FP16 optimizer skip + scale cut)
        new_params = jax.tree.map(
            lambda n, o: jnp.where(finite, n, o), new_params, state.params)
        new_opt = jax.tree.map(
            lambda n, o: jnp.where(finite, n, o) if n.shape == o.shape else n,
            new_opt, state.opt_state)
        new_scale = update_loss_scale(state.loss_scale, finite)
        new_state = TrainState(
            step=state.step + jnp.where(finite, 1, 0).astype(jnp.int32),
            params=new_params,
            opt_state=new_opt,
            loss_scale=new_scale,
            skipped_steps=state.skipped_steps + jnp.where(finite, 0, 1).astype(jnp.int32),
            # LoCo residuals advance even on a skipped step: they describe
            # the quantization error of the reduce that DID happen
            loco_residual=(state.loco_residual if loco_residual is None
                           else loco_residual),
        )
        aux = {} if aux is None else aux
        icfg = cfg.reliability.integrity
        if icfg.enabled and isinstance(aux, dict):
            from ..reliability.integrity import tree_fingerprint

            # digests of replica-invariant quantities: the unscaled/clipped
            # post-reduce grads, the post-step params and optimizer moments,
            # and the loss scalar. Three scalars per leaf — the transfer to
            # host happens only on check/audit steps (IntegrityPlane)
            fp = {}
            if icfg.fingerprint_grads:
                fp["grads"] = tree_fingerprint(grads)
            if icfg.fingerprint_params:
                fp["params"] = tree_fingerprint(new_params)
            if icfg.fingerprint_opt_state:
                fp["opt_state"] = tree_fingerprint(new_opt)
            fp["loss"] = tree_fingerprint(loss)
            aux = {**aux, "integrity": fp}
        out = StepOutput(loss=loss, grad_norm=grad_norm, lr=lr_t,
                         loss_scale=new_scale.scale,
                         overflow=jnp.logical_not(finite),
                         aux=aux)
        return new_state, out

    def _make_step_fn(self):
        overlap = self._overlap_active()

        def step_fn(state: TrainState, batch, lr_override):
            if overlap:
                grads, loss, aux, new_res = self._accumulate_overlap(
                    state.params, batch, state.loss_scale,
                    state.loco_residual)
                return self._apply_update(state, grads, loss, aux,
                                          lr_override,
                                          loco_residual=new_res)
            grads, loss, aux = self._accumulate(state.params, batch, state.loss_scale)
            return self._apply_update(state, grads, loss, aux, lr_override)

        return step_fn

    def _jit(self, name: str, fn, **jit_kwargs):
        """One of this engine's programs, through the telemetry hub's
        compile monitor (the recompilation sentinel + per-program cost model
        — telemetry/compile.py; default OFF → a plain ``jax.jit``). Whoever
        triggers its trace — a step, ``.lower()``, an audit — the program is
        traced under THIS engine's mesh, so what it calls can ask which mesh
        it spans (a per-device kernel must: ``ops/registry._per_device``)."""
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with jax.sharding.use_abstract_mesh(
                    self.mesh_mgr.mesh.abstract_mesh):
                return fn(*args, **kwargs)

        return self.telemetry.compile.jit(name, traced, **jit_kwargs)

    def _build_train_step(self):
        self._train_step = self._jit(
            "train_step", self._make_step_fn(), donate_argnums=(0,))
        return self._train_step

    # ------------------------------------------------------------------ #
    # remat: true with no policy named -> keep what fits
    # ------------------------------------------------------------------ #
    def _remat_for(self, batch) -> bool:
        """Before anything lowers or runs the step on ``batch`` (sharded):
        the rung of this batch's signature becomes the registry's default
        policy, which the model reads whenever the step is traced. A
        signature never seen before is chosen for first
        (``_choose_remat_rung``) - kept bytes grow with the batch, so the
        rung a short curriculum bucket holds is not the long one's - and
        ``True`` says so: the call that follows lowers the step, and goes
        through ``_first_lowering``."""
        if not self._remat_chooses:
            return False
        from .activation_checkpointing import checkpointing as ac

        sig = tuple((x.shape, x.dtype) for x in jax.tree.leaves(batch))
        choice = self._remat_choices.get(sig)
        first = choice is None
        if first:
            choice = self._choose_remat_rung(batch)
            if choice is None:        # the model leaves nothing to choose
                self._remat_chooses = False
                return False
            self._remat_choices[sig] = self._remat_unreported = choice
        if ac.last_choice() is not choice:
            ac.configure(choice=choice)
        self._remat_choice = choice
        return first

    def _choose_remat_rung(self, batch):
        """Which residuals the model's rematerialized layer scan keeps for
        ``batch`` (sharded), from the memory this device has
        (``activation_checkpointing.choose``): the allocator's limit and
        what the device holds - the larger of this engine's state and the
        allocator's count - as the job's processes agree on them, so every
        host of one program lowers the same one. Activations sharded over
        more than the batch axes (tensor, seq, pipe) are not modelled:
        there, and where the device reports no limit (the CPU mesh), the
        rung is ``full`` and the program is what it always was. ``None``
        where the model's config leaves nothing to choose."""
        from .activation_checkpointing import checkpointing as ac

        t0 = time.perf_counter()
        gas = self.gradient_accumulation_steps()
        mm = self.mesh_mgr

        def one_device(x):   # a micro-batch leaf as one device holds it
            shape = x.sharding.shard_shape(x.shape)
            return jax.ShapeDtypeStruct(shape[1 if gas > 1 else 0:], x.dtype)

        probe = self.model.remat_probe(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         self.state.params),
            jax.tree.map(one_device, batch))
        if probe is None:
            return None
        with self.telemetry.tracer.span("train_remat_choose", cat="train"):
            mem = self.telemetry.memory.snapshot()
            modelled = mm.world_size == mm.dp_world_size
            held, limit = _agreed_max([
                max(ac.device_bytes(self.state), int(mem["bytes_in_use"])),
                -int(mem["bytes_limit"] if modelled else 0)])
            choice = ac.choose(
                probe, self.state.params, self._param_shardings,
                self.precision.compute_dtype, limit_bytes=-limit,
                held_bytes=held,
                gathers_at_use=self.config.zero_config.stage >= 3
                and mm.zero_world_size > 1,
                accumulator_shardings=self._grad_shardings
                if gas > 1 else None)
        log_dist(f"remat: rung '{choice.rung}' of {ac.LADDER} "
                 f"(keeps {choice.kept_bytes.get(choice.rung, 0) / 1e9:.2f} "
                 f"GB, head-room {choice.headroom_bytes}; chosen in "
                 f"{time.perf_counter() - t0:.2f} s)")
        return choice

    def _first_lowering(self, batch, run):
        """``run()`` lowers and compiles the step for a batch signature new
        to it, under the rung ``_remat_for`` published. The head-room
        estimate gates the attempt; this is the net behind it: a compile
        that ends in RESOURCE_EXHAUSTED (nothing has run, the donated state
        is whole) falls back to ``full``, rebuilt and counted. Where the
        job has several processes the step is compiled apart from its run,
        and they fall back together or not at all: none has dispatched a
        program its peers do not hold."""
        choice = self._remat_choice
        try:
            if choice.rung != "full" and jax.process_count() > 1:
                failed = None
                try:
                    self._train_step.lower(self.state, batch,
                                           self._lr_override).compile()
                except jax.errors.JaxRuntimeError as e:
                    failed = e
                if _agreed_max([failed is not None])[0]:
                    raise failed or jax.errors.JaxRuntimeError(
                        "RESOURCE_EXHAUSTED: on another process")
            return run()
        except jax.errors.JaxRuntimeError as e:
            if choice.rung == "full" \
                    or "RESOURCE_EXHAUSTED" not in str(e) \
                    or any(x.is_deleted()
                           for x in jax.tree.leaves(self.state)):
                raise
            logger.warning(f"remat: rung '{choice.rung}' did not fit "
                           f"({str(e).splitlines()[0][:200]}); falling back "
                           f"to 'full'")
            from .activation_checkpointing import checkpointing as ac

            choice.rung = "full"
            choice.fallbacks += 1
            ac.configure(choice=choice)
            # the step is rebuilt, or the trace under the lost rung would
            # be served again; signatures compiled before retrace on their
            # next sight, each under its own rung
            self._build_train_step()
            return run()

    def _report_remat(self, span) -> None:
        """A choice, once its step has compiled: ``Train/remat/*`` gauges
        and arguments on that ``train_step`` span."""
        from .activation_checkpointing import checkpointing as ac

        choice, self._remat_unreported = self._remat_unreported, None
        peak = self.telemetry.compile.summary().get("train_step", {})
        choice.compiled_peak_bytes = int(peak.get("peak_memory_bytes", 0))
        values = {"rung": ac.LADDER.index(choice.rung),
                  "kept_bytes": choice.kept_bytes.get(choice.rung, 0),
                  "headroom_bytes": choice.headroom_bytes or 0,
                  "predicted_peak_bytes": choice.predicted_peak_bytes,
                  "compiled_peak_bytes": choice.compiled_peak_bytes,
                  "fallbacks": choice.fallbacks}
        for name, value in values.items():
            self.telemetry.train_event(f"remat/{name}", value,
                                       self.global_steps)
        for rung, kept in choice.kept_bytes.items():
            self.telemetry.train_event(f"remat/saved_bytes_{rung}", kept,
                                       self.global_steps)
        span.set(**{**{f"remat_{k}": v for k, v in values.items()},
                    "remat_rung": choice.rung})

    def _ensure_audit_step(self):
        """The shadow-recompute executable for integrity audits: the SAME
        step function as ``_train_step`` but WITHOUT input donation, so the
        auditor can re-run fwd/bwd on state buffers the live step is about
        to consume. Built lazily — never compiled unless an audit fires."""
        if getattr(self, "_audit_step", None) is None:
            self._audit_step = self._jit("audit_step", self._make_step_fn())
        return self._audit_step

    def _ensure_apply_step(self):
        """The jitted optimizer-apply phase, shared by the forward/backward/
        step API shims and the wall-clock-breakdown path."""
        if self._apply_step is None:
            self._apply_step = self._jit(
                "apply_step",
                lambda state, grads, loss, lro: self._apply_update(
                    state, grads, loss, lr_override=lro),
                donate_argnums=(0,))
        return self._apply_step

    def _build_breakdown_steps(self):
        """Phase-split steps for ``wall_clock_breakdown``: a loss-only
        forward, the grad computation, and the optimizer apply as three
        separately-jitted programs so each phase can be bracketed by a
        synchronized timer."""
        gas = self.gradient_accumulation_steps()

        def fwd_fn(params, batch):
            if gas == 1:
                return self._loss(params, batch)[0]
            losses = jax.lax.map(lambda mb: self._loss(params, mb)[0], batch)
            return jnp.mean(losses)

        def bwd_fn(params, batch, loss_scale):
            return self._accumulate(params, batch, loss_scale)

        self._fwd_step = self._jit("fwd_step", fwd_fn)
        self._bwd_step = self._jit("bwd_step", bwd_fn)
        self._ensure_apply_step()

    def _train_batch_breakdown(self, batch) -> StepOutput:
        """Instrumented optimizer step (``wall_clock_breakdown: true``):
        three jitted phases bracketed by device-synchronized timers and
        profiler spans. XLA fuses forward into the grad program, so ``fwd``
        is measured from a dedicated loss-only pass and ``bwd`` is the full
        grad phase (it includes the fused forward, as with rematerialized
        activations). This is a diagnostic mode: it costs roughly one extra
        forward per step and defeats the fused-step overlap — production
        throughput numbers come from the un-instrumented path."""
        if self._bwd_step is None:
            self._build_breakdown_steps()
        t = self.timers
        tracer = self.telemetry.tracer
        with tracer.span("train/fwd", cat="train"):
            t(FORWARD_GLOBAL_TIMER).start(sync=True)
            self._fwd_step(self.state.params, batch)
            t(FORWARD_GLOBAL_TIMER).stop(sync=True)
        with tracer.span("train/bwd", cat="train"):
            t(BACKWARD_GLOBAL_TIMER).start()
            grads, loss, aux = self._bwd_step(self.state.params, batch,
                                              self.state.loss_scale)
            t(BACKWARD_GLOBAL_TIMER).stop(sync=True)
        with tracer.span("train/step", cat="train"):
            t(STEP_GLOBAL_TIMER).start()
            self.state, out = self._apply_step(self.state, grads, loss,
                                               self._lr_override)
            t(STEP_GLOBAL_TIMER).stop(sync=True)
        return out

    def _estimate_step_flops(self, batch) -> None:
        """One-shot per-step flops estimate from XLA's cost analysis of the
        fused train step → feeds ThroughputTimer TFLOPS reporting. Gated on
        the flops profiler being enabled (the lowering is not free)."""
        self._flops_estimated = True
        try:
            if self._train_step is None:
                self._build_train_step()
            lowered = self._train_step.lower(self.state, batch,
                                             self._lr_override)
            cost = lowered.compile().cost_analysis() or {}
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            flops = float(cost.get("flops", 0.0))
            if flops > 0:
                self.tput_timer.set_flops_per_step(flops)
                log_dist(f"flops/step estimate: {flops:.3e} "
                         f"(XLA cost analysis)")
        except Exception as e:
            logger.debug(f"step flops estimate unavailable: {e}")

    # ------------------------------------------------------------------ #
    # public API — train_batch (PipelineEngine.train_batch parity)
    # ------------------------------------------------------------------ #
    def _shard_batch(self, batch, with_gas_dim: bool):
        """Reshape global batch [B, ...] → [gas, micro, ...] and place with
        batch sharding over (data, expert) [+ seq on dim 2 when SP active]."""
        gas = self.gradient_accumulation_steps()
        sp = self.mesh_mgr.sp_world_size

        def reshape(x):
            x = jnp.asarray(x)
            if with_gas_dim and gas > 1:
                b = x.shape[0]
                if b % gas != 0:
                    raise ValueError(f"batch dim {b} not divisible by gas={gas}")
                x = x.reshape((gas, b // gas) + x.shape[1:])
            return x

        batch = jax.tree.map(reshape, batch)

        def spec_for(x):
            batch_dim_index = 1 if (with_gas_dim and gas > 1) else 0
            entries = [None] * x.ndim
            if x.ndim > batch_dim_index:
                entries[batch_dim_index] = BATCH_AXES
            seq_dim = batch_dim_index + 1
            # shard the sequence dim for Ulysses SP only when it divides evenly
            # (token arrays often carry a +1 label column)
            if sp > 1 and x.ndim > seq_dim and x.shape[seq_dim] % sp == 0:
                entries[seq_dim] = "seq"
            return NamedSharding(self.mesh_mgr.mesh, P(*entries))

        return jax.tree.map(lambda x: jax.device_put(x, spec_for(x)), batch)

    @staticmethod
    def _count_batch_tokens(batch) -> int:
        """Host-side token estimate for one global batch: the size of the
        ``tokens`` leaf when the batch carries one, the leading (sample) dim
        of the first leaf otherwise. Shape math only — never touches device
        data."""
        try:
            if isinstance(batch, dict) and "tokens" in batch:
                return int(np.prod(np.shape(batch["tokens"])))
            leaves = jax.tree.leaves(batch)
            if leaves:
                shape = np.shape(leaves[0])
                return int(shape[0]) if shape else 1
        except Exception:
            pass
        return 0

    def train_batch(self, batch) -> StepOutput:
        """One full optimizer step from one global batch (all GAS micro-batches
        stacked in the leading dim). One ``train_step`` span: a step event
        on the profiler's timeline, with the host phases of the step as its
        children (docs/observability.md)."""
        with self.telemetry.tracer.step_span(
                "train_step", self.global_steps + 1, cat="train") as span:
            out = self._train_batch(batch)
            if self._remat_unreported is not None:
                self._report_remat(span)
            return out

    def _train_batch(self, batch) -> StepOutput:
        tracer = self.telemetry.tracer
        self.global_tokens += self._count_batch_tokens(batch)
        if self._nvme_opt is not None:
            return self._train_batch_nvme(batch)
        if self._tiered_opt:
            return self._train_batch_tiered(batch)
        breakdown = self.wall_clock_breakdown()
        if self._train_step is None and not breakdown:
            self._build_train_step()
        self.tput_timer.start()
        self.telemetry.step_begin(self.global_steps + 1)
        if self.watchdog is not None:
            self.watchdog.step_started()
        if self.curriculum_scheduler is not None:
            # difficulty = seq length; each bucket is its own cached jit
            batch = self.curriculum_scheduler.truncate(batch, self.global_steps)
        with tracer.span("train_shard_batch", cat="train"):
            batch = self._shard_batch(batch, with_gas_dim=True)
        # before anything lowers the step: the flops estimate and the
        # integrity audit below do, and a trace is cached
        first_lowering = not breakdown and self._remat_for(batch)
        if not self._flops_estimated and self.config.flops_profiler.enabled:
            self._estimate_step_flops(batch)
        if breakdown:
            self.timers(TRAIN_BATCH_TIMER).start()
            with tracer.span("train/train_batch", cat="train",
                             step=self.global_steps + 1):
                out = self._train_batch_breakdown(batch)
            self.timers(TRAIN_BATCH_TIMER).stop(sync=False)
        else:
            # shadow recompute audit (rotating auditor): must run BEFORE
            # the live step donates the state buffers it reads
            if self.integrity is not None:
                self.integrity.pre_step(self, batch)
            # the fused step is ONE XLA program — a single span around its
            # dispatch (the phase split only exists under
            # wall_clock_breakdown)
            with tracer.span("train/train_batch", cat="train",
                             step=self.global_steps + 1):
                if first_lowering:
                    self.state, out = self._first_lowering(
                        batch, lambda: self._train_step(
                            self.state, batch, self._lr_override))
                else:
                    self.state, out = self._train_step(self.state, batch,
                                                       self._lr_override)
        self.global_steps += 1
        self._last_grad_norm = out.grad_norm
        self.lr_scheduler.last_step = self.global_steps
        with tracer.span("train_sync", cat="train"):
            # asks every device to drain (utils/timer.py); on the v5e the
            # call returns at once (PERF.md section 5)
            self.tput_timer.stop()
        with tracer.span("train_step_end", cat="train"):
            self._write_monitor_events(out)
            self.telemetry.step_end(
                self.global_steps,
                step_time_s=self.tput_timer.avg_step_time() or None)
        if self.tuning is not None:
            # optimizer-step seam: the only point a training knob may flip
            # (an apply invalidates the cached step — next batch rebuilds).
            # last_step_time, not the running average: each trial arm must
            # be scored on its own steps
            self.tuning.on_train_step(
                self.global_steps,
                step_time_s=self.tput_timer.last_step_time or None)
        if self.config.steps_per_print and \
                self.global_steps % self.config.steps_per_print == 0:
            log_dist(f"step={self.global_steps} loss={float(out.loss):.4f} "
                     f"lr={float(out.lr):.3e} gnorm={float(out.grad_norm):.3f} "
                     f"scale={float(out.loss_scale):.0f}")
        if self.watchdog is not None:
            self.watchdog.observe(self, out)
        if self.integrity is not None:
            self.integrity.on_step(self, out)
        return out

    # ------------------------------------------------------------------ #
    # forward/backward/step shims (DeepSpeedEngine API parity)
    # ------------------------------------------------------------------ #
    def forward(self, batch):
        """Compute loss for one micro-batch (staging it for backward)."""
        if self._grad_step is None:
            def one_micro(params, b, ls):
                grads, loss, aux = self._grads_one_micro(params, b, ls)
                # staged grads live in the stage's (possibly sharded) layout —
                # the API-parity path must not hold replicated fp32 grads
                return self._constrain_grads(
                    jax.tree.map(lambda g: g.astype(jnp.float32), grads)), loss, aux

            self._grad_step = self._jit("grad_step", one_micro)
        if self.watchdog is not None and not self._staged_batches:
            # first micro-batch of a GAS window: start the stall clock that
            # the boundary step()'s observe() reads
            self.watchdog.step_started()
        self._staged_batches.append(self._shard_batch(batch, with_gas_dim=False))
        if self.wall_clock_breakdown():
            self.timers(FORWARD_MICRO_TIMER).start(sync=True)
        with self.telemetry.tracer.span("train/fwd_micro", cat="train"):
            grads, loss, aux = self._grad_step(self.state.params,
                                               self._staged_batches[-1],
                                               self.state.loss_scale)
        if self.wall_clock_breakdown():
            self.timers(FORWARD_MICRO_TIMER).stop(sync=True)
        self._last_micro = (grads, loss)
        return loss

    def backward(self, loss=None):
        """Accumulate the staged micro-batch's grads (already computed in
        forward — JAX computes loss+grads together)."""
        grads, loss_val = self._last_micro
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_MICRO_TIMER).start()
        if getattr(self, "_pending_grads", None) is None:
            self._pending_grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
            self._pending_loss = loss_val
            self._pending_count = 1
        else:
            self._pending_grads = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), self._pending_grads, grads)
            self._pending_loss = self._pending_loss + loss_val
            self._pending_count += 1
        self.micro_steps += 1
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_MICRO_TIMER).stop(sync=True)
        return loss_val

    def is_gradient_accumulation_boundary(self) -> bool:
        return getattr(self, "_pending_count", 0) >= self.gradient_accumulation_steps()

    def step(self):
        """Apply the optimizer step at the GAS boundary (no-op otherwise,
        matching reference semantics)."""
        if not self.is_gradient_accumulation_boundary():
            return None
        self._ensure_apply_step()
        breakdown = self.wall_clock_breakdown()
        if breakdown:
            self.timers(STEP_MICRO_TIMER).start()
            self.timers(STEP_GLOBAL_TIMER).start()
        n = self._pending_count
        grads = jax.tree.map(lambda g: g / n, self._pending_grads)
        loss = self._pending_loss / n
        with self.telemetry.tracer.span("train/step", cat="train",
                                        step=self.global_steps + 1):
            self.state, out = self._apply_step(self.state, grads, loss,
                                               self._lr_override)
        self._pending_grads = None
        self._pending_loss = None
        self._pending_count = 0
        self._staged_batches.clear()
        self.global_steps += 1
        self._last_grad_norm = out.grad_norm
        if breakdown:
            self.timers(STEP_MICRO_TIMER).stop(sync=True)
            self.timers(STEP_GLOBAL_TIMER).stop(sync=False)
        # commit any in-flight async checkpoint at the boundary (reference
        # decoupled-engine commit, runtime/engine.py:2797)
        ce = getattr(self, "checkpoint_engine", None)
        if ce is not None and getattr(ce, "_pending", None):
            ce.wait_all()
        self._write_monitor_events(out)
        self.telemetry.step_end(self.global_steps)
        if self.watchdog is not None:
            self.watchdog.observe(self, out)
        return out

    def _write_monitor_events(self, out) -> None:
        """Train/Samples/* scalars per step (reference engine.py:2825-2847)."""
        mon = getattr(self, "monitor", None)
        if mon is None or not mon.enabled:
            return
        events = [("Train/Samples/train_loss", float(out.loss),
                   self.global_steps),
                  ("Train/Samples/lr", float(out.lr), self.global_steps)]
        if self.config.fp16.enabled:
            events.append(("Train/Samples/loss_scale", float(out.loss_scale),
                           self.global_steps))
        if out.grad_norm is not None:
            events.append(("Train/Samples/grad_norm", float(out.grad_norm),
                           self.global_steps))
        mon.write_events(events)

    # ------------------------------------------------------------------ #
    # eval / inference forward
    # ------------------------------------------------------------------ #
    def eval_batch(self, batch):
        if not hasattr(self, "_eval_step") or self._eval_step is None:
            self._eval_step = self._jit(
                "eval_step", lambda p, b: self._loss(p, b)[0])
        batch = self._shard_batch(batch, with_gas_dim=False)
        breakdown = self.wall_clock_breakdown()
        with self.telemetry.tracer.span("train/eval_batch", cat="train"):
            if breakdown:
                self.timers("eval_batch").start(sync=True)
            loss = self._eval_step(self.state.params, batch)
            if breakdown:
                self.timers("eval_batch").stop(sync=True)
        return loss

    def __call__(self, batch):
        return self.forward(batch)

    # ------------------------------------------------------------------ #
    # compile / no_sync (reference engine.compile :4444, no_sync :2518)
    # ------------------------------------------------------------------ #
    def compile(self, example_batch=None, backend: Optional[str] = None,
                **kw) -> "DeepSpeedTPUEngine":
        """Reference ``engine.compile()`` enables torch.compile + DeepCompile
        graph passes; here the train step is ALREADY one compiled XLA program,
        so compile() AOT-lowers it for the example batch shape (warms the
        cache so the first train_batch doesn't pay compile latency) and logs
        the compiler's cost analysis."""
        if self._train_step is None:
            self._build_train_step()
        if example_batch is not None:
            if self.curriculum_scheduler is not None:
                # warm the shape train_batch will actually run first
                example_batch = self.curriculum_scheduler.truncate(
                    example_batch, self.global_steps)
            batch = self._shard_batch(example_batch, with_gas_dim=True)
            def lower():
                return self._train_step.lower(
                    self.state, batch, self._lr_override).compile()

            compiled = self._first_lowering(batch, lower) \
                if self._remat_for(batch) else lower()
            cost = compiled.cost_analysis() or {}
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else {}
            log_dist(f"engine.compile: AOT-compiled train step "
                     f"(flops={cost.get('flops', 0):.3e}, "
                     f"bytes={cost.get('bytes accessed', 0):.3e})")
            flops = float(cost.get("flops", 0.0))
            if flops > 0:  # free TFLOPS baseline — the analysis is in hand
                self.tput_timer.set_flops_per_step(flops)
                self._flops_estimated = True
        self._is_compiled = True
        return self

    @property
    def is_compiled(self) -> bool:
        return getattr(self, "_is_compiled", False) or self._train_step is not None

    @contextlib.contextmanager
    def no_sync(self):
        """Reference ``no_sync`` (:2518) disables grad allreduce between
        accumulation steps. Here accumulation is already local —
        forward/backward stage grads without collectives, which only fire in
        the fused step at the boundary — so this is a semantic no-op provided
        for API parity."""
        yield

    # ------------------------------------------------------------------ #
    # dataloader (deepspeed_io parity, runtime/engine.py:2147)
    # ------------------------------------------------------------------ #
    def deepspeed_io(self, dataset, batch_size: Optional[int] = None):
        from .dataloader import DeepSpeedTPUDataLoader

        return DeepSpeedTPUDataLoader(
            dataset,
            batch_size=batch_size or self.train_batch_size(),
            mesh_mgr=self.mesh_mgr)

    # ------------------------------------------------------------------ #
    # checkpointing (full impl in runtime/checkpoint/)
    # ------------------------------------------------------------------ #
    def save_checkpoint(self, save_dir: str, tag: Optional[str] = None,
                        client_state: Optional[dict] = None, **kw):
        from .checkpoint.saver import save_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state or {})

    def load_checkpoint(self, load_dir: str, tag: Optional[str] = None, **kw):
        from .checkpoint.saver import load_checkpoint as _load

        return _load(self, load_dir, tag=tag)

    # --- universal checkpoint v2: elastic, topology-free save/load
    # (runtime/checkpoint/universal.py; docs/reliability.md "Elastic
    # training & universal checkpoint") ---
    def save_universal_checkpoint(self, save_dir: str,
                                  tag: Optional[str] = None,
                                  client_state: Optional[dict] = None,
                                  reason: Optional[str] = None) -> str:
        from .checkpoint.universal import save_universal_checkpoint as _save

        return _save(self, save_dir, tag=tag, client_state=client_state,
                     reason=reason)

    def load_universal_checkpoint(self, load_dir: str,
                                  tag: Optional[str] = None):
        from .checkpoint.universal import load_universal_checkpoint as _load

        return _load(self, load_dir, tag=tag)

    # ------------------------------------------------------------------ #
    # state offload (reference runtime/engine.py:4533 offload_states)
    # ------------------------------------------------------------------ #
    def offload_states(self, include=None, device: str = "cpu",
                       pin_memory: bool = True, non_blocking: bool = False):
        from .offload_states import offload_engine_states

        offload_engine_states(self, include=include, device=device,
                              pin_memory=pin_memory, non_blocking=non_blocking)

    def reload_states(self, non_blocking: bool = False):
        from .offload_states import reload_engine_states

        reload_engine_states(self, non_blocking=non_blocking)

    # ------------------------------------------------------------------ #
    # shutdown (reference engine.destroy :390)
    # ------------------------------------------------------------------ #
    def destroy(self) -> None:
        """Release observability resources: drain pending async checkpoint
        writers (process exit must never truncate an in-flight save), stop
        any live profiler trace, flush + close monitor backends (so partial
        CSV/JSONL rows land on disk). Safe to call more than once; atexit
        backstops it."""
        ce = getattr(self, "checkpoint_engine", None)
        if ce is not None and hasattr(ce, "wait_all"):
            try:
                ce.wait_all()
            except Exception as e:
                # a failed background save must not mask the shutdown path —
                # log it (the checkpoint was never published, so 'latest'
                # still points at the previous good tag)
                logger.error(f"async checkpoint write failed during "
                             f"shutdown: {e}")
        tel = getattr(self, "telemetry", None)
        if tel is not None:
            tel.close()
        store = getattr(self, "tiered_store", None)
        if store is not None:
            store.close()
        mon = getattr(self, "monitor", None)
        if mon is not None:
            mon.close()


# --------------------------------------------------------------------------- #
# initialize() — reference deepspeed/__init__.py:80
# --------------------------------------------------------------------------- #
def initialize(args=None, model: Optional[ModelSpec] = None, optimizer=None,
               model_parameters=None, training_data=None, lr_scheduler=None,
               config=None, config_params=None, mesh_mgr: Optional[MeshManager] = None,
               rng: Optional[jax.Array] = None, dist_init_required: bool = True,
               devices=None, **kwargs):
    """Returns ``(engine, optimizer, training_dataloader, lr_scheduler)`` —
    the reference's 4-tuple.

    ``devices``: build the mesh over this device subset instead of every
    visible device — the elastic runtime (``elasticity/run_elastic``) uses
    it to bring an engine up at a REDUCED chip count after capacity loss."""
    if config is None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if model is None:
        raise ValueError("model (ModelSpec) is required")
    hf_model = None
    if not isinstance(model, ModelSpec):
        # reference UX: deepspeed.initialize(model=<HF transformers model>)
        # — import the weights and route to the family's ModelSpec
        # (conversion is deferred until the config is parsed so the family
        # closures compute in the configured precision, not a default)
        from ..models.hf_import import is_hf_model

        if is_hf_model(model):
            hf_model = model
        else:
            raise TypeError(f"model must be a ModelSpec or a transformers "
                            f"model, got {type(model)}")

    if dist_init_required:
        dist.init_distributed()

    devices = list(devices) if devices is not None else None
    n_devices = len(devices) if devices is not None else \
        (mesh_mgr.world_size if mesh_mgr is not None else len(jax.devices()))
    # resolve mesh first so batch math can use the true dp size
    pre = parse_config(config, world_size=n_devices, resolve_batch=False)
    if hf_model is not None:
        from ..models.hf_import import spec_from_hf

        compute_dtype = (jnp.bfloat16 if pre.bf16.enabled else
                         jnp.float16 if pre.fp16.enabled else jnp.float32)
        model = spec_from_hf(hf_model, compute_dtype=compute_dtype)
    axis_sizes = pre.mesh.axis_sizes(n_devices) if pre.raw.get("mesh") else None
    if axis_sizes is None:
        sizes = {"tensor": pre.tensor_parallel.autotp_size or 1,
                 "pipe": pre.pipeline.stages or 1,
                 "seq": pre.sequence_parallel_size or 1,
                 "expert": pre.moe.expert_parallel_size or 1}
        fixed = int(np.prod(list(sizes.values())))
        if n_devices % fixed != 0:
            raise ValueError(f"device count {n_devices} not divisible by {sizes}")
        sizes["data"] = n_devices // fixed
        axis_sizes = sizes
    # MiCS / ZeRO++ hpZ: carve the shard group out of the data axis — ZeRO
    # shards over 'zero_shard' (size G) and replicates over the remaining
    # 'data' groups (reference runtime/zero/mics.py:63, zero_hpz_partition_size)
    mics = max(int(pre.zero_config.mics_shard_size),
               int(pre.zero_config.zero_hpz_partition_size), 1)
    if mics > 1 and int(axis_sizes.get("zero_shard", 1)) == 1:
        data = int(axis_sizes.get("data", 1))
        if data % mics != 0:
            raise ValueError(f"mics/hpz shard size {mics} does not divide "
                             f"data-parallel size {data}")
        axis_sizes["zero_shard"] = mics
        axis_sizes["data"] = data // mics
    if mesh_mgr is None:
        mesh_mgr = init_mesh(axis_sizes, devices)
        if mics > 1 and int(axis_sizes.get("data", 1)) > 1 \
                and not mesh_mgr.dcn_axes:
            # the zero_shard carve models a 2-level topology: 'zero_shard'
            # is the intra-island (ICI) tier, 'data' the cross-island tier —
            # tag it so CommsTelemetry's link-class split can prove which
            # collectives stay inside the island (real multi-slice meshes
            # auto-detect this in MeshManager.create)
            mesh_mgr.set_dcn_axes(("data",))
    dp = int(axis_sizes.get("data", 1)) * int(axis_sizes.get("zero_shard", 1)) \
        * int(axis_sizes.get("expert", 1))
    cfg = parse_config(config, world_size=n_devices, dp_world_size=dp)

    engine = DeepSpeedTPUEngine(model=model, config=cfg, mesh_mgr=mesh_mgr,
                                optimizer=optimizer, lr_schedule=lr_scheduler,
                                training_data=training_data, rng=rng)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler
