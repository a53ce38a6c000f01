"""Activation checkpointing (rematerialization) — TPU-native.

Capability parity with the reference's Megatron-compatible reimplementation
(``deepspeed/runtime/activation_checkpointing/checkpointing.py``, flags at
:42-45: ``PARTITION_ACTIVATIONS``, ``CPU_CHECKPOINT``, ``CONTIGUOUS_CHECKPOINTING``,
``SYNCHRONIZE``, ``PROFILE_TIME``), redesigned for XLA:

- the reference re-runs the forward in backward by stashing inputs (optionally
  partitioned across TP ranks and/or offloaded to CPU) and replaying with a
  tracked RNG state; under ``jax.checkpoint`` the SAME trade is expressed as a
  *policy* — which intermediates to save vs recompute — and XLA schedules the
  recompute; RNG replay is free because JAX RNG is explicit (no state tracker
  needed — ``get_cuda_rng_tracker`` has no analog by design);
- ``partition_activations`` → saved residuals carry their sharding (they are
  already TP/SP-sharded under SPMD; nothing to do at save time);
- ``cpu_checkpointing`` → ``save_and_offload_only_these_names`` /
  ``offload_checkpoint`` policies that park residuals in host memory
  (``memory_kind='pinned_host'``) between forward and backward.

Policies are selected by name from the config block
(``ActivationCheckpointingConfig.policy``) so models stay policy-agnostic.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ...utils.logging import logger

_config: Optional[Any] = None

# names models may attach via jax.ad_checkpoint.checkpoint_name to mark
# offloadable / saveable residuals. The model families EMIT (training
# blocks, all O(batch·seq) — never the O(seq²) attention internals):
#
#   "qkv_proj" — the q/k/v projection outputs (pre-rotary),
#   "attn_mix" — the attention output BEFORE the wo projection (what the
#                wo backward consumes — saving it is what actually spares
#                the attention recompute),
#   "attn_out" — the attention output projection,
#   "mlp_gate"/"mlp_up" — the FFN gate/up projections (pre-activation),
#   "mlp_out" — the FFN down-projection.
#
# A tier-1 lint test pins that every name a registered policy saves is
# actually emitted by the model families, so a model edit cannot silently
# turn a policy into a no-op.
#
# and ONE name a kernel emits, inside its custom_vjp's forward rule:
#
#   "attn_flash" — the Mosaic flash kernel's own residuals
#                  (ops/pallas/flash_attention.py: its output in the
#                  kernel's layout and the log-sum-exp); saved, the backward
#                  does not replay the kernel's forward (2.6 ms of a 248 ms
#                  step for 0.48 GB at Mistral-7B widths, two layers, on the
#                  chip - PERF.md section 6, PR 56). The XLA attention has no
#                  such value: there the name is simply never met.
CHECKPOINT_NAMES = ("residual", "attn_out", "mlp_out", "block_out")
KERNEL_CHECKPOINT_NAMES = ("attn_flash",)
MATMUL_CHECKPOINT_NAMES = ("qkv_proj", "attn_mix", "attn_out",
                           "mlp_gate", "mlp_up", "mlp_out")

# policy name -> the checkpoint names it saves (name-based policies only;
# shared with the schema registry + the model-emission lint test)
POLICY_SAVED_NAMES = {
    "save_names": CHECKPOINT_NAMES,
    "offload": CHECKPOINT_NAMES,
    # break the recompute CHAIN cheaply: with the attention branch output
    # saved, everything downstream of it (the MLP half) recomputes without
    # re-running attention — but attention's own backward still replays it
    "save_attn_out": ("attn_out",),
    # save EVERY big per-layer MXU dot result: the backward recomputes only
    # cheap elementwise work (norms, rotary, silu) plus the one QK^T dot
    # the O(seq²) probs would otherwise cost in memory — the bounded-HBM
    # analog of dots_saveable (which also saves the quadratic scores).
    # Since PR 56 the flash kernel's residuals too, for whoever pinned the
    # name as for the ladder: 6 bytes a (token, head, head_dim) more a layer
    "save_big_matmuls": MATMUL_CHECKPOINT_NAMES + KERNEL_CHECKPOINT_NAMES,
}


def _host_offload_policy(names: Sequence[str]):
    """Save the named residuals, but in host memory — the ``CPU_CHECKPOINT``
    analog: residuals stream to host after forward and back before backward,
    overlapped by XLA's async copy scheduling."""
    cp = jax.checkpoint_policies
    if hasattr(cp, "save_and_offload_only_these_names"):
        return cp.save_and_offload_only_these_names(
            names_which_can_be_saved=[],
            names_which_can_be_offloaded=list(names),
            offload_src="device", offload_dst="pinned_host")
    logger.warning("offload remat policy unavailable; using save-names policy")
    return cp.save_only_these_names(*names)


POLICIES: dict = {}


def _register_policies():
    cp = jax.checkpoint_policies
    POLICIES.update({
        # recompute everything (the reference's default checkpoint() behavior)
        "full": cp.nothing_saveable,
        "none": None,                       # no remat at all
        # save matmul outputs, recompute cheap elementwise — the usual best
        # trade on TPU (MXU results are expensive to recompute, VPU ops cheap)
        "dots_saveable": cp.dots_saveable,
        "dots_with_no_batch_dims": cp.checkpoint_dots_with_no_batch_dims,
        "save_names": cp.save_only_these_names(*CHECKPOINT_NAMES),
        # selective remat (the HBM-vs-step-time middle ground between
        # "full" — the ~8N-flops-accounted-as-6N tax — and "none"): see
        # POLICY_SAVED_NAMES for exactly what each saves and why
        "save_attn_out": cp.save_only_these_names(
            *POLICY_SAVED_NAMES["save_attn_out"]),
        "save_big_matmuls": cp.save_only_these_names(
            *POLICY_SAVED_NAMES["save_big_matmuls"]),
        "offload": _host_offload_policy(CHECKPOINT_NAMES),
        "offload_dots": (cp.offload_dot_with_no_batch_dims("device", "pinned_host")
                         if hasattr(cp, "offload_dot_with_no_batch_dims")
                         else _host_offload_policy(CHECKPOINT_NAMES)),
    })


_register_policies()


def configure(mpu_=None, deepspeed_config=None, partition_activations=None,
              contiguous_checkpointing=None, checkpoint_in_cpu=None,
              synchronize=None, profile=None, choice=None):
    """API-parity shim for the reference's ``configure``
    (``checkpointing.py`` module-level). Stores the config; the knobs map to a
    remat policy choice rather than runtime buffer management.

    ``choice``: an engine that was named no policy publishes this way the
    rung it chose, from the device's memory, for the batch it is about to
    lower or run (a :class:`RematChoice`; its ``rung`` becomes the default
    policy)."""
    global _config
    import types

    src = deepspeed_config
    if src is not None and hasattr(src, "activation_checkpointing"):
        src = src.activation_checkpointing
    # copy into module-local state — never mutate the caller's config object
    cfg = types.SimpleNamespace(
        policy=choice.rung if choice is not None
        else getattr(src, "policy", "full") if src is not None else "full",
        choice=choice,
        cpu_checkpointing=bool(checkpoint_in_cpu
                               or getattr(src, "cpu_checkpointing", False)),
        partition_activations=bool(partition_activations
                                   or getattr(src, "partition_activations",
                                              False)))
    if cfg.cpu_checkpointing:
        cfg.policy = "offload"
    _config = cfg
    return _config


def is_configured() -> bool:
    return _config is not None


def reset():
    """Reference ``reset()`` frees stashed buffers; JAX holds none."""
    global _config
    _config = None


def default_policy() -> str:
    """The process-wide default: the policy the latest engine was named or
    chose (``configure``), ``full`` where none has spoken."""
    return getattr(_config, "policy", "full") if _config else "full"


def last_choice() -> Optional["RematChoice"]:
    """The choice behind the default policy, where an engine made one."""
    return getattr(_config, "choice", None) if _config else None


def get_policy(name: Optional[str] = None):
    """Resolve a policy name (or the configured one) to a jax.checkpoint policy."""
    if name is None:
        name = default_policy()
    if name not in POLICIES:
        raise ValueError(f"unknown remat policy {name!r}; have {sorted(POLICIES)}")
    return POLICIES[name]


def checkpoint(function: Callable, *args, policy: Optional[str] = None,
               prevent_cse: bool = True, static_argnums=()):
    """Reference ``checkpoint(function, *args)``: run ``function`` under
    rematerialization. Returns the function's output; gradients recompute the
    forward according to the selected policy."""
    name = policy or default_policy()
    if name == "none":
        return function(*args)
    wrapped = jax.checkpoint(function, policy=get_policy(name),
                             prevent_cse=prevent_cse,
                             static_argnums=static_argnums)
    # Bare remat executes its body (and the backward's replay) as ONE fused
    # XLA computation, whose scheduling can differ from op-by-op eager
    # dispatch by float-noise; the jit wrapper makes checkpoint() grads
    # match plain jax.grad exactly, eagerly and under autodiff traces, and
    # is a semantic no-op (inlined pjit) under an outer jit.
    wrapped = jax.jit(wrapped, static_argnums=static_argnums)
    return wrapped(*args)


def checkpoint_wrapper(function: Callable, policy: Optional[str] = None,
                       static_argnums=()) -> Callable:
    """Decorator form: wrap a layer-apply fn once, call many times (plays well
    with ``lax.scan`` over stacked layers)."""
    name = policy or default_policy()
    if name == "none":
        return function
    return jax.checkpoint(function, policy=get_policy(name),
                          static_argnums=static_argnums)


def remat_block(block: Callable, remat_policy: str = "none") -> Callable:
    """A model family's layer block under the policy its config names
    (``remat: true`` on a family config; the families spell an unnamed
    policy ``"none"`` and ``dots_saveable`` ``"dots"``). With none named the
    block takes the process-wide default: the policy the engine's config
    names, else the rung the engine chose from the device's memory
    (:func:`choose_rung`), else ``full`` - so ``remat: true`` alone means
    "recompute what does not fit", not "recompute everything"."""
    name = {"none": None, "dots": "dots_saveable"}.get(remat_policy,
                                                      remat_policy)
    return checkpoint_wrapper(block, policy=name)


class CheckpointFunction:
    """Name-parity shim for the reference's autograd.Function
    (``checkpointing.py CheckpointFunction``)."""

    @staticmethod
    def apply(run_function, *args):
        return checkpoint(run_function, *args)


def saved_bytes(function: Callable, *args,
                policy: Optional[str] = None) -> int:
    """Total bytes of NON-ARGUMENT residuals the backward of ``function``
    keeps alive under the named ``policy`` — the trace-time, exact
    measurement behind the HBM-vs-step-time sweep (``bench.py`` remat sweep,
    ``Train/remat/saved_bytes_<policy>`` telemetry) and the policy-ordering
    tests: ``none`` (no remat) saves every needed intermediate,
    ``save_big_matmuls`` ⊇ ``save_attn_out``, ``full`` saves nothing.

    ``policy=None``/``"none"`` measures the un-rematerialized function."""
    # the installed jax (0.9.0) has no public spelling of this introspection
    from jax._src.ad_checkpoint import saved_residuals

    wrapped = function
    if policy not in (None, "none"):
        wrapped = jax.checkpoint(function, policy=get_policy(policy))
    total = 0
    for aval, desc in saved_residuals(wrapped, *args):
        if "argument" in desc:
            continue  # inputs are resident either way
        n = 1
        for d in aval.shape:
            n *= int(d)
        total += n * aval.dtype.itemsize
    return total


# --------------------------------------------------------------------------- #
# which residuals a rematerialized layer scan keeps, chosen from the memory
# the step has (the engine asks before it first lowers its step for a batch
# signature, when the user asked for rematerialization and named no policy)
# --------------------------------------------------------------------------- #
# Richest first, ending in ``full``. An on-chip A/B at Mistral-7B widths
# (4 x 2048 tokens, two layers, one v5e chip; PERF.md section 6, PR 56)
# fixed the rungs - a policy stands here only where it bought step time for
# its bytes: ``save_big_matmuls`` 246 ms at 14.4 GB against ``full``'s 267
# at 12.7; ``none`` (no rematerialization) was SLOWER than it at 15.8 GB
# (257 ms) and ``save_attn_out`` no faster than ``full`` (269 ms), so
# neither is a rung.
LADDER: Tuple[str, ...] = ("save_big_matmuls", "full")


@dataclasses.dataclass(frozen=True)
class RematProbe:
    """What a model family shows the chooser: the layer block its scan
    rematerializes, as a function of abstract arguments at ONE device's
    shapes (``ModelSpec.remat_probe``). ``block_args[0]`` is the residual
    stream the scan carries, the rest one layer's weights and constants."""

    block: Callable
    block_args: tuple
    layers: int


@dataclasses.dataclass
class RematChoice:
    """The choice for one batch signature, as the engine's telemetry shows
    it (``Train/remat/*``). Bytes are one device's. ``compiled_peak_bytes``
    is 0 until the compile monitor has the step's ``memory_analysis()``."""

    rung: str = "full"
    kept_bytes: Dict[str, int] = dataclasses.field(default_factory=dict)
    #                     rung -> bytes it keeps beyond full; empty where the
    #                     head-room was spent before any rung was traced
    limit_bytes: int = 0                # the allocator's limit; 0: none known
    held_bytes: int = 0                 # what the device holds already
    step_bytes: int = 0                 # the full-remat step beyond that
    margin_bytes: int = 0
    compiled_peak_bytes: int = 0
    fallbacks: int = 0

    @property
    def headroom_bytes(self) -> Optional[int]:
        """Bytes a rung may keep: the limit, less what the device holds,
        less what the full-remat step needs beyond that, less the margin.
        ``None`` where the device reports no limit."""
        if not self.limit_bytes:
            return None
        return self.limit_bytes - self.held_bytes - self.step_bytes \
            - self.margin_bytes

    @property
    def predicted_peak_bytes(self) -> int:
        return self.held_bytes + self.step_bytes \
            + self.kept_bytes.get(self.rung, 0)


def nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def device_bytes(tree, shardings=None, dtype=None) -> int:
    """Bytes ONE device holds of ``tree`` (arrays or shapes with a
    sharding) laid out by ``shardings`` (default: their own), floating
    leaves counted in ``dtype`` where one is given."""
    if shardings is None:
        shardings = jax.tree.map(lambda x: x.sharding, tree)
    total = 0
    for x, sh in zip(jax.tree.leaves(tree), jax.tree.leaves(shardings)):
        as_dtype = dtype if dtype is not None and \
            jnp.issubdtype(x.dtype, jnp.floating) else x.dtype
        total += math.prod(sh.shard_shape(tuple(x.shape))) \
            * jnp.dtype(as_dtype).itemsize
    return total


def choose_rung(kept: Mapping[str, int], headroom: Optional[float]) -> str:
    """The richest rung of ``LADDER`` whose kept bytes fit ``headroom``;
    ``full`` when none does or when no head-room is known. A pure function
    of numbers."""
    if headroom is not None:
        for rung in LADDER:
            if rung in kept and kept[rung] <= headroom:
                return rung
    return "full"


def choose(probe: RematProbe, params, param_shardings, compute_dtype,
           limit_bytes: int, held_bytes: int, gathers_at_use: bool = False,
           accumulator_shardings=None) -> RematChoice:
    """The rung for one step, on a mesh that shards activations over the
    batch alone. ``limit_bytes``: the device allocator's limit (0: none
    reported - the rung is ``full`` and nothing is traced); ``held_bytes``:
    what the device holds before the step.

    What a full-remat step needs beyond that (``step_bytes``), from shapes:
    the compute-dtype copy of ``params`` in its layout; where that layout is
    sharded and gathered at use (ZeRO-3 over devices) one layer's weights
    whole; the gradients as backward leaves them (all of them, whole, in
    the compute dtype); the fp32 accumulator under gradient accumulation
    (``accumulator_shardings``); the residual stream the scan carries; and
    one layer's replay - every residual of the traced block, an upper bound
    on what XLA keeps of them. The loss head's working set is live when no
    layer's replay is and is the smaller of the two wherever this was
    measured (PERF.md section 6, PR 56), so it is not counted. The margin is
    1/32 of the limit.

    At most one small trace a rung and one for the replay, never the whole
    step - and none once the head-room is spent: a step that has no room
    for a rung pays for no trace of it."""
    limit = int(limit_bytes)
    choice = RematChoice(limit_bytes=limit, held_bytes=int(held_bytes),
                         margin_bytes=limit // 32)
    if not limit:
        return choice
    choice.step_bytes = (
        device_bytes(params, param_shardings, compute_dtype)
        + (nbytes(probe.block_args[1:]) if gathers_at_use else 0)
        + sum(x.size for x in jax.tree.leaves(params))
        * jnp.dtype(compute_dtype).itemsize
        + (device_bytes(params, accumulator_shardings, jnp.float32)
           if accumulator_shardings is not None else 0)
        + probe.layers * nbytes(probe.block_args[0]))
    if choice.headroom_bytes > 0:
        choice.step_bytes += saved_bytes(probe.block, *probe.block_args)
    if choice.headroom_bytes > 0:
        # exact for a policy that saves by name
        choice.kept_bytes = {
            rung: probe.layers * saved_bytes(probe.block, *probe.block_args,
                                             policy=rung)
            for rung in LADDER[:-1]}
    choice.rung = choose_rung(choice.kept_bytes, choice.headroom_bytes)
    return choice


def model_parallel_cuda_manual_seed(seed: int):
    """Reference RNG tracker entry (``checkpointing.py
    model_parallel_cuda_manual_seed``): JAX threads PRNG keys explicitly, so a
    global tracker is unnecessary; kept for API parity — returns a key."""
    return jax.random.PRNGKey(seed)
