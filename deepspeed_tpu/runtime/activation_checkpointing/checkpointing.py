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

import functools
from typing import Any, Callable, Optional, Sequence

import jax

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
CHECKPOINT_NAMES = ("residual", "attn_out", "mlp_out", "block_out")
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
    # analog of dots_saveable (which also saves the quadratic scores)
    "save_big_matmuls": MATMUL_CHECKPOINT_NAMES,
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
              synchronize=None, profile=None):
    """API-parity shim for the reference's ``configure``
    (``checkpointing.py`` module-level). Stores the config; the knobs map to a
    remat policy choice rather than runtime buffer management."""
    global _config
    import types

    src = deepspeed_config
    if src is not None and hasattr(src, "activation_checkpointing"):
        src = src.activation_checkpointing
    # copy into module-local state — never mutate the caller's config object
    cfg = types.SimpleNamespace(
        policy=getattr(src, "policy", "full") if src is not None else "full",
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


def get_policy(name: Optional[str] = None):
    """Resolve a policy name (or the configured one) to a jax.checkpoint policy."""
    if name is None:
        name = getattr(_config, "policy", "full") if _config else "full"
    if name not in POLICIES:
        raise ValueError(f"unknown remat policy {name!r}; have {sorted(POLICIES)}")
    return POLICIES[name]


def checkpoint(function: Callable, *args, policy: Optional[str] = None,
               prevent_cse: bool = True, static_argnums=()):
    """Reference ``checkpoint(function, *args)``: run ``function`` under
    rematerialization. Returns the function's output; gradients recompute the
    forward according to the selected policy."""
    name = policy or (getattr(_config, "policy", "full") if _config else "full")
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
    name = policy or (getattr(_config, "policy", "full") if _config else "full")
    if name == "none":
        return function
    return jax.checkpoint(function, policy=get_policy(name),
                          static_argnums=static_argnums)


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


def model_parallel_cuda_manual_seed(seed: int):
    """Reference RNG tracker entry (``checkpointing.py
    model_parallel_cuda_manual_seed``): JAX threads PRNG keys explicitly, so a
    global tracker is unnecessary; kept for API parity — returns a key."""
    return jax.random.PRNGKey(seed)
