"""Llama-family model, written TPU-first.

Role in the framework: the flagship training/inference model family (the
reference ships llama support via ``module_inject/containers/llama*.py`` and
``inference/v2/model_implementations/llama_v2``; training-side the reference
wraps the HF implementation). Here the model is a *pure function over a param
pytree*:

- layers are **stacked** (leading ``L`` dim) and executed with ``lax.scan`` —
  one trace/compile of a single block regardless of depth, the idiomatic XLA
  form (and the unit pipeline parallelism later splits);
- every param carries **logical axis names** (t5x-style), so tensor/ZeRO/expert
  sharding are rule lookups, not per-model surgery — this is the TPU-native
  replacement for AutoTP's module-graph parsing (``module_inject/auto_tp.py``);
- attention/norm/rotary go through the op registry (Pallas kernel or XLA
  fallback).

Supports GQA, RoPE, SwiGLU, RMSNorm, optional tied embeddings — i.e. Llama 2/3,
Mistral, Qwen dense configs.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import attention
from ._paged import (gather_rows, paged_attention_step, row_positions,
                     scan_layers)
from ._paged import init_paged_pools as _init_paged_pools
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm
from ..ops.rotary import apply_rotary, rope_frequencies

Params = Dict[str, Any]

# checkpoint names this family's TRAINING block attaches (the selective-
# remat saveables) — the tier-1 lint test verifies each appears in the
# traced jaxpr, so a refactor can't silently drop one
CHECKPOINT_NAMES_EMITTED = ("qkv_proj", "attn_mix", "attn_out",
                            "mlp_gate", "mlp_up", "mlp_out")


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    attention_bias: bool = False  # QKV biases (Qwen2; HF attention_bias flag)
    qk_norm: bool = False         # per-head RMSNorm on q/k pre-rotary (Qwen3)
    remat: bool = False          # jax.checkpoint each block
    remat_policy: str = "none"   # none (unnamed: the registry's default - what
    #                              the engine was named or chose) | full |
    #                              dots | any registry policy
    attention_impl: str = "auto"  # auto | xla | ulysses | ring | fpdt | ulysses_fpdt
    fpdt_chunks: int = 4         # query/KV chunk count for the fpdt impls
    fpdt_offload_kv: bool = False  # park K/V in host memory between chunks
    use_pipeline: bool = True    # use the pipe mesh axis when present

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def num_params(self) -> int:
        h, i, v, L = self.hidden_size, self.intermediate_size, self.vocab_size, self.num_layers
        hd = self.head_size
        attn = h * self.num_heads * hd + 2 * h * self.num_kv_heads * hd + self.num_heads * hd * h
        mlp = 3 * h * i
        norms = 2 * h
        embed = v * h * (1 if self.tie_embeddings else 2)
        return L * (attn + mlp + norms) + embed + h

    @classmethod
    def tiny(cls, **kw) -> "LlamaConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
                    rope_theta=10000.0)
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls(vocab_size=128256, hidden_size=4096, intermediate_size=14336,
                   num_layers=32, num_heads=32, num_kv_heads=8, max_seq_len=8192)

    @classmethod
    def mistral_7b(cls) -> "LlamaConfig":
        """Mistral-7B dense config (the reference serves mistral via
        ``inference/v2/model_implementations/mistral``)."""
        return cls(vocab_size=32000, hidden_size=4096, intermediate_size=14336,
                   num_layers=32, num_heads=32, num_kv_heads=8,
                   max_seq_len=8192, rope_theta=10000.0)

    @classmethod
    def qwen2_7b(cls) -> "LlamaConfig":
        """Qwen2-7B dense config (reference ``.../qwen_v2``)."""
        return cls(vocab_size=152064, hidden_size=3584, intermediate_size=18944,
                   num_layers=28, num_heads=28, num_kv_heads=4,
                   max_seq_len=32768, rope_theta=1000000.0)

    @classmethod
    def phi3_mini(cls) -> "LlamaConfig":
        """Phi-3-mini dense config (reference ``.../phi3``)."""
        return cls(vocab_size=32064, hidden_size=3072, intermediate_size=8192,
                   num_layers=32, num_heads=32, num_kv_heads=32,
                   max_seq_len=4096, rope_theta=10000.0)


def init(cfg: LlamaConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Initialize the stacked param pytree."""
    h, hd = cfg.hidden_size, cfg.head_size
    L, nh, nkv, i, v = (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
                        cfg.intermediate_size, cfg.vocab_size)
    keys = jax.random.split(rng, 8)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)).astype(dtype)

    params: Params = {
        "embed": normal(keys[0], (v, h), h),
        "layers": {
            "attn_norm": jnp.ones((L, h), dtype),
            "wq": normal(keys[1], (L, h, nh * hd), h),
            "wk": normal(keys[2], (L, h, nkv * hd), h),
            "wv": normal(keys[3], (L, h, nkv * hd), h),
            "wo": normal(keys[4], (L, nh * hd, h), nh * hd),
            "mlp_norm": jnp.ones((L, h), dtype),
            "w_gate": normal(keys[5], (L, h, i), h),
            "w_up": normal(keys[6], (L, h, i), h),
            "w_down": normal(keys[7], (L, i, h), i),
        },
        "final_norm": jnp.ones((h,), dtype),
    }
    if cfg.attention_bias:
        params["layers"]["bq"] = jnp.zeros((L, nh * hd), dtype)
        params["layers"]["bk"] = jnp.zeros((L, nkv * hd), dtype)
        params["layers"]["bv"] = jnp.zeros((L, nkv * hd), dtype)
    if cfg.qk_norm:
        params["layers"]["q_norm"] = jnp.ones((L, hd), dtype)
        params["layers"]["k_norm"] = jnp.ones((L, hd), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(jax.random.fold_in(rng, 99), (h, v), h)
    return params


def param_logical_axes(cfg: LlamaConfig) -> Params:
    """Logical axis names per param — consumed by the partitioner
    (``runtime/partitioning.py``) to derive mesh shardings. ``None`` marks an
    unsharded dim."""
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
    }
    if cfg.attention_bias:
        axes["layers"]["bq"] = ("layers", "heads")
        axes["layers"]["bk"] = ("layers", "kv_heads")
        axes["layers"]["bv"] = ("layers", "kv_heads")
    if cfg.qk_norm:
        axes["layers"]["q_norm"] = ("layers", None)
        axes["layers"]["k_norm"] = ("layers", None)
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _resolve_attention(cfg: LlamaConfig, in_pipeline: bool = False):
    """Pick the attention path: explicit config wins; 'auto' uses Ulysses when
    the mesh has a seq axis. Ring/Ulysses cannot nest inside the pipeline's
    manual 'pipe' region (nested shard_map / sharding constraints over other
    axes), so that combination is rejected explicitly."""
    impl = cfg.attention_impl
    if in_pipeline and impl in ("ring", "ulysses", "ulysses_fpdt"):
        raise ValueError(
            f"attention_impl='{impl}' cannot run inside pipeline parallelism; "
            "use attention_impl='auto'/'xla' with the pipe axis, or drop the "
            "pipe axis to use sequence parallelism")
    if impl == "ring":
        from ..sequence.ring import ring_attention_spmd

        return ring_attention_spmd
    if impl in ("fpdt", "ulysses_fpdt"):
        # the reference's FPDT composition (fpdt_layer.py:972): chunked
        # flash attention (optionally KV-host-offloaded) as the LOCAL
        # attention, under the Ulysses a2a when a seq axis is present
        from ..sequence.fpdt import fpdt_attention

        chunked = partial(fpdt_attention, chunks=cfg.fpdt_chunks,
                          offload_kv=cfg.fpdt_offload_kv)

        if impl == "fpdt":
            def chunked_plain(q, k, v, causal=True, **kw):
                return chunked(q, k, v, causal=causal)

            return chunked_plain
        from jax.sharding import PartitionSpec as P

        from ..comm.mesh import BATCH_AXES, get_mesh
        from ..sequence.layer import head_shard_axes, ulysses_attention

        def chunked_inner(q, k, v, causal=True, **kw):
            # post-a2a the head dim is sharded per head_shard_axes (the ONE
            # policy, shared with ulysses' to_heads). Run the chunked
            # attention under shard_map over those axes: heads are
            # independent, so each device runs fpdt locally on its head
            # group — and the Pallas kernels never meet the SPMD partitioner
            # (a pallas_call under plain jit with sharded operands forces an
            # involuntary full remat, b/433785288)
            mm = get_mesh()
            sp, tp = mm.axis_size("seq"), mm.axis_size("tensor")
            n = q.shape[-2]
            axes = head_shard_axes(n, sp=sp, tp=tp)
            group = tp * sp if "tensor" in axes else sp
            if n % group != 0:  # uneven heads: ulysses gathered the sequence
                return chunked(q, k, v, causal=causal)
            nkv = k.shape[-2]
            if nkv % group != 0:
                # GQA-narrow KV can't shard over the head group — widen by
                # the SMALLEST factor that aligns (lcm(nkv, group) — the
                # ONE alignment policy, ops.attention.kv_alignment_heads),
                # keeping the host-offload stream as narrow as possible
                # (fpdt fetches narrow; under attention.gqa_native it runs
                # the native kernel on the aligned-narrow K/V directly).
                from ..ops.attention import (gqa_native_active,
                                             kv_alignment_heads, widen_kv)

                target = kv_alignment_heads(nkv, n, group)
                if target == n and gqa_native_active():
                    # misaligned lcm would force FULL q-width — with the
                    # native kernel that widening is pure waste; gather the
                    # sequence instead and keep K/V narrow
                    return chunked(q, k, v, causal=causal)
                k, v = widen_kv(k, v, target)
            spec = P(BATCH_AXES, None, axes, None)
            from ..comm import comm as dist
            return dist.shard_map(
                lambda ql, kl, vl: chunked(ql, kl, vl, causal=causal),
                mesh=mm.mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)(q, k, v)

        def ulysses_fpdt(q, k, v, **kw):
            return ulysses_attention(q, k, v, inner=chunked_inner, **kw)

        return ulysses_fpdt
    if impl == "ulysses" or (impl == "auto" and not in_pipeline):
        from ..comm.mesh import get_mesh

        if get_mesh().sp_world_size > 1:
            from ..sequence.layer import ulysses_attention

            return ulysses_attention
    return attention


def _qkv_proj(cfg: LlamaConfig, y: jnp.ndarray, layer: Params):
    """QKV projections with optional biases (Qwen2 — the reference's qwen_v2
    container maps q/k/v biases explicitly)."""
    b, s, _ = y.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    q, k, v = y @ layer["wq"], y @ layer["wk"], y @ layer["wv"]
    if "bq" in layer:
        q = q + layer["bq"]
        k = k + layer["bk"]
        v = v + layer["bv"]
    # "qkv_proj": the three projection dot results — selective-remat
    # saveables (identity outside a targeting policy)
    q = checkpoint_name(q, "qkv_proj")
    k = checkpoint_name(k, "qkv_proj")
    v = checkpoint_name(v, "qkv_proj")
    q = q.reshape(b, s, nh, hd)
    k = k.reshape(b, s, nkv, hd)
    if "q_norm" in layer:
        # Qwen3: per-head RMSNorm on q/k before rotary
        q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    return q, k, v.reshape(b, s, nkv, hd)


def _residual_sharding():
    """NamedSharding pinning the [batch, seq, hidden] residual stream to its
    canonical layout — batch over the data axes, seq over ('seq', 'tensor'),
    hidden replicated — or None when no TP/SP axis is active.

    This is the Megatron sequence-parallel pattern (Korthikanti et al. 2022):
    with the residual's seq dim sharded over the TENSOR axis, the TP
    row-parallel projections' partial sums REDUCE-SCATTER into seq shards
    (and the column projections all-gather on entry) instead of all-reducing
    into a tensor-replicated residual. Same wire bytes, but the residual,
    norms, and their activations shrink by tp_size, and SPMD never lands the
    residual hidden-sharded (the involuntary full-rematerialization boundary
    observed in the r1 8-device dryrun).  Without the pin, propagation from
    the next layer's ZeRO-sharded weights can reshard the residual
    mid-stream."""
    try:
        from ..comm.mesh import BATCH_AXES, get_mesh

        mm = get_mesh()
        seq_axes = tuple(
            a for a, on in (("seq", mm.sp_world_size > 1),
                            ("tensor", mm.tp_world_size > 1)) if on)
        if seq_axes:
            return mm.sharding(BATCH_AXES, seq_axes)
    except Exception:
        pass
    return None


def _block(cfg: LlamaConfig, x: jnp.ndarray, layer: Params,
           cos: jnp.ndarray, sin: jnp.ndarray,
           positions: Optional[jnp.ndarray],
           attn_fn=attention, res_sharding=None) -> jnp.ndarray:
    """One transformer block. x: [batch, seq, hidden] (compute dtype)."""
    b, s, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size

    def pin(t):
        if res_sharding is None:
            return t
        return lax.with_sharding_constraint(t, res_sharding)

    # the named scopes (norm / attn / ffn) are metadata on the lowered ops:
    # a trace reduction sums device time by them (docs/observability.md)
    with jax.named_scope("norm"):
        y = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):
        q, k, v = _qkv_proj(cfg, y, layer)
        q = apply_rotary(q, cos, sin, positions)
        k = apply_rotary(k, cos, sin, positions)
        # checkpoint names mark the selective-remat saveables (identity
        # outside a jax.checkpoint policy that targets them — see
        # POLICY_SAVED_NAMES in
        # runtime/activation_checkpointing/checkpointing.py): "attn_mix" =
        # the pre-projection attention output (what the wo backward
        # consumes), "attn_out"/"mlp_out" = the residual-branch projections
        attn_out = checkpoint_name(attn_fn(q, k, v, causal=True), "attn_mix")
        x = x + pin(checkpoint_name(
            attn_out.reshape(b, s, nh * hd) @ layer["wo"], "attn_out"))

    with jax.named_scope("norm"):
        y = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    with jax.named_scope("ffn"):
        gate = jax.nn.silu(checkpoint_name(y @ layer["w_gate"], "mlp_gate"))
        up = checkpoint_name(y @ layer["w_up"], "mlp_up")
        x = x + pin(checkpoint_name((gate * up) @ layer["w_down"], "mlp_out"))
    return x


def _head_split(cfg: LlamaConfig, params: Params, x: jnp.ndarray,
                compute_dtype):
    """Final norm + unembed matrix WITHOUT the logits matmul — the
    factorization the tiled fused logits+loss head consumes so [B, S, V]
    is never materialized. ``_head`` composes it back for the dense path."""
    with jax.named_scope("norm"):
        x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                     cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return x, head.astype(compute_dtype)


def _head(cfg: LlamaConfig, params: Params, x: jnp.ndarray, compute_dtype):
    x, head = _head_split(cfg, params, x, compute_dtype)
    with jax.named_scope("logits"):
        return (x @ head).astype(jnp.float32)


def apply(cfg: LlamaConfig, params: Params, tokens: jnp.ndarray, *,
          positions: Optional[jnp.ndarray] = None,
          compute_dtype=jnp.bfloat16, return_hidden: bool = False):
    """Forward pass → logits [batch, seq, vocab] (fp32); with
    ``return_hidden`` → the ``_head_split`` pair (normed hidden, unembed)
    for the tiled loss head instead.

    Layers run under ``lax.scan`` over the stacked leading dim; with
    ``cfg.remat`` each block is wrapped in ``jax.checkpoint`` so the backward
    pass rematerializes activations (the reference's
    ``runtime/activation_checkpointing``)."""
    with jax.named_scope("embed"):
        x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)

    layers = jax.tree.map(lambda p: p.astype(compute_dtype)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p,
                          params["layers"])

    pipe_stages = 1
    if cfg.use_pipeline:
        try:
            from ..comm.mesh import get_mesh

            pipe_stages = get_mesh().pp_world_size
        except Exception:
            pipe_stages = 1

    attn_fn = _resolve_attention(cfg, in_pipeline=pipe_stages > 1)
    # no residual pin inside the pipeline's manual shard_map region (the
    # full-mesh NamedSharding is not addressable from there)
    res_sharding = _residual_sharding() if pipe_stages == 1 else None
    if res_sharding is not None:
        # enter the blocks already in the residual layout so layer 0 doesn't
        # pay a reshard inside the scan
        x = lax.with_sharding_constraint(x, res_sharding)
    block = partial(_block, cfg, attn_fn=attn_fn, res_sharding=res_sharding)
    if cfg.remat:
        # the shared remat-policy registry: the policy the config names,
        # else the one the engine was named or chose (ac.remat_block)
        from ..runtime.activation_checkpointing import checkpointing as ac

        block = ac.remat_block(block, cfg.remat_policy)

    if pipe_stages > 1:
        from ..runtime.pipe import pipeline_apply

        x = pipeline_apply(lambda layer, h: block(h, layer, cos, sin, positions),
                           layers, x)
    else:
        from ..comm import overlap as ov

        def scan_body(x, layer):
            # ZeRO-3: pin the slice to the gathered compute layout
            # (engine-published; identity otherwise) so SPMD can't
            # repartition the fwd+bwd scan into wrong numerics
            return block(x, ov.constrain_scan_slice(layer),
                         cos, sin, positions), None

        if ov.layer_prefetch_active():
            # ZeRO-3 per-layer all-gather prefetch: layer i+1's param shards
            # gather while layer i's matmuls run (engine-configured; same
            # slices in the same order → bit-identical to the plain scan)
            x, _ = ov.prefetch_scan(scan_body, x, layers)
        else:
            x, _ = lax.scan(scan_body, x, layers)
    if return_hidden:
        return _head_split(cfg, params, x, compute_dtype)
    return _head(cfg, params, x, compute_dtype)


# --------------------------------------------------------------------------- #
# KV-cached inference path (reference: inference v1 fused-module decode and
# v2 ``inference/v2/model_implementations/llama_v2`` — here a pure function
# over a stacked cache pytree, scanned per layer)
# --------------------------------------------------------------------------- #
def init_cache(cfg: LlamaConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    """Dense KV cache: [layers, batch, max_len, kv_heads, head_dim]."""
    L, nkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_size
    shape = (L, batch_size, max_len, nkv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: LlamaConfig) -> Params:
    spec = ("layers", None, None, "kv_heads", None)
    return {"k": spec, "v": spec}


def _write_cache(cache: jnp.ndarray, new: jnp.ndarray,
                 starts: jnp.ndarray) -> jnp.ndarray:
    """Scatter new K/V rows into the cache at per-sequence offsets.
    cache [b, S, nkv, hd], new [b, t, nkv, hd], starts [b]."""
    def one(c, n, s):
        return lax.dynamic_update_slice(c, n.astype(c.dtype), (s, 0, 0))

    return jax.vmap(one)(cache, new, starts)


def _block_cached(cfg: LlamaConfig, x: jnp.ndarray, layer: Params,
                  k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                  cache_len: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                  positions: jnp.ndarray):
    """One block with KV-cache read/write. x: [b, t, h]; cache_len: [b]."""
    b, t, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    S = k_cache.shape[1]

    y = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    q, k, v = _qkv_proj(cfg, y, layer)
    q = apply_rotary(q, cos, sin, positions)
    k = apply_rotary(k, cos, sin, positions)
    k_cache = _write_cache(k_cache, k, cache_len)
    v_cache = _write_cache(v_cache, v, cache_len)

    # attend over the cache: kv slot j is visible to query i (absolute
    # position cache_len + i) iff j <= cache_len + i
    kv_pos = jnp.arange(S)[None, None, None, :]
    q_abs = cache_len[:, None, None, None] + jnp.arange(t)[None, None, :, None]
    mask = kv_pos <= q_abs  # [b, 1, t, S]
    attn_out = attention(q, k_cache, v_cache, causal=False, mask=mask)
    x = x + attn_out.reshape(b, t, nh * hd) @ layer["wo"]

    y = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    gate = jax.nn.silu(y @ layer["w_gate"])
    up = y @ layer["w_up"]
    x = x + (gate * up) @ layer["w_down"]
    return x, k_cache, v_cache


def apply_cached(cfg: LlamaConfig, params: Params, tokens: jnp.ndarray,
                 cache: Params, cache_len: jnp.ndarray, *,
                 compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Forward with KV cache (prefill when cache_len==0, decode otherwise).

    tokens [b, t]; cache_len [b] — number of valid cache slots per sequence.
    Returns (logits [b, t, vocab] fp32, updated cache)."""
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
    x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)
    positions = cache_len[:, None] + jnp.arange(tokens.shape[1])[None, :]

    layers = jax.tree.map(lambda p: p.astype(compute_dtype)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p,
                          params["layers"])

    def scan_body(x, scanned):
        layer, k_c, v_c = scanned
        x, k_c, v_c = _block_cached(cfg, x, layer, k_c, v_c, cache_len,
                                    cos, sin, positions)
        return x, (k_c, v_c)

    x, (new_k, new_v) = lax.scan(scan_body, x, (layers, cache["k"], cache["v"]))
    x = rms_norm(x, params["final_norm"].astype(compute_dtype), cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = x @ head.astype(compute_dtype)
    return logits.astype(jnp.float32), {"k": new_k, "v": new_v}


# --------------------------------------------------------------------------- #
# Paged (blocked) KV-cache path — reference: inference v2 blocked attention
# over ``BlockedKVCache`` (``inference/v2/ragged/kv_cache.py``) and the ragged
# decode kernels. Block tables are fixed-width; block 0 is the trash block.
# --------------------------------------------------------------------------- #
def init_paged_cache(cfg: LlamaConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16,
                     kv_quant_group: Optional[int] = None) -> Params:
    # [*, nkv, block_size, hd]: the decode kernel's per-block tile is then
    # (block_size, hd) — legal TPU tiling (second-to-last %8; a squeezed kv
    # head in the last two positions is rejected by the Mosaic lowering).
    # kv_quant_group (inference.kv_quant): int8 code pools + fp32 scale
    # pools instead — see models/_paged.py.
    return _init_paged_pools(cfg.num_layers, num_blocks, cfg.num_kv_heads,
                             block_size, cfg.head_size, dtype,
                             kv_quant_group)



def _block_paged(cfg: LlamaConfig, x: jnp.ndarray, layer: Params,
                 k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                 block_tables: jnp.ndarray, context_lens: jnp.ndarray,
                 valid: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                 positions: jnp.ndarray):
    """One block over the paged cache. x [B, t, h]; block_tables
    [B, max_blocks]; context_lens [B]; valid [B, t] (False → write to trash)."""
    b, t, h = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size

    with jax.named_scope("norm"):
        y = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):   # the pool update inside is "kv_write"
        q, k, v = _qkv_proj(cfg, y, layer)
        q = apply_rotary(q, cos, sin, positions)
        k = apply_rotary(k, cos, sin, positions)
        attn_out, k_cache, v_cache = paged_attention_step(
            q, k, v, k_cache, v_cache, block_tables, context_lens, positions,
            valid)
        x = x + attn_out.reshape(b, t, nh * hd) @ layer["wo"]

    return swiglu_block(cfg, x, layer), k_cache, v_cache


def swiglu_block(cfg, x: jnp.ndarray, layer: Params) -> jnp.ndarray:
    """A served block's second half: ``x + W_down(silu(W_gate n) * W_up n)``,
    ``n = RMSNorm(x)``, under the ``norm`` and ``ffn`` scopes (this family's
    paged block and ``models/brumby.py``'s)."""
    with jax.named_scope("norm"):
        y = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
    with jax.named_scope("ffn"):
        gate = jax.nn.silu(y @ layer["w_gate"])
        up = y @ layer["w_up"]
        return x + (gate * up) @ layer["w_down"]


def apply_paged(cfg: LlamaConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the paged cache (prefill chunks or decode steps).

    tokens [B, t]; context_lens [B] tokens already cached per sequence;
    block_tables [B, max_blocks] into the shared pool; valid [B, t] marks
    real (non-pad) tokens. A mixed call: ``block_tables``
    is a ``_paged.MixedCall``, ``context_lens`` None and tokens
    [1, slots + t] - every slot's decode token, then one prefill chunk.
    ``rows`` [B, r]: the rows along ``t`` whose logits the call reads
    (``_paged.gather_rows``) - the final norm and the head run on those
    alone; None scores every row.
    Returns (logits [B, t, vocab] fp32 - [B, r, vocab] with ``rows`` -,
    cache)."""
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    with jax.named_scope("embed"):
        x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)
    positions = row_positions(block_tables, context_lens, t)

    layers = jax.tree.map(lambda p: p.astype(compute_dtype)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p,
                          params["layers"])

    def scan_body(x, scanned):
        layer, k_c, v_c = scanned
        x, k_c, v_c = _block_paged(cfg, x, layer, k_c, v_c, block_tables,
                                   context_lens, valid, cos, sin, positions)
        return x, (k_c, v_c)

    x, cache = scan_layers(scan_body, x, layers, cache)
    x = gather_rows(x, rows)
    with jax.named_scope("norm"):
        x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                     cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    with jax.named_scope("logits"):
        logits = (x @ head.astype(compute_dtype)).astype(jnp.float32)
    return logits, cache


def model_spec(cfg: LlamaConfig, compute_dtype=jnp.bfloat16):
    """Build the engine-facing ModelSpec for this config."""
    from ..runtime.engine import ModelSpec

    return ModelSpec(
        name="llama",
        init_fn=lambda rng: init(cfg, rng),
        loss_fn=lambda params, batch: loss_fn(cfg, params, batch,
                                              compute_dtype=compute_dtype),
        tiled_loss_fn=lambda params, batch, shards=8: tiled_loss_fn(
            cfg, params, batch, compute_dtype=compute_dtype, shards=shards),
        apply_fn=lambda params, tokens, **kw: apply(cfg, params, tokens,
                                                    compute_dtype=compute_dtype, **kw),
        logical_axes=param_logical_axes(cfg),
        pipeline_capable=cfg.use_pipeline,
        pipeline_grad_fn=(make_pipeline_grad_fn(cfg, compute_dtype)
                          if cfg.use_pipeline else None),
        remat_probe=lambda params, batch: remat_probe(
            cfg, params, batch, compute_dtype=compute_dtype),
    )


def make_pipeline_grad_fn(cfg: LlamaConfig, compute_dtype=jnp.bfloat16):
    """1F1B train-step grads (used by the engine when the mesh has a pipe
    axis ≥ 2). Embedding/norm/head params are shared stage-replicated state;
    their grads reduce over 'pipe' — tied-embedding reduction included."""

    def grad_fn(params: Params, batch: Dict[str, jnp.ndarray],
                loss_scale: Optional[jnp.ndarray] = None):
        from ..runtime.pipe.one_f_one_b import pipeline_value_and_grad

        tokens = batch["tokens"]
        if "labels" in batch:
            inputs, labels = tokens, batch["labels"]
        else:
            inputs, labels = tokens[:, :-1], tokens[:, 1:]
        cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len,
                                    cfg.rope_theta)
        attn_fn = _resolve_attention(cfg, in_pipeline=True)
        scale = 1.0 if loss_scale is None else loss_scale

        # each side carries only the params it reads (zero-grad vocab-sized
        # buffers would otherwise be psum'd over pipe every step); with tied
        # embeddings the head side includes 'embed' and the grad merge below
        # sums the two partials — ReduceTiedGrads
        E_params = {"embed": params["embed"]}
        H_params = {"final_norm": params["final_norm"]}
        if "lm_head" in params:
            H_params["lm_head"] = params["lm_head"]
        else:
            H_params["embed"] = params["embed"]

        def embed_fn(P, toks):
            return embedding_lookup(P["embed"], toks, compute_dtype)

        def block(layer, h):
            layer = jax.tree.map(
                lambda p: p.astype(compute_dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p, layer)
            return _block(cfg, h, layer, cos, sin, None, attn_fn=attn_fn)

        def head_fn(P, h, lab):
            x = rms_norm(h, P["final_norm"].astype(compute_dtype),
                         cfg.rms_norm_eps)
            head = P.get("lm_head")
            head = P["embed"].T if head is None else head
            logits = (x @ head.astype(compute_dtype)).astype(jnp.float32)
            valid = lab != -100
            safe = jnp.where(valid, lab, 0)
            logp = jax.nn.log_softmax(logits, axis=-1)
            tl = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
            # SUM of token losses — the global valid-token mean divides once
            # at the end (a per-micro mean would up-weight short microbatches
            # vs the unpipelined loss_fn). Loss scaling seeds the backward.
            return jnp.where(valid, tl, 0.0).sum() * scale

        loss, grads = pipeline_value_and_grad(
            embed_fn, block, head_fn,
            {"embed": E_params, "layers": params["layers"], "head": H_params},
            inputs, labels)
        # module returns (1/M)*sum_i loss_i and matching grads; rescale both
        # to the global valid-token mean
        from ..comm.mesh import get_mesh

        M = max(get_mesh().pp_world_size, 1)  # module default num_micro = S
        denom = jnp.maximum((labels != -100).sum(), 1).astype(jnp.float32)
        factor = M / denom
        g_merged = dict(grads["embed"])
        for k, v in grads["head"].items():
            g_merged[k] = jax.tree.map(jnp.add, g_merged[k], v) \
                if k in g_merged else v
        out_grads = {k: jax.tree.map(lambda g: g * factor, v)
                     for k, v in g_merged.items()}
        out_grads["layers"] = jax.tree.map(lambda g: g * factor,
                                           grads["layers"])
        loss = loss * factor / scale
        return out_grads, loss, {"loss": loss,
                                 "ntokens": (labels != -100).sum()}

    return grad_fn


def loss_fn(cfg: LlamaConfig, params: Params, batch: Dict[str, jnp.ndarray], *,
            compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """Next-token cross-entropy. batch: {"tokens": [b, s+1]} or
    {"tokens": [b, s], "labels": [b, s]} with -100 = ignore."""
    tokens = batch["tokens"]
    if "labels" in batch:
        inputs, labels = tokens, batch["labels"]
    else:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
    logits = apply(cfg, params, inputs, compute_dtype=compute_dtype)
    with jax.named_scope("loss"):
        valid = labels != -100
        safe_labels = jnp.where(valid, labels, 0)
        logp = jax.nn.log_softmax(logits, axis=-1)
        token_loss = -jnp.take_along_axis(
            logp, safe_labels[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(valid.sum(), 1)
        loss = jnp.where(valid, token_loss, 0.0).sum() / denom
    return loss, {"loss": loss, "ntokens": valid.sum()}


def remat_probe(cfg: LlamaConfig, params: Params,
                batch: Dict[str, jnp.ndarray], compute_dtype=jnp.bfloat16):
    """``ModelSpec.remat_probe``: the block ``apply`` scans, over shapes
    alone - ``params`` the parameter tree's and ``batch`` ONE device's
    micro-batch. ``None`` where this config leaves the engine nothing to
    choose: no rematerialization, or a policy the user named."""
    from ..runtime.activation_checkpointing.checkpointing import RematProbe

    if not cfg.remat or cfg.remat_policy != "none":
        return None
    b, s = batch["tokens"].shape
    if "labels" not in batch:
        s -= 1
    x = jax.ShapeDtypeStruct((b, s, cfg.hidden_size), compute_dtype)
    layer = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(
            p.shape[1:], compute_dtype
            if jnp.issubdtype(p.dtype, jnp.floating) else p.dtype),
        params["layers"])
    cos, sin = jax.eval_shape(partial(
        rope_frequencies, cfg.head_size, cfg.max_seq_len, cfg.rope_theta))
    attn_fn = _resolve_attention(cfg)

    def block(x, layer, cos, sin):
        return _block(cfg, x, layer, cos, sin, None, attn_fn=attn_fn)

    return RematProbe(block=block, block_args=(x, layer, cos, sin),
                      layers=cfg.num_layers)


def tiled_loss_fn(cfg: LlamaConfig, params: Params,
                  batch: Dict[str, jnp.ndarray], *,
                  compute_dtype=jnp.bfloat16, shards: int = 8
                  ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """``loss_fn`` with the unembed matmul + CE fused per sequence tile
    (``sequence.tiled_loss``): the [B, S, V] logits tensor — the first OOM
    at long context — is never materialized; one [B, S/shards, V] tile
    lives at a time inside a rematerialized scan."""
    from ..sequence.tiled import tiled_fused_logits_loss

    tokens = batch["tokens"]
    if "labels" in batch:
        inputs, labels = tokens, batch["labels"]
    else:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
    hidden, head = apply(cfg, params, inputs, compute_dtype=compute_dtype,
                         return_hidden=True)
    with jax.named_scope("logits"):    # unembed matmul and CE, fused per tile
        loss = tiled_fused_logits_loss(hidden, head, labels, shards=shards)
    return loss, {"loss": loss, "ntokens": (labels != -100).sum()}
