"""Inference engine v1: TP-sharded, KV-cached generation.

Reference parity: ``InferenceEngine`` (``inference/engine.py:40``) and
``deepspeed.init_inference`` (``deepspeed/__init__.py:313``). TPU-first
redesign:

- AutoTP (``module_inject/auto_tp.py`` graph parsing + Linear swapping)
  becomes a rule lookup: model families publish logical axis names per param
  and the shared ``Partitioner`` maps heads/mlp/vocab dims onto the 'tensor'
  mesh axis. No module surgery, no ``LinearAllreduce`` — XLA inserts the
  collectives the sharding implies.
- Kernel injection (``replace_transformer_layer``) is the op registry's
  backend choice; fused decode comes from jit, not hand-fused modules.
- CUDA-graph capture (``_create_cuda_graph`` ``inference/engine.py:496``)
  is jit compilation caching — shape-stable prefill buckets + a fixed decode
  shape mean each graph compiles once.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..comm.mesh import MeshManager, get_mesh, init_mesh, set_mesh
from ..runtime.partitioning import Partitioner
from ..telemetry.trace import Tracer
from ..utils.logging import log_dist
from .config import InferenceConfig
from .sampling import SamplingParams, sample


@dataclasses.dataclass
class ModelFamily:
    """What the engine needs from a model family: pure functions over a param
    pytree (the counterpart of passing an ``nn.Module`` + injection policy).
    The paged forward a serving engine calls, ``apply_paged``, takes ``rows=``
    (the rows whose logits the program reads) and a mixed call
    (``models/_paged.py MixedCall``) in every family."""

    cfg: Any
    apply_fn: Callable  # (cfg, params, tokens) -> logits
    apply_cached: Callable  # (cfg, params, tokens, cache, cache_len) -> (logits, cache)
    init_cache: Callable  # (cfg, batch, max_len) -> cache pytree
    param_logical_axes: Callable
    cache_logical_axes: Optional[Callable] = None
    name: str = "model"
    # (cfg, rows) -> the MoE layer's static row counts for a serving call of
    # ``rows`` tokens; None for a dense family (models/mixtral.py moe_rows)
    moe_rows: Optional[Callable] = None
    # (cfg) -> bytes of recurrent state ONE sequence slot holds;
    # a family that has it declares recurrent state: its paged cache carries
    # per-slot leaves (named in ``state_leaves``) - beside its block pools
    # (models/granite_hybrid.py) or, where its cache has no leaf with a
    # block axis, alone (models/brumby.py) - and its ``apply_paged`` takes
    # each row's ``slots``
    state_slot_bytes: Optional[Callable] = None
    state_leaves: Tuple[str, ...] = ()
    # (cfg, rows, chunk_rows) -> span arguments a step's launch says of ONE
    # recurrent layer of its call beside ``ssm_rows`` / ``ssm_tokens``: the
    # live single-token rows and the rows of the chunk riding with them,
    # under the family's own names (models/brumby.py ``retention_rows``)
    state_rows: Optional[Callable] = None
    # (cfg, contexts) -> what ONE layer's learned token selection does for
    # rows at those contexts (``sparse_rows``, ``sparse_ctx_scored``,
    # ``sparse_kv_selected``), {} for a family without one; a family that
    # has one keeps a THIRD block pool, the index keys' (models/mixtral.py)
    sparse_rows: Optional[Callable] = None
    # (cfg) -> {kind: window} for a family whose stack has sliding-window
    # layers with a KV pool of their own kind beside the full-attention
    # layers' (``inference.ragged.WindowKind``; models/cohere2_moe.py):
    # its ``init_paged_cache`` takes ``window_blocks`` and its
    # ``apply_paged`` a block table of one segment a kind
    window_kinds: Optional[Callable] = None
    # (cfg) -> {"key_width", "value_width"} for a family whose ONE kind of
    # KV state is a latent pool (MLA; models/axk1.py): one row a token a
    # layer, ``cache["latent"]``, the row's first ``value_width`` numbers
    # its values; its ``apply_paged`` attends in the absorbed form
    latent_kind: Optional[Callable] = None
    # parameter leaves (by their own key) the engine keeps in the type they
    # come in where it casts the rest to its own: a router published in
    # float32 (models/nemotron_h.py ``FLOAT32_PARAMS``)
    float32_params: Tuple[str, ...] = ()

    @classmethod
    def from_module(cls, module, cfg) -> "ModelFamily":
        def apply_logits(*a, **kw):
            out = module.apply(*a, **kw)
            # MoE families return (logits, aux_loss); inference wants logits
            return out[0] if isinstance(out, tuple) else out

        return cls(cfg=cfg, apply_fn=apply_logits,
                   apply_cached=module.apply_cached,
                   init_cache=module.init_cache,
                   param_logical_axes=module.param_logical_axes,
                   cache_logical_axes=getattr(module, "cache_logical_axes", None),
                   name=getattr(module, "__name__", "model").rsplit(".", 1)[-1],
                   moe_rows=getattr(module, "moe_rows", None),
                   state_slot_bytes=getattr(module, "state_slot_bytes", None),
                   state_leaves=tuple(getattr(module, "STATE_LEAVES", ())),
                   state_rows=getattr(module, "state_rows", None),
                   sparse_rows=getattr(module, "sparse_rows", None),
                   window_kinds=getattr(module, "window_kinds", None),
                   latent_kind=getattr(module, "latent_kind", None),
                   float32_params=tuple(getattr(module, "FLOAT32_PARAMS",
                                                ())))


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


class InferenceEngine:
    """Construct via :func:`init_inference`."""

    def __init__(self, family: ModelFamily, params: Any,
                 config: Optional[InferenceConfig] = None,
                 mesh_mgr: Optional[MeshManager] = None,
                 tracer: Optional[Tracer] = None):
        self.family = family
        self.config = config or InferenceConfig()
        self.dtype = jnp.dtype(self.config.dtype)
        # spans (telemetry/trace.py): the ring is governed by the config's
        # ``trace`` block (default OFF); the same spans reach the profiler's
        # timeline whenever a profiler session is running
        self.tracer = tracer if tracer is not None else Tracer(
            getattr(self.config, "trace", None), name="serving",
            annotate=jax.profiler.TraceAnnotation)
        self._generate_cache: Dict[Tuple, Callable] = {}

        # --- mesh / TP group (reference _create_model_parallel_group :247) ---
        if mesh_mgr is None:
            from ..comm import mesh as mesh_lib

            tp = self.config.tensor_parallel.tp_size
            existing = mesh_lib._global_mesh
            if existing is not None and (tp == 1 or existing.tp_world_size == tp):
                mesh_mgr = existing
            else:
                n = len(jax.devices())
                if tp > n or n % tp:
                    raise ValueError(f"tp_size {tp} incompatible with {n} devices")
                mesh_mgr = init_mesh({"tensor": tp, "data": n // tp})
        self.mesh_mgr = mesh_mgr
        set_mesh(mesh_mgr)

        # --- shard params over 'tensor' (AutoTP equivalent) ---
        self.partitioner = Partitioner(mesh_mgr, zero_stage=0)
        axes = family.param_logical_axes(family.cfg)
        specs = self.partitioner.param_specs(axes, jax.tree.map(jnp.shape, params))
        self.param_shardings = self.partitioner.shardings(specs)
        abstract = all(isinstance(l, jax.ShapeDtypeStruct)
                       for l in jax.tree.leaves(params))
        self._quantized = self.config.quant.enabled
        if abstract:
            # caller supplies real weights later (hybrid engine sync path) —
            # avoids a host round-trip + throwaway HBM copy at construction
            self.params = None
        elif self._quantized:
            # weight-only quantization (reference inference/quantization
            # INT8/INT4): weights REST in HBM as int8 + per-row fp scales;
            # dequantization happens inside the jitted step (XLA fuses it
            # into the consuming matmul, so the full-precision copy is
            # transient per-use)
            qtree, qshardings = self._quantize_params(
                jax.tree.map(jnp.asarray, params))
            self.params = jax.device_put(qtree, qshardings)
        else:
            from ..utils.tree import cast_floating

            self.params = jax.device_put(
                cast_floating(jax.tree.map(jnp.asarray, params), self.dtype,
                              keep=family.float32_params),
                self.param_shardings)
        log_dist(f"init_inference: {family.name} sharded over "
                 f"tensor={mesh_mgr.tp_world_size} (dtype={self.dtype})")

        self._forward = jax.jit(
            lambda p, t: family.apply_fn(family.cfg, self._dq(p), t))

    # ------------------------------------------------------------------ #
    # weight-only quantization (int8 / packed-int4 / fp8 at rest,
    # dequantize-on-use — reference ``inference/quantization`` INT4/INT8 and
    # ``csrc/fp_quantizer`` float formats)
    # ------------------------------------------------------------------ #
    @staticmethod
    def _is_qleaf(x) -> bool:
        return isinstance(x, dict) and set(x) in ({"q", "scale"},
                                                  {"q4", "scale"},
                                                  {"f8", "scale"})

    def _quantize_params(self, params):
        """≥2-D float leaves → quantized-at-rest forms the consuming matmul
        dequantizes on use (XLA fuses it):

        - bits=8: {'q': int8 (same shape), 'scale': per-row fp32}
        - bits=4: {'q4': uint8 (last dim halved — two nibbles per byte),
                   'scale'} (odd last dims fall back to int8)
        - fp8:    {'f8': float8_e4m3fn (same shape), 'scale': per-row fp32}
        Shardings: 'q'/'f8' reuse the leaf's spec; packed 'q4' too (the
        halved last dim divides the same mesh axes for even splits)."""
        bits = self.config.quant.bits
        use_fp8 = str(getattr(self.config.quant, "dtype", "int")).lower() in \
            ("fp8", "float8", "e4m3")
        qmax = 2 ** (bits - 1) - 1
        flat, treedef = jax.tree_util.tree_flatten(params)
        sflat = jax.tree_util.tree_flatten(self.param_shardings)[0]
        rep = self.mesh_mgr.replicated()
        qleaves, qshard = [], []
        for leaf, sh in zip(flat, sflat):
            if hasattr(leaf, "ndim") and leaf.ndim >= 2 and \
                    jnp.issubdtype(leaf.dtype, jnp.floating):
                if use_fp8:
                    amax = jnp.maximum(jnp.max(jnp.abs(leaf), axis=-1,
                                               keepdims=True), 1e-8)
                    scale = amax / 448.0  # e4m3 max normal
                    f8 = (leaf / scale).astype(jnp.float8_e4m3fn)
                    qleaves.append({"f8": f8,
                                    "scale": scale.astype(jnp.float32)})
                    qshard.append({"f8": sh, "scale": rep})
                    continue
                scale = jnp.maximum(jnp.max(jnp.abs(leaf), axis=-1,
                                            keepdims=True), 1e-8) / qmax
                q = jnp.clip(jnp.round(leaf / scale), -qmax - 1, qmax) \
                    .astype(jnp.int8)
                packed_shape = leaf.shape[:-1] + (leaf.shape[-1] // 2,)
                try:  # packed last dim must still divide the mesh axes
                    sh.shard_shape(packed_shape)
                    pack_ok = leaf.shape[-1] % 2 == 0
                except ValueError:
                    pack_ok = False
                if bits == 4 and pack_ok:
                    lo = q[..., 0::2] & 0xF
                    hi = (q[..., 1::2] & 0xF) << 4
                    packed = (lo | hi).astype(jnp.uint8)
                    qleaves.append({"q4": packed,
                                    "scale": scale.astype(jnp.float32)})
                    qshard.append({"q4": sh, "scale": rep})
                else:
                    qleaves.append({"q": q, "scale": scale.astype(jnp.float32)})
                    qshard.append({"q": sh, "scale": rep})
            else:
                qleaves.append(leaf.astype(self.dtype)
                               if jnp.issubdtype(leaf.dtype, jnp.floating)
                               else leaf)
                qshard.append(sh)
        return (jax.tree_util.tree_unflatten(treedef, qleaves),
                jax.tree_util.tree_unflatten(treedef, qshard))

    def _dq_leaf(self, x):
        if "q" in x:
            return x["q"].astype(self.dtype) * x["scale"].astype(self.dtype)
        if "f8" in x:
            return x["f8"].astype(self.dtype) * x["scale"].astype(self.dtype)
        # packed int4: sign-extend nibbles, re-interleave
        packed = x["q4"]
        lo = (packed & 0xF).astype(jnp.int8)
        hi = (packed >> 4).astype(jnp.int8)
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = jnp.where(hi >= 8, hi - 16, hi)
        q = jnp.stack([lo, hi], axis=-1).reshape(
            packed.shape[:-1] + (2 * packed.shape[-1],))
        return q.astype(self.dtype) * x["scale"].astype(self.dtype)

    def _dq(self, params):
        """Dequantize inside jit (no-op when quantization is off)."""
        if not self._quantized:
            return params
        return jax.tree.map(
            lambda x: self._dq_leaf(x) if self._is_qleaf(x) else x,
            params, is_leaf=self._is_qleaf)

    # ------------------------------------------------------------------ #
    @property
    def module(self):
        return self.family

    def _require_params(self):
        if self.params is None:
            raise RuntimeError(
                "inference engine was built with abstract params (shapes "
                "only) — assign real weights to engine.params before use")

    def forward(self, tokens) -> jnp.ndarray:
        """Full no-cache forward → logits (scoring / perplexity path)."""
        self._require_params()
        return self._forward(self.params, jnp.asarray(tokens))

    __call__ = forward

    # ------------------------------------------------------------------ #
    def _step_fns(self, batch: int, prompt_pad: int, max_len: int,
                  params_s: SamplingParams):
        key = (batch, prompt_pad, max_len, params_s)
        if key in self._generate_cache:
            return self._generate_cache[key]
        fam = self.family

        def prefill(params, tokens, lengths, rng):
            cache = fam.init_cache(fam.cfg, batch, max_len)
            logits, cache = fam.apply_cached(fam.cfg, self._dq(params), tokens,
                                             cache,
                                             jnp.zeros((batch,), jnp.int32))
            # last valid logit per sequence
            last = jnp.take_along_axis(
                logits, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
            tok = sample(rng, last, params_s)
            return tok.astype(jnp.int32), cache

        def decode(params, tok, cache, cache_len, rng):
            logits, cache = fam.apply_cached(fam.cfg, self._dq(params),
                                             tok[:, None], cache, cache_len)
            nxt = sample(rng, logits[:, 0], params_s)
            return nxt.astype(jnp.int32), cache

        def decode_chunk(params, tok, cache, cache_len, rng, finished, eos,
                         n_steps):
            """``n_steps`` decode ticks in one lax.scan — one compiled
            program and ONE host sync per chunk (per-token np.asarray syncs
            dominate decode over a network-attached chip). EOS propagation
            runs in-jit: finished rows keep emitting eos, exactly like the
            old host loop; the caller checks ``finished`` between chunks
            for the early exit."""
            def tick(carry, key_t):
                tok, cache, cache_len, finished = carry
                nxt, cache = decode(params, tok, cache, cache_len, key_t)
                step = jnp.where(finished, eos, nxt)
                finished = finished | (step == eos)
                return (step, cache, cache_len + 1, finished), step

            keys = jax.random.split(rng, n_steps)
            (tok, cache, cache_len, finished), steps = jax.lax.scan(
                tick, (tok, cache, cache_len, finished), keys)
            return steps.T, tok, cache, cache_len, finished  # [b, n_steps]

        fns = (jax.jit(prefill),
               jax.jit(decode_chunk, donate_argnums=(2,),
                       static_argnums=(7,)))
        self._generate_cache[key] = fns
        return fns

    def generate(self, prompts, prompt_lengths=None, max_new_tokens: int = 64,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 ) -> np.ndarray:
        """prompts: [batch, t] int array (right-padded); returns
        [batch, max_new_tokens] generated ids (post-EOS positions hold EOS)."""
        self._require_params()
        prompts = np.asarray(prompts, np.int32)
        b, t = prompts.shape
        if prompt_lengths is None:
            prompt_lengths = np.full((b,), t, np.int32)
        lengths = jnp.asarray(prompt_lengths, jnp.int32)

        pad_t = _round_up(t, self.config.prefill_bucket)
        max_len = pad_t + max_new_tokens
        padded = np.zeros((b, pad_t), np.int32)
        padded[:, :t] = prompts
        sp = SamplingParams(temperature=temperature, top_k=top_k, top_p=top_p,
                            greedy=temperature == 0.0)
        prefill, decode_chunk = self._step_fns(b, pad_t, max_len, sp)

        rng = jax.random.PRNGKey(seed)
        rng, k = jax.random.split(rng)
        with self.tracer.span("generate/prefill", cat="serving"):
            tok, cache = prefill(self.params, jnp.asarray(padded), lengths, k)
        first_tok = tok
        if max_new_tokens <= 1:
            return np.asarray(tok)[:, None]
        # -1 never matches a token id, so "no EOS" needs no separate trace
        eos_val = -1 if eos_token_id is None else int(eos_token_id)
        eos_dev = jnp.int32(eos_val)
        finished = tok == eos_dev  # device op: decode dispatch never waits
        cache_len = lengths
        # chunked quanta: one compiled scan + ONE host sync per CHUNK tokens,
        # with the all-finished early exit checked between chunks (an
        # EOS-at-step-2 batch must not pay for max_new_tokens of decode)
        CHUNK = 32
        outs = []
        remaining = max_new_tokens - 1
        while remaining > 0:
            n = min(CHUNK, remaining)
            rng, k = jax.random.split(rng)
            with self.tracer.span("generate/decode_chunk", cat="serving"):
                steps, tok, cache, cache_len, finished = decode_chunk(
                    self.params, tok, cache, cache_len, k, finished, eos_dev, n)
            outs.append(np.asarray(steps))
            remaining -= n
            if eos_token_id is not None and bool(np.asarray(finished).all()):
                break
        if remaining > 0:  # early exit: pad the tail with EOS on host
            outs.append(np.full((b, remaining), eos_token_id, np.int32))
        return np.concatenate([np.asarray(first_tok)[:, None]] + outs, axis=1)


def init_inference(model=None, config=None, *, family: Optional[ModelFamily] = None,
                   model_cfg=None, params=None, checkpoint: Optional[str] = None,
                   **kwargs) -> InferenceEngine:
    """TPU counterpart of ``deepspeed.init_inference`` (``__init__.py:313``).

    Accepts either a ``ModelFamily`` (via ``family=``) or a model *module*
    (e.g. ``deepspeed_tpu.models.llama``) plus its config and params::

        engine = init_inference(llama, model_cfg=cfg, params=params,
                                config={"tensor_parallel": {"tp_size": 4}})

    ``checkpoint`` loads weights from disk (reference checkpoint loading,
    ``inference/engine.py:303-471``): a directory written by
    ``engine.save_checkpoint`` (pass model module + model_cfg too), or a
    local HF checkpoint directory (family/config inferred from its
    config.json).
    """
    if params is None and checkpoint is not None:
        import os as _os

        if _os.path.exists(_os.path.join(checkpoint, "latest")) or \
                _os.path.exists(_os.path.join(checkpoint, "meta.json")):
            # our engine checkpoint layout
            from ..runtime.checkpoint.saver import read_state_tree, resolve_tag

            if family is None and (model is None or model_cfg is None):
                raise ValueError("engine-checkpoint loading needs the model "
                                 "module and model_cfg= (or family=) "
                                 "alongside checkpoint=")
            tag_dir = checkpoint
            if _os.path.exists(_os.path.join(checkpoint, "latest")):
                tag_dir = _os.path.join(checkpoint,
                                        resolve_tag(checkpoint, None))
            universal = _os.path.join(tag_dir, "universal")
            if _os.path.exists(universal) and model is not None:
                # topology-free path: resharded restore via a shape template.
                # Restored to HOST memory (not replicated HBM — a model that
                # needs TP to fit would OOM before the engine reshards it);
                # the engine device_puts with its real shardings afterwards.
                from functools import partial as _partial

                from ..runtime.checkpoint.universal import load_universal

                shapes = jax.eval_shape(_partial(model.init, model_cfg),
                                        jax.random.PRNGKey(0))
                rep = get_mesh().replicated()
                try:
                    host = rep.with_memory_kind("pinned_host")
                except Exception:  # backend without host memory kinds (CPU)
                    host = rep
                template = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                   sharding=host), shapes)
                params, _, _ = load_universal(universal, template, None)
            elif jax.process_count() > 1:
                raise ValueError(
                    "multi-host init_inference(checkpoint=) needs a "
                    "universal checkpoint (bin/dstpu_to_universal) AND the "
                    "model module + model_cfg for the restore template — "
                    "the raw state tree cannot be reconstituted across "
                    "processes")
            else:
                params = read_state_tree(tag_dir)["params"]
        else:
            # local HF checkpoint directory — one read resolves family,
            # config, and weights (shared loader; falls back to AutoModel
            # for encoder/contrastive families)
            from ..models.hf_import import load_checkpoint_dir_module

            fam, model, model_cfg, params = \
                load_checkpoint_dir_module(checkpoint)
            if not hasattr(model, "apply_cached"):
                raise ValueError(
                    f"family '{fam}' is not generative (no KV-cached "
                    f"decode path) — use its module API directly "
                    f"(e.g. models/{fam}.encode_*) instead of "
                    f"init_inference")
    if isinstance(config, dict) or config is None:
        config = InferenceConfig.from_dict({**(config or {}), **kwargs})
    if family is None and model is not None and model_cfg is None \
            and params is None:
        # reference UX: init_inference(<HF transformers model>) — the
        # kernel-injection entry (``module_inject/replace_module.py:189``):
        # import weights once, route to the family's fused TPU implementation
        from ..models.hf_import import from_hf, is_hf_model, resolve_module

        if is_hf_model(model):
            fam_name = model.config.model_type
            module = resolve_module(fam_name)
            model_cfg, params = from_hf(model, fam_name)
            model = module
    if family is None:
        if model is None or model_cfg is None:
            raise ValueError("pass family= or (model module, model_cfg=) "
                             "or a transformers model")
        family = ModelFamily.from_module(model, model_cfg)
    if params is None:
        raise ValueError("params pytree is required")
    return InferenceEngine(family, params, config)
