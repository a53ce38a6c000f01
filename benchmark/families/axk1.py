"""The ``axk1`` family: A.X-K1's published keys ->
``deepspeed_tpu.models.axk1`` (latent attention over ONE latent pool, YaRN
rope, a leading dense layer, the group-limited scaled sigmoid router, one
chip's share of the expert bank switched on), and the parameter tree -> the
plain reference's weights, read lazily: one matrix or ONE expert cut out of
the stacked tree when it is asked for (the engine holds 13.7 GB while a
probe's reference runs). The program's module is loaded when a cell asks for
it: no other family's set-up pays for it.
"""

from __future__ import annotations

import collections.abc
import dataclasses
import functools
import os
import types

import numpy as np

from .cohere2_moe import _Each, rounded  # noqa: F401  (a lazy sequence of
#   ONE expert's matrices at a time; the precision control's rounding)

REFERENCE = "axk1"
CONFIG_FILE = "a.x-k1.json"
# the one rule of this configuration's random weights that differs from the
# program's own ``init``: the latent's columns of W_dkv are scaled by
# LATENT_GAIN, so that the latent BEFORE its norm has an RMS of 2 and not of
# 1. The program's init makes every projection of a normed input unit-RMS,
# and an RMSNorm with a weight of one over a unit-RMS vector changes it by
# 1 / sqrt(2 x 512) = 3 %: "no norm on the latent" and "the rope key normed
# with the latent" would then read what bf16 reads (the first chip run, PR
# 47: the right form 0.027, the median row). A trained checkpoint's latent
# is not unit-RMS - that is what its norm is for. The right form is unmoved
# but for rounding: the norm takes the gain out again.
LATENT_GAIN = 2.0


def _program():
    try:
        from deepspeed_tpu.models import axk1
    except ImportError:
        from benchmark.harness.manifest import ManifestError

        raise ManifestError(
            "this program has no models/axk1.py: it cannot run the axk1 "
            "family") from None
    return axk1


def module():
    """The program's module with ``init`` below in the place of its own
    (the harness draws a cell's weights by ``module().init``)."""
    return types.SimpleNamespace(**{**vars(_program()), "init": init})


def init(cfg, rng, **kw):
    """The program's ``init`` with the latent's columns of every layer's
    W_dkv at ``LATENT_GAIN``."""
    params = _program().init(cfg, rng, **kw)
    for stack in ("dense_layers", "layers"):
        w = params[stack]["w_dkv"]
        params[stack]["w_dkv"] = w.at[..., :cfg.kv_lora_rank].multiply(
            LATENT_GAIN).astype(w.dtype)
    return params


def build_cfg(hf: dict, **program_options):
    """``num_local_experts`` (= ``n_routed_experts``) is the router's width
    and ``num_experts`` the experts HELD here, both keys this benchmark ADDS
    (the configuration's ``assumed``); ``moe_intermediate_size`` is ONE
    expert's width, ``intermediate_size`` the leading dense layers'."""
    m = _program()
    if hf.get("attention_bias") or hf["tie_word_embeddings"]:
        raise ValueError("models/axk1.py has no attention bias and an "
                         "untied head")
    rope = hf["rope_scaling"]
    if not (hf["scoring_func"] == "sigmoid" and hf["topk_method"] == "none"
            and hf["hidden_act"] == "silu" and hf["moe_layer_freq"] == 1
            and rope["type"] == "yarn" and hf["n_shared_experts"] >= 1
            and hf["n_routed_experts"] == hf["num_local_experts"]):
        raise ValueError("the configuration is not one models/axk1.py runs "
                         "as published")
    if program_options.get("norm_topk_prob", True) != hf["norm_topk_prob"]:
        raise ValueError("the role's program_options and the published "
                         "configuration disagree on norm_topk_prob")
    routed, held = hf["num_local_experts"], hf["num_experts"]
    return dataclasses.replace(
        m.AxK1Config(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        dense_intermediate_size=hf["intermediate_size"],
        intermediate_size=hf["moe_intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        first_k_dense=hf["first_k_dense_replace"],
        num_heads=hf["num_attention_heads"],
        q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"],
        qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"], num_experts=routed,
        top_k=hf["num_experts_per_tok"],
        num_shared_experts=hf["n_shared_experts"],
        n_group=hf["n_group"], topk_group=hf["topk_group"],
        route_scale=float(hf["routed_scaling_factor"]),
        experts_held=None if held == routed
        else (hf.get("experts_first", 0), held),
        max_seq_len=hf["max_position_embeddings"],
        rope_theta=float(hf["rope_theta"]),
        rope_factor=float(rope["factor"]),
        rope_original_max_len=rope["original_max_position_embeddings"],
        rope_beta_fast=float(rope["beta_fast"]),
        rope_beta_slow=float(rope["beta_slow"]),
        rope_mscale=float(rope["mscale"]),
        rope_mscale_all_dim=float(rope["mscale_all_dim"]),
        rms_norm_eps=hf["rms_norm_eps"], **program_options)


class _Layer(collections.abc.Mapping):
    """One layer's weights under the reference's names, each cut out of its
    stacked tree when it is read. A dense layer has ``ffn``, a sparse one
    ``router``, ``experts`` (the HELD ones, as the bank has them) and
    ``shared``."""

    NAMES = {"attn_norm": "attn_norm", "dq": "w_dq", "q_norm": "q_norm",
             "uq": "w_uq", "dkv": "w_dkv", "kv_norm": "kv_norm",
             "ukv": "w_ukv", "o": "wo", "ffn_norm": "ffn_norm"}

    def __init__(self, stack, i: int, dense: bool):
        self._make = {name: functools.partial(lambda leaf: stack[leaf][i],
                                              leaf)
                      for name, leaf in self.NAMES.items()}
        if dense:
            self._make["ffn"] = lambda: (
                stack["w_gate"][i], stack["w_up"][i], stack["w_down"][i])
            return
        moe = stack["moe"]
        width = moe["w_gate"].shape[-1]
        cut = lambda j: slice(j * width, (j + 1) * width)
        self._make.update({
            "router": lambda: moe["router"][i],
            "experts": lambda: _Each(
                moe["w_gate"].shape[1],
                lambda e: (moe["w_gate"][i, e], moe["w_up"][i, e],
                           moe["w_down"][i, e])),
            "shared": lambda: _Each(
                moe["shared_w_gate"].shape[-1] // width,
                lambda j: (moe["shared_w_gate"][i, :, cut(j)],
                           moe["shared_w_up"][i, :, cut(j)],
                           moe["shared_w_down"][i, cut(j)]))})

    def __getitem__(self, name):
        return self._make[name]()

    def __iter__(self):
        return iter(self._make)

    def __len__(self):
        return len(self._make)


class Weights:
    """The program's parameter tree, read one layer at a time under the
    reference's names: layers ``0 .. first_k_dense - 1`` from the dense
    stack, the rest from the sparse one. ``program`` is the program these
    weights are served by, for the reference's comparison beyond the served
    tokens (``reference/axk1.py`` ``held``)."""

    def __init__(self, params, role=None):
        self._dense, self._sparse = params["dense_layers"], params["layers"]
        self.dense_layers = self._dense["attn_norm"].shape[0]
        self.embed = params["embed"]
        self.final_norm = params["final_norm"]
        self.head = params["lm_head"]           # [hidden, vocab]
        self.program = Program(params, role)

    def layer(self, i: int) -> _Layer:
        if i < self.dense_layers:
            return _Layer(self._dense, i, True)
        return _Layer(self._sparse, i - self.dense_layers, False)


def serve_role(hf: dict) -> dict:
    """The serve role of this family's configuration file, at the rehearsal's
    sizes where ``hf`` has the rehearsal's widths: what a cell serves ``hf``
    with (the block size, the SplitFuse chunk, the precision)."""
    from benchmark.harness import manifest

    data = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs",
                                           CONFIG_FILE))
    role = data["roles"]["serve"]
    reh = data["rehearsal"]
    if hf["hidden_size"] == reh["published"]["hidden_size"]:
        role = {**role,
                "engine": manifest.merge(role["engine"], reh["serve_engine"]),
                "held": manifest.merge(role["held"], reh["serve_held"])}
    return role


# of a probe's prompt, the last so many rows are judged beside its decoded
# rows (``Program.logits``): a routing variant moves about half the rows of
# ONE chip's share - those whose top 8 hold one of its experts - so the
# judged rows must be many enough for a quantile to tell half from the
# eighth that bf16's own flips move (``reference/axk1.py`` ``held``)
PROMPT_ROWS = 64


@functools.lru_cache(maxsize=None)
def _paged_call(cfg, dtype: str):
    """One jitted ``apply_paged`` a configuration and precision, for every
    ``Program`` of a process: the logits of the call's last ``min(
    PROMPT_ROWS, width)`` real rows (a call with fewer real rows repeats its
    first), the cache donated."""
    import jax
    import jax.numpy as jnp

    m = module()

    def call(params, cache, table, tokens, ctx, n_valid):
        width = tokens.shape[1]
        r = min(PROMPT_ROWS, width)
        valid = jnp.arange(width)[None] < n_valid
        rows = jnp.clip(n_valid - r + jnp.arange(r), 0)[None]
        logits, cache = m.apply_paged(
            cfg, params, tokens, cache, table, ctx, valid=valid, rows=rows,
            compute_dtype=jnp.dtype(dtype))
        return logits[0], cache

    return jax.jit(call, donate_argnums=(1,))


class Program:
    """The program beside its reference, on ONE sequence with a latent pool
    of its own: ``logits`` are ``apply_paged``'s in the served precision
    (the role's ``weights_dtype``) over the serve role's block geometry -
    the sequence in padded chunks of the SplitFuse size, its last tokens one
    at a time: prefill then decode through the latent pool. ``limits``: what
    the configuration holds the logits to (``roles.serve.held``). ``role``
    is the configuration's serve role (None: the configuration file's);
    ``weights`` names a type the weights are rounded to first (the
    precision control of ``tools/axk1_check.py``)."""

    def __init__(self, params, role=None, weights=None):
        self.params, self._role, self._call = params, role, None
        self.weights = weights

    def _setup(self, hf: dict):
        if self._call is not None:
            return
        import jax
        import jax.numpy as jnp

        role = self._role = self._role or serve_role(hf)
        self.cfg = build_cfg(hf, **role["program_options"])
        self.limits = role["held"]
        self.dtype = jnp.dtype(role["weights_dtype"])
        self.block = role["engine"]["ragged"]["block_size"]
        self.chunk = role["engine"]["split_prefill_chunk"]
        self.width = -(-hf["max_position_embeddings"] // self.block)
        if self.weights is not None:
            self.params = jax.tree.map(
                lambda p: rounded(p, self.weights), self.params)
        self._call = _paged_call(self.cfg, self.dtype.name)

    def logits(self, hf: dict, tokens, decode: int):
        """``[p + decode, vocab]``: the logits at the last ``p + decode``
        positions of ``tokens`` - the last ``p = min(PROMPT_ROWS, the final
        chunk's rows)`` rows of the chunked part, then a row a single-token
        call (every token is GIVEN: none is sampled)."""
        import jax.numpy as jnp

        self._setup(hf)
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens) - decode
        assert n > 0 and len(tokens) <= self.width * self.block, len(tokens)
        blocks = -(-len(tokens) // self.block)
        # the sequence's blocks in order behind the trash block
        table = np.zeros((1, self.width), np.int32)
        table[0, :blocks] = 1 + np.arange(blocks)
        table = jnp.asarray(table)
        # (one pool shape for every probe: one compile a call width)
        cache = module().init_paged_cache(self.cfg, self.width + 1,
                                          self.block, dtype=self.dtype)
        rows = []
        calls = [(a, min(a + self.chunk, n), self.chunk)
                 for a in range(0, n, self.chunk)] \
            + [(i, i + 1, 1) for i in range(n, len(tokens))]
        for start, end, width in calls:
            padded = np.zeros((1, width), np.int32)
            padded[0, :end - start] = tokens[start:end]
            row, cache = self._call(
                self.params, cache, table, jnp.asarray(padded),
                jnp.asarray([start], jnp.int32),
                jnp.asarray(end - start, jnp.int32))
            if width == 1 or end == n:
                rows.append(np.asarray(row)[-min(end - start, len(row)):])
        del cache
        return np.concatenate(rows)
