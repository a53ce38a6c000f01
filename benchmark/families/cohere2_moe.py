"""The ``cohere2_moe`` family: Command A+'s published language-model keys ->
``deepspeed_tpu.models.cohere2_moe`` (the parallel block, the two kinds of
layer with a KV pool of their own kind each, the sigmoid router and the
averaged shared experts, one chip's share of the expert bank switched on),
the configuration's rule for random weights (``init``), and the parameter
tree -> the plain reference's weights. The program's module is loaded when a
cell asks for it: no other family's set-up pays for it.
"""

from __future__ import annotations

import collections.abc
import dataclasses
import functools
import os
import types

import numpy as np

REFERENCE = "cohere2_moe"
CONFIG_FILE = "command-a-plus-05-2026.json"
# the one rule of this configuration's random weights that could differ from
# the program's own ``init``: Wq and Wk each scaled by ``sqrt(QK_GAIN)``, so
# that scores ``q . k / sqrt(128)`` spread by QK_GAIN instead of 1. Tried
# because a softmax over 4-12 k random keys at unit scores is all but flat,
# and a window on the wrong kind of layer might then have moved no logit
# beyond bf16's noise. It does: chosen on the chip by the wrong variants AND
# by the right form's own noise (``tools/cohere2_check.py --gains``, PERF.md
# section 6, PR 42) - at 1.0 the program's logits lie 0.0058-0.0081 (the
# median judged row's mean absolute difference) from the reference's, a
# window on the full layer 0.30 and the nearest wrong variant (an RMSNorm)
# 0.030-0.044; at 2.0 the attention variants read up to twice as loud but
# the right form does too (0.0116-0.0137; the RMSNorm 0.046-0.053). So: 1.0,
# the program's own weights.
QK_GAIN = 1.0


def module():
    """The program's module with ``init`` below in the place of its own
    (the harness draws a cell's weights by ``module().init``)."""
    from deepspeed_tpu.models import cohere2_moe

    return types.SimpleNamespace(**{**vars(cohere2_moe), "init": init})


def init(cfg, rng, gain=None, **kw):
    """The program's ``init`` with Wq and Wk at ``sqrt(gain)`` (None:
    ``QK_GAIN`` as it stands when the weights are drawn)."""
    from deepspeed_tpu.models import cohere2_moe

    gain = QK_GAIN if gain is None else gain
    params = cohere2_moe.init(cfg, rng, **kw)
    for name in ("wq", "wk"):
        w = params["layers"][name]
        params["layers"][name] = (w * gain ** 0.5).astype(w.dtype)
    return params


def build_cfg(hf: dict, **program_options):
    """``num_local_experts`` is the router's width - all the experts a token
    chooses among; a key this benchmark ADDS (the published file has ONE
    key, ``num_experts``, for both; the harness reads ``num_local_experts``
    as a width no cut may touch) - and ``num_experts`` the experts HELD here.
    ``intermediate_size`` is ONE expert's width, routed or shared."""
    try:
        from deepspeed_tpu.models import cohere2_moe as m
    except ImportError:
        from benchmark.harness.manifest import ManifestError

        raise ManifestError(
            "this program has no models/cohere2_moe.py: it cannot run the "
            "cohere2_moe family") from None
    for key in ("attention_bias", "use_qk_norm", "first_k_dense_replace"):
        if hf.get(key):
            raise ValueError(f"models/cohere2_moe.py has no {key}")
    if not (hf["use_parallel_block"] and hf["tie_word_embeddings"]
            and hf["expert_selection_fn"] == "sigmoid"
            and hf["shared_expert_combination_strategy"] == "average"
            and hf["position_embedding_type"] == "rope_gptj"
            and hf["rotary_pct"] == 1 and hf["hidden_act"] == "silu"):
        raise ValueError("the configuration is not one models/cohere2_moe.py "
                         "runs as published")
    if program_options.get("norm_topk_prob", True) != hf["norm_topk_prob"]:
        raise ValueError("the role's program_options and the published "
                         "configuration disagree on norm_topk_prob")
    routed, held = hf["num_local_experts"], hf["num_experts"]
    return dataclasses.replace(
        m.Cohere2MoeConfig(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        num_experts=routed, top_k=hf["num_experts_per_tok"],
        num_shared_experts=hf["num_shared_experts"],
        layer_types=tuple(hf["layer_types"][:hf["num_hidden_layers"]]),
        sliding_window=hf["sliding_window"],
        experts_held=None if held == routed
        else (hf.get("experts_first", 0), held),
        max_seq_len=hf["max_position_embeddings"],
        rope_theta=float(hf["rope_theta"]),
        layer_norm_eps=hf["layer_norm_eps"],
        logit_scale=float(hf["logit_scale"]), **program_options)


class Weights:
    """The program's stacked parameter tree, read one layer at a time under
    the reference's names. ``layer(i)["experts"]`` are the HELD experts, as
    the bank has them; ``["shared"]`` the shared experts one by one, cut out
    of the one wide FFN the program keeps them as. ``program`` is the
    program these weights are served by, for the reference's comparison
    beyond the served tokens (``reference/cohere2_moe.py`` ``held``)."""

    def __init__(self, params, role=None):
        self._layers = params["layers"]
        self.embed = params["embed"]            # the head too: tied
        self.final_norm = params["final_norm"]
        self.program = Program(params, role)

    def layer(self, i: int) -> "_Layer":
        return _Layer(self._layers, i)


class _Each:
    """A layer's experts as a sequence that cuts ONE expert's three matrices
    out of the stack when it is asked for it: a layer's bank is 1.6 GB at
    the cell's widths, and the reference runs beside a serving engine."""

    def __init__(self, count: int, one):
        self._count, self._one = count, one

    def __len__(self):
        return self._count

    def __getitem__(self, e):
        if isinstance(e, slice):
            return [self._one(j) for j in range(*e.indices(self._count))]
        return self._one(range(self._count)[e])

    def __iter__(self):
        return (self._one(j) for j in range(self._count))


class _Layer(collections.abc.Mapping):
    """One layer's weights under the reference's names, each cut out of the
    stacked tree when it is read (``keys`` are all there: ``{**layer}``
    reads every one)."""

    def __init__(self, layers, i: int):
        moe = layers["moe"]
        width = moe["w_gate"].shape[-1]
        cut = lambda j: slice(j * width, (j + 1) * width)
        self._make = {
            "norm": lambda: layers["norm"][i], "q": lambda: layers["wq"][i],
            "k": lambda: layers["wk"][i], "v": lambda: layers["wv"][i],
            "o": lambda: layers["wo"][i], "router": lambda: moe["router"][i],
            "experts": lambda: _Each(
                moe["w_gate"].shape[1],
                lambda e: (moe["w_gate"][i, e], moe["w_up"][i, e],
                           moe["w_down"][i, e])),
            "shared": lambda: _Each(
                moe["shared_w_gate"].shape[-1] // width,
                lambda j: (moe["shared_w_gate"][i, :, cut(j)],
                           moe["shared_w_up"][i, :, cut(j)],
                           moe["shared_w_down"][i, cut(j)]))}

    def __getitem__(self, name):
        return self._make[name]()

    def __iter__(self):
        return iter(self._make)

    def __len__(self):
        return len(self._make)


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


def rounded(p, below: str):
    """``p`` rounded to the floating type ``below`` names and back, by
    ``lax.reduce_precision``: a pair of converts is what XLA on the chip
    takes out again (it allows excess precision), and the control would
    read what the right form reads."""
    import jax
    import jax.numpy as jnp

    if not jnp.issubdtype(p.dtype, jnp.floating):
        return p
    info = jnp.finfo(jnp.dtype(below))
    return jax.lax.reduce_precision(p, info.nexp, info.nmant)


@functools.lru_cache(maxsize=None)
def _paged_call(cfg, dtype: str):
    """One jitted ``apply_paged`` a configuration and precision, for every
    ``Program`` of a process (a run builds one a probe): the logits of the
    call's last real row, the cache donated."""
    import jax
    import jax.numpy as jnp

    m = module()

    def call(params, cache, table, tokens, ctx, n_valid):
        valid = jnp.arange(tokens.shape[1])[None] < n_valid
        logits, cache = m.apply_paged(cfg, params, tokens, cache, table,
                                      ctx, valid=valid,
                                      compute_dtype=jnp.dtype(dtype))
        return logits[0, n_valid - 1], cache

    return jax.jit(call, donate_argnums=(1,))


# what a read of a block that was given back finds (``Program(poison=)``):
# large enough that one such key takes a softmax over, small enough for bf16
POISON = 1.0e3


class Program:
    """The program beside its reference, on ONE sequence with pools and a
    ``StateManager`` of its own: ``logits`` are ``apply_paged``'s in the
    served precision (the role's ``weights_dtype``) over the serve role's
    block geometry - the sequence in padded chunks of the SplitFuse size, its
    last tokens one at a time -, each call's tables built by the manager as
    the engine's builds them, so the window kind's blocks behind the window
    are GIVEN BACK on the way. ``limits``: what the configuration holds the
    logits to (``roles.serve.held``). ``role`` is the configuration's serve
    role (None: the configuration file's).

    The controls of ``tools/cohere2_check.py``: ``release_early`` gives each
    window block back that many blocks before the manager would (a wrong
    PROGRAM); ``poison`` fills the window kind's trash block - what a table
    entry of a given-back block points at - and every block the allocator
    has free with ``POISON``, so that a read of what was given back is
    loud where the right form, which never reads it, is unmoved; ``weights``
    names a type the weights are rounded to first (the precision control)."""

    def __init__(self, params, role=None, release_early: int = 0,
                 poison: bool = False, weights=None):
        self.params, self._role, self._call = params, role, None
        self.release_early, self.poison = release_early, poison
        self.weights = weights

    def _setup(self, hf: dict):
        if self._call is not None:
            return
        import jax
        import jax.numpy as jnp

        role = self._role = self._role or serve_role(hf)
        self.cfg = cfg = build_cfg(hf, **role["program_options"])
        self.limits = role["held"]
        self.dtype = jnp.dtype(role["weights_dtype"])
        self.block = role["engine"]["ragged"]["block_size"]
        self.chunk = role["engine"]["split_prefill_chunk"]
        self.width = -(-hf["max_position_embeddings"] // self.block)
        if self.weights is not None:
            self.params = jax.tree.map(
                lambda p: rounded(p, self.weights), self.params)

        self._call = _paged_call(cfg, self.dtype.name)

    def _state(self):
        """A manager for one sequence: the full kind a table's width of
        blocks, the window kind what the engine would give one slot."""
        from deepspeed_tpu.inference.ragged import StateManager, WindowKind

        kinds = tuple(
            WindowKind.sized(name, window, 1, self.chunk, self.block)
            for name, window in module().window_kinds(self.cfg).items())
        return StateManager(1, self.width + 1, self.block, self.width,
                            window_kinds=kinds)

    def _table(self, state, desc, n: int):
        """``desc``'s table for a call that writes its next ``n`` tokens,
        as the engine builds it; with ``release_early`` the window kinds'
        first so many live entries point at the trash block too."""
        state.extend(desc, n)
        table = state.block_table(desc)
        at = state.max_blocks_per_seq
        for kind in state.window_kinds if self.release_early else ():
            # the segment: the offset, then the blocks held from the first
            # live one on; never the block the call writes into
            early = min(self.release_early,
                        desc.seen_tokens // self.block - table[at])
            table[at + 1:at + 1 + max(early, 0)] = 0
            at += 1 + kind.blocks_per_seq
        return table[None]

    def logits(self, hf: dict, tokens, decode: int):
        """``[decode + 1, vocab]``: the logits at the last ``decode + 1``
        positions of ``tokens`` - the row that ends the chunked part, then a
        row a single-token call (every token is GIVEN: none is sampled)."""
        import jax.numpy as jnp

        self._setup(hf)
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens) - decode
        assert n > 0 and len(tokens) <= self.width * self.block, len(tokens)
        state = self._state()
        desc = state.admit(0, n)
        cache = module().init_paged_cache(
            self.cfg, self.width + 1, self.block, dtype=self.dtype,
            window_blocks={k.name: k.num_blocks for k in state.window_kinds})
        if self.poison:
            # every block of a window kind: what the calls write they write
            # over it, and what they gave back (or never had) stays loud
            cache = {name: jnp.full_like(pool, POISON)
                     if name.split("_")[-1] in
                     {k.name for k in state.window_kinds} else pool
                     for name, pool in cache.items()}
        rows = []
        calls = [(a, min(a + self.chunk, n), self.chunk)
                 for a in range(0, n, self.chunk)] \
            + [(i, i + 1, 1) for i in range(n, len(tokens))]
        for start, end, width in calls:
            padded = np.zeros((1, width), np.int32)
            padded[0, :end - start] = tokens[start:end]
            row, cache = self._call(
                self.params, cache,
                jnp.asarray(self._table(state, desc, end - start)),
                jnp.asarray(padded), jnp.asarray([start], jnp.int32),
                jnp.asarray(end - start, jnp.int32))
            desc.seen_tokens = end
            if width == 1 or end == n:
                rows.append(np.asarray(row))
        state.debug_check()
        del cache
        return np.stack(rows)
