"""The ``keye`` family: Keye-VL-2.0's published language-model keys ->
``deepspeed_tpu.models.mixtral`` (the program's one module for its sparse
decoders, with a head size of its own, the per-head QK-norm, the learned
token selection and one chip's share of the expert bank switched on), the
configuration's rule for random weights (``init``), and the parameter tree ->
the plain reference's weights. The program's module is loaded when a cell
asks for it, and so are the selection's kernels (``models/_paged.py`` imports
them inside the sparse step): no other family's set-up pays for them.
"""

from __future__ import annotations

import dataclasses
import os
import types

import numpy as np

from . import mixtral

REFERENCE = "keye"
# the QK-norms' weights, the one rule of this configuration's random weights
# that could differ from the program's: with an RMSNorm over each head of q
# and k a gain on Wq or Wk would be normed away, so the norms would carry
# it, and scores of q . k / sqrt(128) would spread by QK_GAIN ** 2. Chosen on
# the chip by the wrong variants AND by the right form's own noise
# (``tools/keye_check.py --gains``, PERF.md section 6, PR 38): at 1.0 the
# served logits lie 0.007-0.012 (mean) from the reference's and the nearest
# wrong variant 0.078; at 1.5 the right form's noise triples; at 2.0
# attention is so peaked that one token taken the other side of a threshold
# in bf16 changes a head's whole output, the model is chaotic under bf16
# (the right form 0.35-0.60 off, served tokens up to 3.9 below the
# reference's top) and no variant can be told from it. So: 1.0, the
# program's own ones.
QK_GAIN = 1.0


def module():
    """The program's module with ``init`` below in the place of its own
    (the harness draws a cell's weights by ``module().init``)."""
    return types.SimpleNamespace(**{**vars(mixtral.module()), "init": init})


def init(cfg, rng, **kw):
    """The program's ``init`` with both QK-norms' weights at ``QK_GAIN``."""
    params = mixtral.module().init(cfg, rng, **kw)
    for name in ("q_norm", "k_norm"):
        w = params["layers"][name]
        params["layers"][name] = (w * QK_GAIN).astype(w.dtype)
    return params


def build_cfg(hf: dict, **program_options):
    """``num_local_experts`` is the router's width - all the experts a token
    chooses among, a width to the harness - and ``num_experts`` the experts
    HELD here (the published file gives both as 128; the cut of one chip's
    share changes the second alone). ``moe_intermediate_size`` is one
    expert's width; ``intermediate_size`` (6144) is the dense width no layer
    of this model uses (``mlp_only_layers`` is empty)."""
    m = mixtral.module()
    if not hasattr(m, "SparseAttention"):
        from benchmark.harness.manifest import ManifestError

        raise ManifestError(
            "this program's models/mixtral.py has no learned token "
            "selection (SparseAttention): it cannot run the keye family")
    for key in ("attention_bias", "use_sliding_window", "sliding_window",
                "tie_word_embeddings", "mlp_only_layers"):
        if hf.get(key):
            raise ValueError(f"models/mixtral.py has no {key}")
    sa = hf["sa_config"]
    if hf["decoder_sparse_step"] != 1 or sa["indexer_num_kv_heads"] != 1 \
            or hf["rope_scaling"]["rope_type"] != "default":
        raise ValueError("the configuration is not one models/mixtral.py "
                         "runs as published")
    if program_options.get("norm_topk_prob", True) != hf["norm_topk_prob"]:
        raise ValueError("the role's program_options and the published "
                         "configuration disagree on norm_topk_prob")
    routed, held = hf["num_local_experts"], hf["num_experts"]
    return dataclasses.replace(
        m.MixtralConfig(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["moe_intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        qk_norm=True, num_experts=routed, top_k=hf["num_experts_per_tok"],
        experts_held=None if held == routed
        else (hf.get("experts_first", 0), held),
        sparse_attention=m.SparseAttention(
            index_heads=sa["indexer_num_heads"],
            index_head_dim=sa["indexer_head_dim"], topk=sa["topk"]),
        max_seq_len=hf["max_position_embeddings"],
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        **program_options)


class Weights(mixtral.Weights):
    """``layer(i)["experts"]`` are the HELD experts, as the bank has them.
    ``program`` is the program these weights are served by, for the
    reference's comparison beyond the served tokens (``reference/keye.py``
    ``held``)."""

    def __init__(self, params, role=None):
        super().__init__(params)
        self.program = Program(params, role)

    def layer(self, i: int) -> dict:
        p = self._layers
        return {**super().layer(i), "q_norm": p["q_norm"][i],
                "k_norm": p["k_norm"][i], "q_idx": p["wq_idx"][i],
                "k_idx": p["wk_idx"][i], "w_idx": p["ww_idx"][i]}


CONFIG_FILE = "keye-vl-2.0-30b-a3b.json"


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


class Program:
    """The program beside its reference, on ONE sequence and a pool of its
    own: ``logits`` are ``apply_paged``'s in the served precision (the role's
    ``weights_dtype``: bf16 is also what the engine's own calls compute in)
    over the serve role's block geometry (the sequence in padded chunks of
    the SplitFuse size, its last tokens one at a time), ``selected`` the sets its
    indexer and its selection (``paged_sparse_select``) take from a given
    normed input; ``limits`` what the configuration holds the two to
    (``roles.serve.held``). ``role`` is the configuration's serve role (None:
    the configuration file's)."""

    def __init__(self, params, role=None):
        self.params, self._role, self._call = params, role, None

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
        m = module()

        def call(params, cache, table, tokens, ctx, n_valid):
            valid = jnp.arange(tokens.shape[1])[None] < n_valid
            logits, cache = m.apply_paged(cfg, params, tokens, cache, table,
                                          ctx, valid=valid,
                                          compute_dtype=self.dtype)
            return logits[0, n_valid - 1], cache

        self._call = jax.jit(call, donate_argnums=(1,))

    def logits(self, hf: dict, tokens, decode: int):
        """``[decode + 1, vocab]``: the logits at the last ``decode + 1``
        positions of ``tokens`` - the row that ends the chunked part, then a
        row a single-token call (every token is GIVEN: none is sampled)."""
        import jax.numpy as jnp

        self._setup(hf)
        tokens = np.asarray(tokens, np.int32)
        n = len(tokens) - decode
        assert n > 0 and len(tokens) <= self.width * self.block, len(tokens)
        table = np.zeros((1, self.width), np.int32)
        need = -(-len(tokens) // self.block)
        table[0, :need] = 1 + np.arange(need)           # block 0 is the trash
        table = jnp.asarray(table)
        cache = module().init_paged_cache(self.cfg, self.width + 1,
                                          self.block, dtype=self.dtype)
        rows = []
        for start in range(0, n, self.chunk):
            piece = tokens[start:min(start + self.chunk, n)]
            padded = np.zeros((1, self.chunk), np.int32)
            padded[0, :len(piece)] = piece
            row, cache = self._call(
                self.params, cache, table, jnp.asarray(padded),
                jnp.asarray([start], jnp.int32),
                jnp.asarray(len(piece), jnp.int32))
        rows.append(np.asarray(row))
        for i in range(n, len(tokens)):
            row, cache = self._call(
                self.params, cache, table, jnp.asarray(tokens[None, i:i + 1]),
                jnp.asarray([i], jnp.int32), jnp.asarray(1, jnp.int32))
            rows.append(np.asarray(row))
        del cache
        return np.stack(rows)

    def selected(self, hf: dict, layer: int, y, rows: int, keys=None):
        """``[rows, len(y)]`` bool: the cached tokens the last ``rows`` rows
        of a sequence may read at ``layer``, from its normed input ``y
        [seq, hidden]`` cast to the served precision. ``keys`` names a type
        the index keys are rounded to before they are scored: the control of
        a precision below the configuration's (``tools/keye_check.py``)."""
        import jax.numpy as jnp

        from deepspeed_tpu.models import mixtral as program
        from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse
        from deepspeed_tpu.ops.registry import get_op

        self._setup(hf)
        n, topk = y.shape[0], self.cfg.sparse_attention.topk
        last = slice(n - rows, n)
        idx = {k: v[layer] for k, v in self.params["layers"].items()
               if k.endswith("_idx")}
        q_idx, k_idx, w_idx = program.index_vectors(
            self.cfg, idx, y.astype(self.dtype)[None],
            *program.index_rope(self.cfg), jnp.arange(n)[None])
        if keys is not None:
            k_idx = k_idx.astype(keys).astype(self.dtype)
        s = sparse.index_scores_dense(q_idx[0, last], k_idx[0],
                                      w_idx[0, last])
        s = jnp.pad(s, ((0, 0), (0, (-n) % 2048)))
        q_abs = jnp.arange(n - rows, n, dtype=jnp.int32)
        tau, cut = get_op("paged_sparse_select")(s, q_abs, topk=topk)
        pos = jnp.arange(s.shape[1])[None]
        return np.asarray(sparse.selected(s, pos, tau[:, None], cut[:, None])
                          & (pos <= q_abs[:, None]))[:, :n]
