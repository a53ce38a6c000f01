"""The ``nemotron_h`` family: Nemotron-3-Nano's published keys ->
``deepspeed_tpu.models.nemotron_h`` (three kinds of layer in the order
``hybrid_override_pattern`` spells, Mamba-2 mixers with ``n_groups`` groups
of B and C, two-matrix relu^2 experts under a sigmoid router with a choice
bias, one chip's share of the bank switched on), the configuration's rule
for random weights (``init``), and the parameter tree, stacked by KIND of
layer, -> the plain reference's weights, read lazily: one layer's matrices
or ONE expert's cut out of the stack when asked for (the engine holds 13 GB
while a probe's reference runs). The program's module is loaded when a cell
asks for it: no other family's set-up pays for it.
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

REFERENCE = "nemotron_h"
CONFIG_FILE = "nemotron-3-nano-30b-a3b.json"
# the one rule of this configuration's random weights that differs from the
# program's own ``init``: the router's score-correction bias is DRAWN,
# uniform in ``BIAS_RANGE``, where the release initialises it to zeros (and
# training moves it) - with zeros "the choice without its bias" and "the bias
# in the gates" ARE the right form - and each expert's column of the router
# is SCALED so that the load stays balanced WITH its bias, which is what the
# bias is trained for (auxiliary-loss-free balancing). A sigmoid score of a
# logit of spread ``sigma`` passes a threshold ``t`` with probability ``1 -
# Phi(logit(t) / sigma)``; with ``q = Phi^-1(1 - k / E)`` and ``t =
# sigmoid(q)`` an unbiased unit-spread expert is chosen by ``k / E`` of the
# rows, and an expert with bias ``b`` by as many where ``sigma = logit(t -
# b) / q`` (0.13 at b = 0.3, 1.67 at b = -0.1). Without the scaling a bias of
# +-0.1 sends an expert three times its share of the rows or a sixteenth of
# it, and no deployment balances its load so. This is a rule of REALISM and
# a rough one: on the chip a held expert is still chosen by 0-30 % of a
# probe's rows where the uniform share is 4.7 % (PERF.md section 6, PR 50;
# the cause was not looked for),
# so the bank's roofline counts by the shares the run's probes MEASURE
# (``reference/nemotron_h.py routed_shares``), not by this rule.
# The range leans positive because a positive bias is what the gates show
# (an expert chosen at s = 0.55 gates 0.55 of its share, and 0.85 in the
# wrong form that gates from s + b).
BIAS_RANGE = (-0.1, 0.3)
# and a second: the embedding's rows are drawn at an RMS of EMBED_RMS, the
# scale of the residual stream they enter, where the program's fan-in draw
# gives 1 / sqrt(hidden) = 0.019. Every mixer and feed-forward adds an update
# of about unit RMS to the stream, so behind a fan-in table the first layers'
# updates ARE the stream: one expert of the first sparse layers chosen the
# other way (bf16 rows against a float32 reference: 23 sparse layers, eight
# held experts of 128) turned a third of the stream and every later layer's
# routing with it - on the chip (PR 50, 2 160 judged rows of 30 probes) 40
# rows served a token more than 0.4 under the reference's top, their least
# routing margin in the first six sparse layers in 28 of the 40, and no
# margin told them from the others (3 of the 359 rows with NO layer under
# 0.05 among them): the harness's rule of ONE such position a run would have
# failed one run in fifteen. A trained table is not at fan-in scale (Granite
# publishes an ``embedding_multiplier`` of 12 for its own); at RMS 3 the
# stream is 3 at the first layer and sqrt(9 + l) after l, and a layer's
# update is a third of it at most.
EMBED_RMS = 3.0


def _program():
    try:
        from deepspeed_tpu.models import nemotron_h
    except ImportError:
        from benchmark.harness.manifest import ManifestError

        raise ManifestError(
            "this program has no models/nemotron_h.py: it cannot run the "
            "nemotron_h family") from None
    return nemotron_h


def module():
    """The program's module with ``init`` below in the place of its own
    (the harness draws a cell's weights by ``module().init``)."""
    return types.SimpleNamespace(**{**vars(_program()), "init": init})


def init(cfg, rng, **kw):
    """The program's ``init`` with every layer's choice bias drawn and its
    router's columns scaled to balance the load with it (``BIAS_RANGE``),
    and the embedding at the stream's scale (``EMBED_RMS``)."""
    import jax
    from jax.scipy.special import logit, ndtri

    params = _program().init(cfg, rng, **kw)
    moe = params["moe"]
    bias = jax.random.uniform(
        jax.random.fold_in(rng, 0xB1A5), moe["router_bias"].shape,
        moe["router_bias"].dtype, *BIAS_RANGE)
    q = ndtri(1.0 - cfg.top_k / cfg.num_experts)
    spread = logit(jax.nn.sigmoid(q) - bias) / q            # [layers, E]
    moe["router_bias"] = bias
    moe["router"] = moe["router"] * spread[:, None, :].astype(
        moe["router"].dtype)
    embed = params["embed"]
    params["embed"] = (embed.astype("float32") * (
        EMBED_RMS * cfg.hidden_size ** 0.5)).astype(embed.dtype)
    return params


def build_cfg(hf: dict, **program_options):
    """Every published size from the configuration file; ``num_experts``
    (ADDED: the configuration's ``assumed``) is the experts HELD here of the
    ``n_routed_experts`` the router chooses among, ``experts_first`` (absent:
    0) the first of them. What the program does not have is refused, not
    dropped."""
    m = _program()
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias",
                "tie_word_embeddings", "sliding_window"):
        if hf.get(key):
            raise ValueError(f"models/nemotron_h.py has no {key}")
    if not (hf["use_conv_bias"] and hf["mlp_hidden_act"] == "relu2"
            and hf["mamba_hidden_act"] == "silu"
            and hf["n_group"] == 1 and hf["topk_group"] == 1
            and hf["n_shared_experts"] == 1 and hf["norm_topk_prob"]
            and hf["moe_intermediate_size"] == hf["intermediate_size"]
            and len(hf["hybrid_override_pattern"])
            == hf["num_hidden_layers"]):
        raise ValueError("the configuration is not one models/nemotron_h.py "
                         "runs as published")
    routed, held = hf["n_routed_experts"], hf["num_experts"]
    return dataclasses.replace(
        m.NemotronHConfig(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        pattern=hf["hybrid_override_pattern"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        mamba_heads=hf["mamba_num_heads"],
        mamba_head_dim=hf["mamba_head_dim"],
        mamba_state=hf["ssm_state_size"], mamba_groups=hf["n_groups"],
        mamba_conv=hf["conv_kernel"], mamba_chunk=hf["chunk_size"],
        intermediate_size=hf["moe_intermediate_size"],
        shared_intermediate_size=hf["moe_shared_expert_intermediate_size"],
        num_experts=routed, top_k=hf["num_experts_per_tok"],
        route_scale=float(hf["routed_scaling_factor"]),
        norm_topk_prob=hf["norm_topk_prob"],
        experts_held=None if held == routed
        else (hf.get("experts_first", 0), held),
        max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["layer_norm_epsilon"], **program_options)


class _Layer(collections.abc.Mapping):
    """One layer's weights under the reference's names, each cut out of its
    kind's stack when it is read; ``kind`` is the pattern's character."""

    _ATTENTION = {"norm": "norm", "q": "wq", "k": "wk", "v": "wv", "o": "wo"}
    _MAMBA = ("norm", "conv_w", "conv_b", "dt_bias", "A_log", "D",
              "gate_norm", "out_proj")

    def __init__(self, params, kind: str, j: int):
        at = lambda stack, leaf: functools.partial(
            lambda: params[stack][leaf][j])
        self._make = {"kind": lambda: kind}
        if kind == "*":
            self._make.update({name: at("attn", leaf)
                               for name, leaf in self._ATTENTION.items()})
        elif kind == "M":
            import jax.numpy as jnp

            p = params["mamba"]
            self._make.update({name: at("mamba", name)
                               for name in self._MAMBA})
            # the published in_proj: [z | xBC | dt] (the program keeps the
            # dt columns by themselves)
            self._make["in_proj"] = lambda: jnp.concatenate(
                [p["in_proj"][j], p["dt_proj"][j]], 1)
        else:
            moe = params["moe"]
            self._make.update({
                "norm": at("moe", "norm"), "router": at("moe", "router"),
                "router_bias": at("moe", "router_bias"),
                "experts": lambda: _Each(
                    moe["w_up"].shape[1],
                    lambda e: (moe["w_up"][j, e], moe["w_down"][j, e])),
                "shared": lambda: (moe["shared_w_up"][j],
                                   moe["shared_w_down"][j])})

    def __getitem__(self, name):
        return self._make[name]()

    def __iter__(self):
        return iter(self._make)

    def __len__(self):
        return len(self._make)


class Weights:
    """The program's parameter tree, stacked by kind, read one layer at a
    time under the reference's names: ``layer(kind, j)`` is the ``j``-th
    layer of its kind (the reference walks the pattern). ``program`` is the
    program these weights are served by, for the reference's comparison
    beyond the served tokens (``reference/nemotron_h.py``
    ``logits_and_margin``)."""

    def __init__(self, params, role=None):
        self._params = params
        self.embed = params["embed"]
        self.final_norm = params["final_norm"]
        self.head = params["lm_head"]           # [hidden, vocab]
        self.program = Program(params, role)

    def layer(self, kind: str, j: int) -> _Layer:
        return _Layer(self._params, kind, j)


def serve_role(hf: dict) -> dict:
    """The serve role of this family's configuration file, at the rehearsal's
    sizes where ``hf`` has the rehearsal's widths."""
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


# of a probe's chunked part, the last so many rows are judged (``Program.
# prefill``), as the A.X-K1 cell's: many enough for a quantile of them to
# tell a routing variant from bf16's own flips
PROMPT_ROWS = 64


@functools.lru_cache(maxsize=None)
def paged_call(cfg, dtype: str):
    """One jitted ``apply_paged`` a configuration and precision, for every
    ``Program`` of a process: the logits of the call's last ``min(
    PROMPT_ROWS, width)`` real rows (a call with fewer real rows repeats its
    first), the cache donated. The sequence is slot 0.
    (``paged_call.__wrapped__`` is a jit of its own: ``tools/
    nemotron_h_check.py`` traces one with a fault planted.)"""
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
    """The program beside its reference, on ONE sequence with pools of its
    own (one slot of state): ``logits`` are ``apply_paged``'s in the served
    precision (the role's ``weights_dtype``) over the serve role's block
    geometry - ``prefill``, the sequence in padded chunks of the SplitFuse
    size through the chunked scan, then ``decode``, its last tokens one at
    a time through the state update. The two are apart so that a control
    can give the single-token calls ALONE other weights or another program
    (``tools/nemotron_h_check.py``: ``reference/nemotron_h.py`` ``held``
    judges the decoded rows by themselves). ``limits``: what the
    configuration holds the logits to (``roles.serve.held``). ``role`` is
    the configuration's serve role (None: the configuration file's);
    ``weights`` names a type the weights are rounded to first (the precision
    control); ``options`` are laid over the role's ``program_options`` (the
    ``state_dtype`` control).

    Not a subclass of ``families/axk1.py`` ``Program``, whose ``_setup`` and
    ``logits`` read that module's own ``module()``, ``build_cfg`` and
    ``_paged_call``: overriding both is the whole class."""

    def __init__(self, params, role=None, weights=None, options=None):
        self.params, self._role, self.call = params, role, None
        self.weights, self.options = weights, options or {}

    def _setup(self, hf: dict):
        if self.call is not None:
            return
        import jax
        import jax.numpy as jnp

        role = self._role = self._role or serve_role(hf)
        self.cfg = build_cfg(hf, **{**role["program_options"],
                                    **self.options})
        self.limits = role["held"]
        self.dtype = jnp.dtype(role["weights_dtype"])
        self.block = role["engine"]["ragged"]["block_size"]
        self.chunk = role["engine"]["split_prefill_chunk"]
        self.width = -(-hf["max_position_embeddings"] // self.block)
        if self.weights is not None:
            self.params = jax.tree.map(
                lambda p: rounded(p, self.weights), self.params)
        self.call = paged_call(self.cfg, self.dtype.name)

    def _run(self, call, tokens, calls, cache, table):
        """``calls`` (start, end, width) in order over ``cache``: ``(the
        last call's rows and every single-token call's, the cache)``."""
        import jax.numpy as jnp

        rows = []
        for start, end, width in calls:
            padded = np.zeros((1, width), np.int32)
            padded[0, :end - start] = tokens[start:end]
            row, cache = call(
                self.params, cache, table, jnp.asarray(padded),
                jnp.asarray([start], jnp.int32),
                jnp.asarray(end - start, jnp.int32))
            if width == 1 or (start, end, width) == calls[-1]:
                rows.append(np.asarray(row)[-min(end - start, len(row)):])
        return np.concatenate(rows), cache

    def prefill(self, hf: dict, tokens, n: int):
        """The first ``n`` of ``tokens`` in chunks, over fresh pools: ``(the
        logits at the last min(PROMPT_ROWS, the final chunk's rows) of them,
        the pools, the block table)``."""
        import jax.numpy as jnp

        self._setup(hf)
        assert 0 < n <= len(tokens) <= self.width * self.block, len(tokens)
        blocks = -(-len(tokens) // self.block)
        # the sequence's blocks in order behind the trash block
        table = np.zeros((1, self.width), np.int32)
        table[0, :blocks] = 1 + np.arange(blocks)
        table = jnp.asarray(table)
        # (one pool shape for every probe: one compile a call width)
        cache = module().init_paged_cache(self.cfg, self.width + 1,
                                          self.block, dtype=self.dtype,
                                          slots=1)
        rows, cache = self._run(
            self.call, np.asarray(tokens, np.int32),
            [(a, min(a + self.chunk, n), self.chunk)
             for a in range(0, n, self.chunk)], cache, table)
        return rows, cache, table

    def decode(self, hf: dict, tokens, n: int, cache, table, call=None):
        """``tokens[n:]`` one a call over the pools ``prefill`` left (they
        are DONATED): a row of logits each. ``call``: another program than
        this one's for them (``paged_call``'s signature)."""
        self._setup(hf)
        rows, cache = self._run(
            call or self.call, np.asarray(tokens, np.int32),
            [(i, i + 1, 1) for i in range(n, len(tokens))], cache, table)
        del cache
        return rows

    def logits(self, hf: dict, tokens, decode: int):
        """``[p + decode, vocab]``: the logits at the last ``p + decode``
        positions of ``tokens`` - ``prefill``'s rows, then a row a
        single-token call (every token is GIVEN: none is sampled)."""
        n = len(tokens) - decode
        rows, cache, table = self.prefill(hf, tokens, n)
        return np.concatenate(
            [rows, self.decode(hf, tokens, n, cache, table)])
