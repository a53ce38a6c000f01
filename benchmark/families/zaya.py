"""The ``zaya`` family: ZAYA1-8B's published keys ->
``deepspeed_tpu.models.zaya`` (attention in a convolved, compressed latent
with a per-slot tail beside the paged keys and values in every layer, a
top-1 bank behind an MLP router whose state is carried from layer to layer,
a skip output, a scaled residual path), the configuration's rule for random
weights (``init``), and the parameter tree -> the plain reference's weights,
read lazily: one layer's matrices or ONE expert's cut out of the stack when
asked for. The program's module is loaded when a cell asks for it: no other
family's set-up pays for it.
"""

from __future__ import annotations

import collections.abc
import dataclasses
import os
import sys
import types

from . import mixed_program
from .cohere2_moe import _Each  # a lazy sequence of ONE expert's matrices

REFERENCE = "zaya"
CONFIG_FILE = "zaya1-8b.json"
# The rules of this configuration's random weights beside the program's own
# ``init`` (the configuration's ``assumed`` (``weights``) and PERF.md section
# 6, PR 64, have the readings that chose them):
# the router's last matrix times ROUTER_GAIN, so that a row's 17 router logits
# spread as a trained top-1 router's do and not within what bf16 rows resolve
# (every flipped top-1 is a whole expert, not an eighth of a layer).
ROUTER_GAIN = 4.0
# The table's entries spread by EMBED_SPREAD (the program draws them at
# ``hidden ** -0.5``) and the final norm's weight its inverse, so that the
# tied head's logits keep unit spread. An attention layer of random weights
# at unit key scale AVERAGES its context: what it adds to the stream is all
# but the same vector for every row of a sequence (13 against 2 of a row's
# own at a mid size), and it grows layer by layer. Under a table drawn at
# ``hidden ** -0.5`` that vector IS the stream: every row of a sequence
# routes with its sequence, a choice bias balanced on one sequence sends
# another's rows to 0-9 x an output's share, experts stay dead or take a
# second tile at a 512-row chunk, and the chunk's tick - the cell's tail -
# moved 1.5-1.9 % from seed to seed. At 2 the row's own token carries the
# router through all twenty layers (a sequence's worst output 0.1-2.9 x its
# share at a mid size; 1.5: 0-4.8 x).
EMBED_SPREAD = 2.0
# The LAST merge hands the head the last sublayer's branch alone (its
# ``s_res`` zero) and the last router never skips (its skip's choice bias
# NO_SKIP): under a TIED table a stream that still carries its token's
# embedding at the head puts that token 45 sigma over every other, a greedy
# answer is one token repeated, a probe's 96 decoded rows share ONE routing
# margin, and two runs of six read ``correct`` false by the harness's
# quarter of decided positions alone. A trained model's last layers write
# the prediction over the token's own embedding; a diagonal weight cannot (a
# sign drawn for the final norm's channels makes the readout a SYMMETRIC
# kernel of two tokens' embeddings: answers of two tokens, turn about). No
# router of the twenty sees the change but the last one's skip.
NO_SKIP = -2.0
# The routers' BALANCING BIAS (``router_bias``, in the choice alone) set as a
# balancing procedure leaves a trained one (``_balance``): ONE pass of the
# plain reference over BALANCE_TOKENS random tokens, a layer at a time, each
# layer's bias solved (BALANCE_STEPS steps, ``_choice_bias``) on its own
# router's probabilities over the rows past BALANCE_FROM tokens of context
# (where the cell's rows live). A random MLP's 17 outputs carry offsets as
# large as what a row adds: unbalanced, an untrained top-1 router sends half
# a call's rows to one expert.
BALANCE_TOKENS, BALANCE_FROM, BALANCE_STEPS = 4096, 256, 256
# What the program's init leaves at one or zero, and a trained model does
# not, drawn so that a form that drops it is another model: ``tau``, the
# residual path's scales and the router's carry scale uniform within SPREAD
# of one, the path's biases normal at BIAS.
SPREAD = 0.25
BIAS = 0.05


def _program():
    try:
        from deepspeed_tpu.models import zaya
    except ImportError:
        from benchmark.harness.manifest import ManifestError

        raise ManifestError(
            "this program has no models/zaya.py: it cannot run the zaya "
            "family") from None
    return zaya


def module():
    """The program's module with ``init`` below in the place of its own
    (the harness draws a cell's weights by ``module().init``)."""
    return types.SimpleNamespace(**{**vars(_program()), "init": init})


def init(cfg, rng, **kw):
    """The program's ``init`` with the rules above laid over it."""
    import jax
    import jax.numpy as jnp

    params = _program().init(cfg, rng, **kw)
    layers = params["layers"]
    keys = iter(jax.random.split(jax.random.fold_in(rng, 0x2A7A), 16))

    def near_one(a):
        return (1.0 + SPREAD * jax.random.uniform(
            next(keys), a.shape, jnp.float32, -1.0, 1.0)).astype(a.dtype)

    def small(a):
        return (BIAS * jax.random.normal(next(keys), a.shape,
                                         jnp.float32)).astype(a.dtype)

    gain = EMBED_SPREAD * cfg.hidden_size ** 0.5
    for name, by in (("embed", gain), ("final_norm", 1.0 / gain)):
        params[name] = (params[name] * by).astype(params[name].dtype)
    layers["tau"] = near_one(layers["tau"])
    for path in ("attn_path", "mlp_path"):
        layers[path] = {name: (near_one if name.startswith("s_") else small)(
            leaf) for name, leaf in sorted(layers[path].items())}
    last = layers["mlp_path"]["s_res"]
    layers["mlp_path"]["s_res"] = last.at[-1].set(0)
    moe = layers["moe"]
    moe["router_carry"] = near_one(moe["router_carry"])
    moe["router_out"] = moe["router_out"] * ROUTER_GAIN
    bias = _balance(cfg, params, next(keys))
    moe["router_bias"] = bias.at[-1, -1].set(NO_SKIP)
    return params


def _balance(cfg, params, key):
    """A choice bias ``[L, E + 1]`` under which the rows of ONE random
    sequence choose every output of every layer alike, as the auxiliary-
    loss-free balancing of a trained top-1 router leaves it. By the plain
    REFERENCE's float32 layers, not the program's: one pass, a layer at a
    time - the layer's router probabilities over the rows as the balanced
    layers before it left them, its bias solved on those (``_choice_bias``),
    then its experts under that bias."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import zaya as reference

    layers = params["layers"]
    hf = {"num_attention_heads": cfg.num_heads, "head_dim": cfg.head_dim,
          "num_key_value_heads": cfg.num_kv_heads,
          "num_experts": cfg.num_experts, "rms_norm_eps": cfg.rms_norm_eps,
          "partial_rotary_factor": cfg.partial_rotary_factor,
          "rope_parameters": {"hybrid": {"rope_theta": cfg.rope_theta}}}
    eps = cfg.rms_norm_eps
    s = min(BALANCE_TOKENS, cfg.max_seq_len)
    first = min(BALANCE_FROM, s // 2)       # the rows judged: from here on
    tokens = jax.random.randint(key, (s,), 0, cfg.vocab_size)
    widths = Weights.widths(layers)

    def body(stream, one):
        r, z = stream
        w = dict(_Layer(jax.tree.map(lambda a: a[None], one), 0, widths))
        y = reference.attention(reference.norm(r, w["attn_norm"], eps), w,
                                hf)
        r = reference.merge(r, y, w["attn_path"])
        u = reference.norm(r, w["mlp_norm"], eps)
        P, _ = reference.probabilities(u, z, w["router"], eps)
        beta = _choice_bias(P[first:])
        w["router"] = {**w["router"], "bias": beta}
        y, z = reference.experts(u, z, w, hf)
        return (reference.merge(r, y, w["mlp_path"]), z), beta

    with jax.default_matmul_precision("highest"):
        r = params["embed"][tokens].astype(jnp.float32)
        z = jnp.zeros((s, cfg.router_hidden_size), jnp.float32)
        return jax.lax.scan(body, (r, z), layers)[1]


def _choice_bias(P):
    """The bias ``[n]`` under which ``argmax(P + bias)`` over the rows ``P
    [rows, n]`` chooses every output alike: BALANCE_STEPS steps against each
    output's excess load (smaller towards the end: the choice is an argmax,
    and a fixed step hunts about the balance it cannot land on)."""
    import jax
    import jax.numpy as jnp

    n = P.shape[-1]

    def step(i, beta):
        load = jnp.mean(jax.nn.one_hot(jnp.argmax(P + beta, axis=-1), n,
                                       dtype=jnp.float32), axis=0)
        return beta - (1.0 - i / BALANCE_STEPS) * (load - 1.0 / n)

    return jax.lax.fori_loop(0, BALANCE_STEPS, step,
                             jnp.zeros((n,), jnp.float32))


# published keys this family runs at ONE value: (key, the value, what the
# program would need for another)
PUBLISHED_AS = (
    ("model_type", "zaya", "another family's module"),
    ("attention_bias", False, "biases on the projections"),
    ("lm_head_bias", False, "a bias on the head"),
    ("tie_word_embeddings", True, "an untied head"),
    ("hidden_act", "silu", "another activation in the experts"),
    ("sliding_window", None, "window layers (the 74B's, every fourth)"),
    ("num_experts_per_tok", 1, "more than one expert a token"),
)


def build_cfg(hf: dict, **program_options):
    """Every published size from the configuration file. ``num_local_experts``
    (ADDED: the configuration's ``assumed``) and ``num_experts`` are the
    experts there are AND held; the router's width is one more (the skip). A
    published key the program does not run as published is refused BY NAME,
    not dropped."""
    m = _program()
    for key, value, needs in PUBLISHED_AS:
        if hf[key] != value:
            raise ValueError(f"models/zaya.py runs {key} = {value!r} alone "
                             f"({hf[key]!r} needs {needs})")
    layers = hf["num_hidden_layers"]
    if set(hf["layer_types"][:layers]) != {"hybrid"}:
        raise ValueError("models/zaya.py has 'hybrid' layers alone (an "
                         "attention and an expert sublayer each)")
    rope = hf["rope_parameters"]["hybrid"]
    if rope["rope_type"] != "default" \
            or rope["partial_rotary_factor"] != hf["partial_rotary_factor"]:
        raise ValueError("models/zaya.py ropes by the plain table over "
                         "partial_rotary_factor of a head: "
                         "rope_parameters.hybrid says otherwise")
    if hf.get("num_local_experts", hf["num_experts"]) != hf["num_experts"]:
        raise ValueError("models/zaya.py holds every expert: num_experts "
                         "must equal num_local_experts")
    return dataclasses.replace(
        m.ZayaConfig(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=layers, num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        cca_time0=hf["cca_time0"], cca_time1=hf["cca_time1"],
        partial_rotary_factor=hf["partial_rotary_factor"],
        rope_theta=float(rope["rope_theta"]),
        num_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        intermediate_size=hf["moe_intermediate_size"],
        router_hidden_size=hf["router_hidden_size"],
        max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"], **program_options)


class _Layer(collections.abc.Mapping):
    """One layer's weights under the reference's names, each cut out of the
    stacked tree when it is read."""

    _NAMES = ("attn_norm", "conv0_w", "conv0_b", "conv1_w", "conv1_b", "tau",
              "wo", "mlp_norm")
    _ROUTER = {"down": "router_down", "down_bias": "router_down_bias",
               "carry": "router_carry", "norm": "router_norm",
               "w1": "router_w1", "b1": "router_b1", "w2": "router_w2",
               "b2": "router_b2", "out": "router_out", "bias": "router_bias"}

    def __init__(self, layers, i: int, widths):
        moe, (q, k, d) = layers["moe"], widths
        self._make = {name: (lambda name=name: layers[name][i])
                      for name in self._NAMES}
        # ``w_in`` is [Wq | Wk | Wv1 | Wv2]: one matmul of the program's
        cuts = {"wq": (0, q), "wk": (q, q + k), "wv1": (q + k, q + k + d),
                "wv2": (q + k + d, q + k + 2 * d)}
        self._make.update({
            name: (lambda a=a, b=b: layers["w_in"][i, :, a:b])
            for name, (a, b) in cuts.items()})
        self._make.update({
            path: (lambda path=path: {n: leaf[i] for n, leaf
                                      in layers[path].items()})
            for path in ("attn_path", "mlp_path")})
        self._make.update({
            "router": lambda: {name: moe[leaf][i]
                               for name, leaf in self._ROUTER.items()},
            "experts": lambda: _Each(
                moe["w_up"].shape[1],
                lambda e: (moe["w_gate"][i, e], moe["w_up"][i, e],
                           moe["w_down"][i, e]))})

    def __getitem__(self, name):
        return self._make[name]()

    def __iter__(self):
        return iter(self._make)

    def __len__(self):
        return len(self._make)


class Weights:
    """The program's stacked parameter tree, read one layer at a time under
    the reference's names. ``program`` is the program these weights are
    served by, for the reference's comparison beyond the served tokens
    (``reference/zaya.py`` ``logits_and_margin``)."""

    def __init__(self, params, role=None):
        self._layers = params["layers"]
        self.embed = params["embed"]            # [vocab, hidden]: the head too
        self.final_norm = params["final_norm"]
        self._widths = self.widths(self._layers)
        self.program = Program(params, role)

    @staticmethod
    def widths(layers):
        """``(q, k, one value half)``: the cuts of ``w_in``."""
        hd = layers["conv1_w"].shape[-1]
        return layers["wo"].shape[1], layers["tau"].shape[-1] * hd, hd

    def layer(self, i: int) -> _Layer:
        return _Layer(self._layers, i, self._widths)


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


def Program(params, role=None, **kw):
    """This family's program beside its reference, run as the window runs it
    (``families/mixed_program.py``: every call a mixed call over the role's
    slots, other sequences live in the other slots)."""
    return mixed_program.MixedProgram(sys.modules[__name__], params, role,
                                      **kw)
