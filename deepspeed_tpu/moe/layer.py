"""MoE layer: gate → dispatch → sharded experts → combine.

Reference parity: ``deepspeed/moe/layer.py`` (``MoE`` :17) + ``MOELayer``
(``sharded_moe.py:537``) + ``Experts`` (``moe/experts.py``): the expert FFNs
live on separate ranks (expert parallelism); dispatch/combine travel through
all-to-all. Expert parameters get their own "expert group" treatment in the
reference's grad reduction (``runtime/engine.py:3088-3130``) — here that falls
out of sharding: expert params are sharded over the 'expert' mesh axis, so
their gradients reduce only within their replica group automatically.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..comm.mesh import get_mesh
from ..ops import pallas as _pallas_ops  # noqa: F401 (registers the kernel)
from ..ops.registry import get_op
from .sharded_moe import (GatingOutput, row_groups, row_tile, top_k_gating,
                          top_k_gating_compact)

Params = Dict[str, Any]
# the expert bank's leaves, [E, ...]. A bank has one of two FORMS, and a
# call reads which off the bank it is given: all three - a SwiGLU,
# ``down(silu(gate(x)) * up(x))`` - or ``w_up`` and ``w_down`` alone: the
# two-matrix ``down(relu(up(x)) ** 2)`` (Nemotron-H's ``relu2`` experts). A
# shared expert (``shared_w_*``) has the form its own leaves give the same way.
BANK = ("w_gate", "w_up", "w_down")


def init_moe_ffn(rng: jax.Array, n_experts: int, hidden: int, intermediate: int,
                 dtype=jnp.float32, routed: Optional[int] = None,
                 gated: bool = True) -> Params:
    """Expert FFN bank [E, ...] + router [H, E]: a SwiGLU bank of three
    matrices an expert, or, not ``gated``, the two-matrix relu^2 form (no
    ``w_gate``). ``routed``: the router's width where the bank holds only
    ``n_experts`` of the experts it chooses among (one chip's share:
    :class:`MoELayer` ``held``)."""
    ks = jax.random.split(rng, 4)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

    bank = {
        "router": normal(ks[0], (hidden, routed or n_experts), hidden),
        "w_gate": normal(ks[1], (n_experts, hidden, intermediate), hidden),
        "w_up": normal(ks[2], (n_experts, hidden, intermediate), hidden),
        "w_down": normal(ks[3], (n_experts, intermediate, hidden), intermediate),
    }
    if not gated:
        del bank["w_gate"]
    return bank


def relu2(u):
    """``relu(u) ** 2``, the activation and its square each a value of
    ``u``'s type (as the slab form's ``xe @ w`` rounds a matmul's result)."""
    r = jax.nn.relu(u)
    return r * r


def expert_ffn(w_gate, w_up, w_down, x):
    """One expert (or a dense FFN of an expert's form) over its rows: the
    SwiGLU, or the two-matrix relu^2 form where ``w_gate`` is None."""
    if w_gate is None:
        return relu2(x @ w_up) @ w_down
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def moe_ffn_logical_axes() -> Params:
    return {
        "router": ("embed", None),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }


def _expert_constraint(x):
    """Shard the leading expert dim over the 'expert' mesh axis (the a2a)."""
    mm = get_mesh()
    if mm.ep_world_size <= 1:
        return x
    spec = P(*(["expert"] + [None] * (x.ndim - 1)))
    return lax.with_sharding_constraint(x, NamedSharding(mm.mesh, spec))


class MoELayer:
    """Functional MoE FFN. Call with params from :func:`init_moe_ffn`.

    Returns (output, aux_loss). Use inside a transformer block in place of the
    dense FFN; add ``aux_loss_coef * aux_loss`` to the training loss. The
    experts' form (a SwiGLU, or two matrices and relu^2), a score-correction
    bias on the router's choice (``params["router_bias"]``) and a float32
    router are read off ``params`` (``BANK``), not options of the layer.

    Two forms of one function (:meth:`grouped` says which a call takes). A
    call that may drop tokens builds capacity SLABS, ``[E, C, H]``: a row
    past its expert's capacity has no slot, and the slabs are what an
    ``expert`` mesh axis exchanges. A call that may not, on one device and
    over the layers' STACKED banks (every serving forward of a one-chip
    engine), runs GROUPED: the routed rows gathered into an expert-major
    order and through ``moe_grouped_matmul``, which computes the rows the
    router sent - with no drops a slab is as long as the call, and all but
    ``k / E`` of the slabs' rows were zeros.

    ``held = (first, count)``: one chip's share of an expert-parallel
    deployment. The router runs over all ``n_experts`` at its published
    width and the gates are what the whole layer would give; the bank
    (``params["w_*"]``: ``count`` experts) holds experts ``first ..
    first + count - 1``, only rows routed to them are dispatched (the
    grouped form's groups, the slab form's masks, are the held experts'),
    and the output is THEIR part of the layer's sum.
    What the absent experts would add is left out: nothing stands in for the
    other chips or for their exchange. ``None``: the bank holds them all.
    """

    def __init__(self, n_experts: int, top_k: int = 2,
                 capacity_factor: float = 1.25, min_capacity: int = 4,
                 drop_tokens: bool = True, norm_topk: bool = True,
                 dispatch: str = "einsum",
                 held: Optional[Tuple[int, int]] = None,
                 score: str = "softmax",
                 shared_scale: Optional[float] = None,
                 groups: Optional[Tuple[int, int]] = None,
                 route_scale: Optional[float] = None):
        self.n_experts = n_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.min_capacity = min_capacity
        self.drop_tokens = drop_tokens
        self.norm_topk = norm_topk
        if dispatch not in ("einsum", "compact"):
            raise ValueError(f"dispatch must be 'einsum' or 'compact', "
                             f"got '{dispatch}'")
        # 'einsum': dense one-hot [T,E,C] contractions (MXU-friendly,
        # O(T·E·C·H)). 'compact': index-table gather / scatter-add
        # (O(k·T·H) movement, the shape a Pallas moe_scatter/moe_gather
        # kernel computes — reference inference/v2/kernels/ragged_ops).
        # scripts/moe_dispatch_bench.py measures which wins per backend.
        self.dispatch = dispatch
        if held is not None:
            first, count = held
            if dispatch != "einsum":
                raise ValueError("a held range of experts is the einsum "
                                 "dispatch's: 'compact' has none")
            if not (0 <= first and count >= 1
                    and first + count <= n_experts):
                raise ValueError(f"held experts {held} are not a range of "
                                 f"the {n_experts} routed")
        self.held = held
        # the router's score function (``sharded_moe.SCORES``) and, for
        # shared experts with no gate of their own, what their summed
        # output is scaled by (``n`` averaged experts of one width are one
        # FFN ``n`` times as wide, times ``1 / n``)
        self.score = score
        self.shared_scale = shared_scale
        # group-limited routing (``sharded_moe.top_k_gating_compact``
        # ``groups``) and what the ROUTED experts' sum is scaled by before a
        # shared expert is added (DeepSeek-V3's ``routed_scaling_factor``)
        self.groups = groups
        self.route_scale = route_scale

    def grouped(self) -> bool:
        """Whether a call over the stacked banks takes the grouped form - the
        rows the router sent, sorted by expert, through a grouped matmul -
        or the capacity slabs: grouped where nothing may be dropped and the
        program is one device's. Dropping IS the slab's meaning (a row past
        its expert's capacity has no slot), an ``expert`` mesh axis exchanges
        slabs, and the grouped matmul is a per-device kernel
        (``ops/registry.py``) that Mosaic refuses in a program XLA would
        have to partition. Which mesh a program spans: the process's mesh
        (``comm.mesh.get_mesh``: the inference engines install theirs and
        trace with no mesh context, their operands committed to it) and the
        mesh of the trace in progress (``MeshManager.activate``)."""
        traced = jax.sharding.get_abstract_mesh()
        return (not self.drop_tokens and self.dispatch == "einsum"
                and get_mesh().world_size == 1
                and all(n == 1 for n in traced.shape.values()))

    def __call__(self, params: Params, x: jnp.ndarray, layer=None,
                 logits=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """x: [batch, seq, hidden] → ([batch, seq, hidden], aux_loss).
        ``logits [batch * seq, n_experts]``: the router's logits where the
        family computed them itself (a router that is not one matrix:
        ``models/zaya.py``); ``params`` then needs no ``router``.
        ``layer``: ``params["w_gate" / "w_up" / "w_down"]`` are the STACKED
        ``[L, E, ...]`` banks of every layer and this is the one to read
        (int or traced scalar: the layer scan's index); None: they are one
        layer's ``[E, ...]`` and the call builds slabs (training: its
        backward needs a transpose the grouped kernel has not)."""
        b, s, h = x.shape
        tokens = x.reshape(b * s, h)
        # (``w_gate`` None: a two-matrix bank, ``BANK``)
        bank = [params[n].astype(tokens.dtype) if n in params else None
                for n in BANK]
        # moe_router: router logits, gating and the dispatch of tokens to
        # expert rows; moe_experts: the expert bank and the combine
        if logits is None:
            logits = self._router_logits(params["router"], tokens)
        bias = params.get("router_bias")     # enters the CHOICE alone
        if layer is not None and self.grouped():
            out, aux_loss = self._grouped(tokens, logits, bank, layer, bias)
        else:
            if layer is not None:
                bank = [w if w is None else w[layer] for w in bank]
            out, aux_loss = self._slabs(tokens, logits, bank, bias)
        if self.route_scale is not None:
            out = out * jnp.asarray(self.route_scale, out.dtype)
        # shared experts: a dense FFN of an expert's form (a SwiGLU, or two
        # matrices and relu^2 where ``shared_w_gate`` is absent) added to
        # every token (params present only when used) - Qwen2-MoE's under a
        # learned sigmoid gate; without a gate of its own (cohere2_moe:
        # ``shared_scale``) a plain FFN, which runs under that scope
        if "shared_w_up" in params:
            gated = "shared_gate" in params
            with jax.named_scope("moe_experts" if gated else "ffn"):
                shared = expert_ffn(*(
                    params["shared_" + n].astype(tokens.dtype)
                    if "shared_" + n in params else None for n in BANK),
                    tokens)
                if gated:
                    gate = jax.nn.sigmoid(tokens @ params["shared_gate"].astype(tokens.dtype))
                    shared = gate * shared
                elif self.shared_scale is not None:
                    shared = shared * jnp.asarray(self.shared_scale,
                                                  shared.dtype)
                out = out + shared
        return out.reshape(b, s, h), aux_loss

    @staticmethod
    def _router_logits(router, tokens):
        with jax.named_scope("moe_router"):
            if router.dtype == jnp.float32 != tokens.dtype:
                # a router kept in float32 beside narrower weights is
                # applied to float32 rows, as its family publishes it
                return jnp.dot(tokens.astype(jnp.float32), router,
                               precision=lax.Precision.HIGHEST)
            return tokens @ router.astype(tokens.dtype)

    def _gate_kw(self):
        return dict(capacity_factor=self.capacity_factor,
                    min_capacity=self.min_capacity,
                    drop_tokens=self.drop_tokens, norm_topk=self.norm_topk,
                    score=self.score, groups=self.groups)

    def _grouped(self, tokens, logits, bank, layer, bias=None):
        """The no-drop form: O(k·T·H) movement around a bank that computes
        the routed rows (and what pads each expert's rows to whole tiles)
        alone; no ``[T, E, C]`` tensor exists."""
        T, h = tokens.shape
        with jax.named_scope("moe_router"):
            cg = top_k_gating_compact(logits, self.top_k, bias=bias,
                                      **self._gate_kw())
            held, inter = bank[1].shape[-3], bank[1].shape[-1]
            groups = row_groups(
                cg, row_tile(T, self.n_experts, self.top_k, held, inter),
                self.held)
            toks_z = jnp.concatenate([tokens, jnp.zeros((1, h), tokens.dtype)])
            expert_in = toks_z[groups.source]       # each row to its place
        with jax.named_scope("moe_experts"):
            expert_out = get_op("moe_grouped_matmul")(
                expert_in, *bank, groups.tile_expert, groups.tile_rows,
                groups.num_tiles, jnp.asarray(layer, jnp.int32),
                tile=groups.tile)
            # combine: each token's k results under its gates, products and
            # their sum in float32 as the combine einsum's are on the MXU. A
            # row with no place reads nothing: what lies past the tiles in
            # use was never written
            placed = groups.place < expert_in.shape[0]
            picked = expert_out[jnp.where(placed, groups.place, 0)]
            picked = jnp.where(placed[..., None], picked, 0)
            gates = cg.gates.astype(tokens.dtype).astype(jnp.float32)
            out = jnp.sum(gates[..., None] * picked.astype(jnp.float32),
                          axis=1).astype(tokens.dtype)
        return out, cg.aux_loss

    def _slabs(self, tokens, logits, bank, bias=None):
        """The capacity form: every expert a ``[C, H]`` slab of slots."""
        T, h = tokens.shape
        with jax.named_scope("moe_router"):
            # dispatch to [E, C, H], then expert-shard (a2a)
            if self.dispatch == "compact":
                # O(k·T) end to end: the gating stays compact (no [T, E, C]
                # tensor ever exists) and the (expert, slot) → token table +
                # per-slot gate come from two scatters — the computation the
                # reference's moe_scatter/top_k_gating kernels perform
                # (inference/v2/kernels/ragged_ops)
                cg = top_k_gating_compact(logits, self.top_k, bias=bias,
                                          **self._gate_kw())
                aux_loss = cg.aux_loss
                E, C = self.n_experts, cg.capacity
                t_ids = jnp.broadcast_to(
                    jnp.arange(T, dtype=jnp.int32)[:, None], cg.pos.shape)
                e_flat = jnp.where(cg.keep, cg.topk_idx, E).reshape(-1)
                p_flat = cg.pos.reshape(-1)
                # distinct (expert, slot) pairs are unique by construction, so
                # .set scatters can't collide; dropped entries go out of bounds
                token_for = jnp.full((E, C), T, jnp.int32).at[
                    e_flat, p_flat].set(t_ids.reshape(-1), mode="drop")
                w_for = jnp.zeros((E, C), jnp.float32).at[
                    e_flat, p_flat].set(cg.gates.reshape(-1), mode="drop")
                toks_z = jnp.concatenate(
                    [tokens, jnp.zeros((1, h), tokens.dtype)])
                expert_in = toks_z[token_for]                         # gather
            else:
                gating: GatingOutput = top_k_gating(
                    logits, self.top_k, held=self.held, bias=bias,
                    **self._gate_kw())
                aux_loss = gating.aux_loss
                expert_in = jnp.einsum(
                    "tec,th->ech", gating.dispatch_mask.astype(tokens.dtype),
                    tokens)
        with jax.named_scope("moe_experts"):
            expert_in = _expert_constraint(expert_in)

            # expert FFN bank, vmapped over E (each expert's compute lands on its
            # own 'expert' shard)
            expert_out = _expert_constraint(jax.vmap(
                expert_ffn, in_axes=(None if bank[0] is None else 0, 0, 0,
                                     0))(*bank, expert_in))

            # combine: back to [T, H]  (a2a back)
            if self.dispatch == "compact":
                out = jnp.zeros_like(tokens).at[token_for.reshape(-1)].add(
                    (expert_out * w_for[..., None].astype(tokens.dtype))
                    .reshape(-1, h), mode="drop")
            else:
                out = jnp.einsum(
                    "tec,ech->th", gating.combine_weights.astype(tokens.dtype),
                    expert_out)
        return out, aux_loss
