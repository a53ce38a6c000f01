"""Top-k gating + einsum dispatch for MoE — expert parallelism.

Reference parity: ``deepspeed/moe/sharded_moe.py`` (``TopKGate`` :453,
``top1gating`` :184, ``top2gating`` :291, ``topkgating`` :375, ``MOELayer``
:537): softmax gate → top-k expert choice → capacity-bounded position
assignment → einsum dispatch → all-to-all → experts → all-to-all → combine,
plus the load-balancing auxiliary loss.

TPU-first: dispatch/combine are dense one-hot einsums (MXU-friendly, static
shapes); the all-to-all is a sharding-constraint flip on the expert dimension
(XLA lowers it to an ICI a2a over the 'expert' mesh axis). Capacity is static:
``ceil(k * tokens * capacity_factor / n_experts)``.
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class GatingOutput(NamedTuple):
    combine_weights: jnp.ndarray   # [tokens, experts, capacity] f32
    dispatch_mask: jnp.ndarray     # [tokens, experts, capacity] bool
    aux_loss: jnp.ndarray          # scalar load-balancing loss
    router_probs: jnp.ndarray      # [tokens, experts]


class CompactGating(NamedTuple):
    """O(k·T) gating result — no [T, E, C] tensor anywhere.

    This is the output shape of the reference's dedicated gating kernels
    (``inference/v2/kernels/ragged_ops/top_k_gating``: expert assignment +
    offset per token), and the form the compact dispatch consumes directly.
    """
    topk_idx: jnp.ndarray          # [T, k] int32 — chosen expert per level
    gates: jnp.ndarray             # [T, k] f32 — (renormalized) gate values,
                                   #   zeroed where keep is False (dropped)
    pos: jnp.ndarray               # [T, k] int32 — slot within the expert
    keep: jnp.ndarray              # [T, k] bool — survived capacity
    capacity: int
    aux_loss: jnp.ndarray          # scalar load-balancing loss
    router_probs: jnp.ndarray      # [T, E] f32
    counts: jnp.ndarray            # [E] int32 — rows each expert was sent


class RowGroups(NamedTuple):
    """The routed rows of a no-drop call in an expert-major order, each
    expert's rows padded to whole row tiles (``row_groups``): what a grouped
    matmul (``ops/pallas/grouped_matmul.py``) walks."""
    place: jnp.ndarray             # [T, k] int32 — each routed row's place;
                                   #   ``places`` where its expert is absent
    source: jnp.ndarray            # [places] int32 — the token at each
                                   #   place; T where the place is padding
    tile_expert: jnp.ndarray       # [tiles] int32 — the expert of each tile
    tile_rows: jnp.ndarray         # [tiles] int32 — its rows in use, a prefix
    num_tiles: jnp.ndarray         # scalar int32 — tiles in use
    tile: int                      # rows a tile


# what a router logit becomes before the top-k, by name
SCORES = {"softmax": lambda logits: jax.nn.softmax(logits, axis=-1),
          "sigmoid": jax.nn.sigmoid}


def compute_capacity(tokens: int, n_experts: int, k: int,
                     capacity_factor: float, min_capacity: int = 4) -> int:
    cap = int(math.ceil(k * tokens * capacity_factor / n_experts))
    return max(cap, min_capacity)


def top_k_gating_compact(logits: jnp.ndarray, k: int = 1, *,
                         capacity_factor: float = 1.0, min_capacity: int = 4,
                         drop_tokens: bool = True,
                         norm_topk: bool = True,
                         score: str = "softmax",
                         groups: Optional[Tuple[int, int]] = None,
                         bias: Optional[jnp.ndarray] = None
                         ) -> CompactGating:
    """logits: [tokens, experts] → compact assignment (see CompactGating).

    The reference's top1/top2/topk gating family as one k-generic routine
    (drop policy = capacity truncation); position assignment is priority by
    token order within each k-level, levels sequential (reference: top1
    first). ``norm_topk=False`` keeps the raw softmax probs of the selected
    experts (Qwen2-MoE's norm_topk_prob=False). ``score``: what a logit
    becomes before the top-k - ``"softmax"`` over the experts, or
    ``"sigmoid"``, each expert's score by itself (``expert_selection_fn``
    of the cohere2_moe family; with ``norm_topk`` the gates are the chosen
    scores over their sum), in float32 either way. ``groups = (n_group,
    topk_group)``: group-limited routing (DeepSeek-V3, arXiv:2412.19437
    section 2.1.2, without its score-correction bias) - the experts lie in
    ``n_group`` equal groups side by side, a group's score is the sum of its
    two best experts', and the top-k is taken among the experts of the
    ``topk_group`` best groups alone; the gates are the chosen experts' own
    scores as before. Biggest live tensor is
    the [T, E] cumsum — the dense [T, E, C] view exists only in
    :func:`top_k_gating` for the einsum dispatch."""
    tokens, n_experts = logits.shape
    if score not in SCORES:
        raise ValueError(f"score must be one of {sorted(SCORES)}, "
                         f"got {score!r}")
    probs = SCORES[score](logits.astype(jnp.float32))

    if groups is None and bias is None:
        topk_probs, topk_idx = jax.lax.top_k(probs, k)      # [T, k]
    else:
        choice = probs if bias is None else probs + bias.astype(jnp.float32)
        if groups is not None:
            choice = _group_limited(choice, *groups)
        topk_idx = jax.lax.top_k(choice, k)[1]
        topk_probs = jnp.take_along_axis(probs, topk_idx, axis=1)
    if norm_topk:
        # renormalize the selected gates (reference top2: gates /= denom)
        denom = jnp.sum(topk_probs, axis=-1, keepdims=True)
        topk_gates = topk_probs / jnp.maximum(denom, 1e-9)
    else:
        topk_gates = topk_probs

    capacity = compute_capacity(tokens, n_experts, k, capacity_factor,
                                min_capacity)
    if not drop_tokens:
        capacity = max(capacity, tokens)  # no-drop: every token fits

    pos_levels, keep_levels = [], []
    prior_count = jnp.zeros((n_experts,), jnp.int32)
    for level in range(k):
        idx = topk_idx[:, level]                              # [T]
        onehot = jax.nn.one_hot(idx, n_experts, dtype=jnp.int32)  # [T, E]
        pos_in_level = jnp.cumsum(onehot, axis=0) - onehot        # [T, E]
        pos_tok = (jnp.take_along_axis(pos_in_level, idx[:, None], 1)[:, 0]
                   + prior_count[idx])                            # [T]
        pos_levels.append(pos_tok)
        keep_levels.append(pos_tok < capacity)
        prior_count = prior_count + jnp.sum(onehot, axis=0)
    pos = jnp.stack(pos_levels, axis=1)                       # [T, k]
    keep = jnp.stack(keep_levels, axis=1)                     # [T, k]

    # load-balancing aux loss (reference top1gating l_aux): E * Σ_e f_e · P_e
    top1_onehot = jax.nn.one_hot(topk_idx[:, 0], n_experts, dtype=jnp.float32)
    me = jnp.mean(probs, axis=0)            # mean router prob per expert
    ce = jnp.mean(top1_onehot, axis=0)      # fraction of tokens per expert
    aux_loss = jnp.sum(me * ce) * n_experts

    return CompactGating(topk_idx=topk_idx, gates=topk_gates * keep,
                         pos=pos, keep=keep, capacity=capacity,
                         aux_loss=aux_loss, router_probs=probs,
                         counts=prior_count)


def _group_limited(probs: jnp.ndarray, n_group: int,
                   topk_group: int) -> jnp.ndarray:
    """``probs [T, E]`` with every expert outside its row's ``topk_group``
    best groups at -inf: what the top-k may choose from."""
    tokens, n_experts = probs.shape
    if n_experts % n_group or not 1 <= topk_group <= n_group:
        raise ValueError(f"groups {(n_group, topk_group)} do not divide "
                         f"{n_experts} experts")
    grouped = probs.reshape(tokens, n_group, n_experts // n_group)
    best = jnp.sum(jax.lax.top_k(grouped, min(2, grouped.shape[-1]))[0],
                   axis=-1)                                  # [T, n_group]
    chosen = jax.lax.top_k(best, topk_group)[1]
    allowed = jnp.any(jax.nn.one_hot(chosen, n_group, dtype=jnp.bool_),
                      axis=1)                                # [T, n_group]
    return jnp.where(allowed[:, :, None], grouped, -jnp.inf) \
        .reshape(tokens, n_experts)


def row_tile(tokens: int, n_experts: int, k: int, held: int,
             inter: int) -> int:
    """Rows a tile of a grouped call over ``tokens`` token rows, through a
    bank of ``held`` of the ``n_experts`` routed experts, each ``inter``
    wide: a power of two, 16 (a bf16 register tile's rows) .. 256, no
    larger than covers the call's rows. A tile is ONE expert's, and an expert with
    more rows than a tile takes a second visit, its weights read again; a
    larger tile pads every expert's rows further, and the padding is
    gathered (never computed: the kernel works a tile in sub-tiles of an
    MXU pass and skips the empty ones). What was measured (my chip runs, PR
    41; PERF.md Findings): a tile should cover the largest group the router
    sends one expert, up to 256 rows. That group is the routing's, unknown
    when the call is traced, so the rule below is a FIT to the three cells
    it was measured at, checked under a uniform router and one skewed one
    and no further. One and a half times an expert's mean share of the rows
    where the experts are narrow and many (OLMoE, Keye: 64; 128 there pads
    a gathered buffer the call pays more for than for the second visits it
    spares, 9.47 against 9.01 ms and 4.12 against 2.90). ``inter // held``
    where that is more, which is the largest tile for wide, few experts
    (Mixtral: 1792 -> 256; its router sends one expert over 128 of a call's
    272 rows in about half the layers, and a second read of 352 MB cost the
    cell 5 % of its ``itl_p99_ms`` at 128-row tiles)."""
    want = min(tokens, max(-(-3 * tokens * k // (2 * n_experts)),
                           inter // held))
    return next((t for t in (16, 32, 64, 128) if t >= want), 256)


def row_groups(cg: CompactGating, tile: int,
               held: Optional[Tuple[int, int]] = None) -> RowGroups:
    """Each routed row's place in an expert-major order, with no sort: the
    tiles before its expert's first one (the exclusive running sum of the
    experts' tile counts, ``ceil(count / tile)``) times ``tile``, plus the
    row's position among its expert's rows (``cg.pos``). ``held = (first,
    count)``: the groups are the held experts' and a row routed to an absent
    expert has no place. The buffer is static - ``tiles * tile`` places for
    the worst routing, every expert's last tile all but empty - and
    ``num_tiles`` says how many the routing at hand uses."""
    tokens, k = cg.topk_idx.shape
    first, count = held or (0, cg.counts.shape[0])
    counts = cg.counts[first:first + count]
    chosen = cg.topk_idx - first
    present = jnp.logical_and(chosen >= 0, chosen < count)
    tiles = (tokens * min(k, count) + count * (tile - 1)) // tile
    per_expert = (counts + tile - 1) // tile
    ends = jnp.cumsum(per_expert)
    starts = ends - per_expert
    place = jnp.where(
        present, starts[jnp.clip(chosen, 0, count - 1)] * tile + cg.pos,
        tiles * tile)
    # places are distinct by construction, so the scatter cannot collide; a
    # row with no place falls out of bounds, each at an index of its own
    # (``unique_indices`` is a promise about those too)
    flat = jnp.arange(tokens * k, dtype=jnp.int32)
    source = jnp.full((tiles * tile,), tokens, jnp.int32).at[
        jnp.where(present, place, tiles * tile + flat.reshape(tokens, k))
        .reshape(-1)].set(flat // k, mode="drop", unique_indices=True)
    index = jnp.arange(tiles, dtype=jnp.int32)
    tile_expert = jnp.minimum(
        jnp.sum(index[:, None] >= ends[None, :], axis=1, dtype=jnp.int32),
        count - 1)
    # (a tile past the last in use counts past its expert's rows: 0)
    tile_rows = jnp.clip(
        counts[tile_expert] - (index - starts[tile_expert]) * tile, 0, tile)
    return RowGroups(place=place.astype(jnp.int32), source=source,
                     tile_expert=tile_expert, tile_rows=tile_rows,
                     num_tiles=ends[-1].astype(jnp.int32), tile=tile)


def top_k_gating(logits: jnp.ndarray, k: int = 1, *,
                 capacity_factor: float = 1.0, min_capacity: int = 4,
                 drop_tokens: bool = True,
                 norm_topk: bool = True,
                 held: Optional[Tuple[int, int]] = None,
                 score: str = "softmax",
                 groups: Optional[Tuple[int, int]] = None,
                 bias: Optional[jnp.ndarray] = None) -> GatingOutput:
    """Dense [T, E, C] view of :func:`top_k_gating_compact` — the form the
    einsum dispatch contracts with (MXU-friendly, but O(T·E·C) memory).
    ``held = (first, count)``: the masks of experts ``first .. first + count
    - 1`` alone, ``[T, count, C]`` - the gating itself (choices, gates, slots,
    aux loss) is over all the experts either way."""
    cg = top_k_gating_compact(logits, k, capacity_factor=capacity_factor,
                              min_capacity=min_capacity,
                              drop_tokens=drop_tokens, norm_topk=norm_topk,
                              score=score, groups=groups, bias=bias)
    tokens, n_experts = logits.shape
    chosen = cg.topk_idx
    if held is not None:
        # an expert outside the range has no column: its one-hot is all zero
        first, n_experts = held
        chosen = chosen - first
    combine = jnp.zeros((tokens, n_experts, cg.capacity), jnp.float32)
    for level in range(cg.topk_idx.shape[1]):
        # cg.gates is already keep-masked, and one_hot of an out-of-range
        # position (dropped: pos >= capacity) is all-zero — no extra guards
        combine = combine + (
            cg.gates[:, level][:, None, None]
            * jax.nn.one_hot(chosen[:, level], n_experts,
                             dtype=jnp.float32)[:, :, None]
            * jax.nn.one_hot(cg.pos[:, level], cg.capacity,
                             dtype=jnp.float32)[:, None, :])
    return GatingOutput(combine_weights=combine, dispatch_mask=combine > 0,
                        aux_loss=cg.aux_loss, router_probs=cg.router_probs)
