"""Shared paged-KV attention step — the one copy of the v2 block-table
protocol every family's ``apply_paged`` builds on.

Contract (see ``models/llama.py`` for the layout): the KV pool is ONE
``[num_layers, num_blocks, kv_heads, block_size, hd]`` buffer (last two dims
are the decode kernel's per-block tile — TPU tiling legal), block tables are
fixed-width ``[b, max_blocks]`` indices into a layer's blocks, block 0 is the
trash block that padded table entries point at (nothing writes it: a padded
row writes nothing), and ``positions`` are absolute token positions
(``context_lens + arange(t)``).

The pools stay where they are (:func:`scan_layers`): they are the layer
scan's carry, every layer writes its rows in place (``paged_kv_write``) and
both attention kernels take the layer as an index into the ``[L, ...]``
buffer. No layer's pool is sliced out and none is stacked back.

Heads narrower than a lane tile (``init_paged_pools(lane_pack=True)``): as
many KV heads as fill 128 lanes lie side by side in one pool row,
``[L, blocks, nkv / pack, bs, pack * hd]`` - the same bytes in whole tiles.
:func:`paged_attention_step` reads the packing off the pool's shape and
packs and unpacks its operands around the same three ops, so a family hands
it q, k and v at their own head size.

Quantized KV mode (``inference.kv_quant``, docs/serving.md "Quantized KV
cache"): the cache dict additionally carries ``k_scale``/``v_scale`` pools
``[num_layers, num_blocks, kv_heads, block_size, ngroups]`` fp32, K/V pools
hold int8 codes, and each :class:`LayerPool` carries its scale pool beside
its codes. Fill-time quantization is fused into the cache update (per-token
groupwise scales — a token's write never touches another position's scale;
codes and scales are written by the one ``paged_kv_write`` call), and dequant
is fused into the attention reads: in-register inside both Pallas kernels
(``paged_decode`` for one query token, ``paged_prefill`` for more). There is
NO standalone int8→bf16 convert pass over the pool — QUANT_TPU_LIVE.json
shows that path losing to bf16 outright.

A mixed call (:class:`MixedCall` in place of the block tables): one
sequence's prefill chunk rides in a decode step as more rows of the same row
dimension, so everything that works a row at a time - projections, the FFN
or the expert bank, the head - reads its weights once for both. Only
:func:`paged_attention_step` splits the rows back into the two segments it
has kernels for.

The rows a call reads (``apply_paged(..., rows=)``, :func:`gather_rows`): a
program that samples from a few rows' logits - a prefill its sequences' last
real rows, a mixed call its decode rows and the chunk's last real row - says
so, and the family gathers the hidden state to those rows BEFORE the final
norm and the head: the unembed matmul, its float32 result and whatever
picked rows out of it run on ``r`` rows and not on ``t``. A call that reads
every row passes nothing and is the program it was.

Kinds of KV state (``inference.ragged.WindowKind``): a family whose stack
mixes full-attention and sliding-window layers keeps ONE such buffer a kind,
``[L_kind, blocks_kind, nkv, bs, hd]`` (:func:`init_kind_pools`: ``k`` /
``v`` the full kind's, ``k_<kind>`` / ``v_<kind>`` a window kind's), each
through its own scan's carry, and its calls' block tables are one segment a
kind side by side (:func:`kind_tables`): a window kind's is SHORT - the
blocks a sequence holds from its first live one on, behind the count of
those it gave back - and its layers walk it at context lengths counted from
there. A layer hands :func:`paged_attention_step` its kind's pools, its
kind's table and lengths and, for a window kind, the window - the kernels
bound their walk by it, so what the manager gave back is never reached.

A latent (MLA) cache (:func:`init_latent_pool`,
:func:`latent_attention_step`): ONE pool, ``cache["latent"]``, one row a
token a layer - the token's normed latent and its roped key side by side -
attended in the absorbed form: one KV head, every query head in its group,
keys the whole row and values its first lanes, through the same write and
the same two walks.

Reads: both kernels walk the block table over the live context
(``ops/pallas/paged_attention.py``). Nothing here gathers a dense view of
the pool; only the ops' XLA references do, and the registry picks those off
a TPU alone.
"""

from __future__ import annotations

import itertools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def lane_pack_of(num_kv_heads: int, head_size: int) -> int:
    """KV heads that share one row of a lane-packed pool's last axis: as
    many as fill a 128-lane tile and divide the heads. A head of 64 is half
    a tile: the device's default layout of a ``[.., bs, 64]`` pool is then
    NOT row-major (it makes the blocks axis minor rather than pad 64 lanes
    to 128), a Mosaic operand must be, and every program re-laid both pools
    out on its way in and on its way out (738 MB of copies a call at two
    attention layers of Granite-4.0-H-Micro's pool, compiled for a
    described v5e; PERF.md Findings, PR 31)."""
    lanes = max(1, 128 // head_size)
    return max(p for p in range(1, lanes + 1) if num_kv_heads % p == 0)


def init_paged_pools(num_layers: int, num_blocks: int, num_kv_heads: int,
                     block_size: int, head_size: int, dtype=jnp.bfloat16,
                     kv_quant_group: Optional[int] = None,
                     lane_pack: bool = False):
    """The one cache-pool constructor every family's ``init_paged_cache``
    delegates to. Plain mode returns the historical ``{"k", "v"}`` dict;
    with ``kv_quant_group`` set (``inference.kv_quant.group_size``, clamped
    to ``head_size``) the pools hold int8 codes plus fp32
    ``[L, num_blocks, nkv, bs, ngroups]`` scale pools beside them — the
    per-block scale table that every block-lifecycle op (COW copy, fork,
    spill, truncate) carries automatically because it is part of the cache
    pytree. Scales init to ZERO so unwritten positions and the trash block
    dequantize to exactly the bf16 pool's zeros. ``lane_pack``: heads
    narrower than a lane tile share pool rows (:func:`lane_pack_of`). A
    family opts in, and the choice is not made from the head size alone:
    packed rows have no quantized mode (the scale pools are a head's), and
    a tensor-parallel cache shards the KV-heads axis that packing folds."""
    pack = lane_pack_of(num_kv_heads, head_size) if lane_pack else 1
    if pack > 1 and kv_quant_group is not None:
        raise ValueError("lane-packed pools have no quantized mode")
    shape = (num_layers, num_blocks, num_kv_heads // pack, block_size,
             pack * head_size)
    if kv_quant_group is None:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    gs = min(int(kv_quant_group), head_size)
    if gs < 1 or head_size % gs:
        raise ValueError(
            f"kv_quant.group_size {kv_quant_group} does not divide "
            f"head_size {head_size}")
    sshape = shape[:-1] + (head_size // gs,)
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32)}


def init_kind_pools(layers: Dict[str, int], blocks: Dict[str, int],
                    num_kv_heads: int, block_size: int, head_size: int,
                    dtype=jnp.bfloat16):
    """One K and one V pool a KIND of KV state (:func:`init_paged_pools`
    each): ``layers`` and ``blocks`` give each kind's layers and blocks;
    the kind named ``"full"`` keeps the historical ``k`` / ``v`` leaves, any
    other is ``k_<kind>`` / ``v_<kind>``."""
    cache = {}
    for kind, n in layers.items():
        pools = init_paged_pools(n, blocks[kind], num_kv_heads, block_size,
                                 head_size, dtype)
        suffix = "" if kind == "full" else "_" + kind
        cache.update({name + suffix: pool for name, pool in pools.items()})
    return cache


def kind_tables(block_tables, context_lens, full_width: int,
                block_size: int) -> Tuple:
    """A call's block tables and context lengths as each kind's own:
    ``((tables, context_lens), ...)``, the full kind's first. The table a
    ``StateManager`` with window kinds builds is the full kind's
    ``full_width`` entries, then a segment a window kind: the blocks the
    sequence has given back at its front (its OFFSET in that kind) and the
    blocks it holds from the first live one on. A window layer walks that
    short table at context lengths counted from the offset - positions only
    ever meet positions of the same sequence, in masks and in the page a
    row is written to, and the offset is whole blocks, so every comparison
    stands; rope takes the true positions elsewhere. A :class:`MixedCall`
    splits both of its tables and shifts ``lens`` and ``chunk_ctx`` the same
    way. (ONE window kind, as the one family with kinds has: everything
    after the full kind's entries is its segment.)"""
    mixed = isinstance(block_tables, MixedCall)
    tables = block_tables.tables if mixed else block_tables
    if tables.shape[1] <= full_width:   # one table for every kind
        return ((block_tables, context_lens),)
    seg = tables[:, full_width:]
    if not mixed:
        return ((tables[:, :full_width], context_lens),
                (seg[:, 1:], context_lens - seg[:, 0] * block_size))
    call = block_tables
    chunk_seg = call.chunk_table[full_width:]
    return ((call._replace(tables=tables[:, :full_width],
                           chunk_table=call.chunk_table[:full_width]), None),
            (call._replace(
                tables=seg[:, 1:], chunk_table=chunk_seg[1:],
                lens=call.lens - seg[:, 0] * block_size,
                chunk_ctx=call.chunk_ctx - chunk_seg[0] * block_size), None))


class LayerPool(NamedTuple):
    """One of a layer's two cache entries as :func:`scan_layers` hands it to
    a family's scan body and the body hands it on to
    :func:`paged_attention_step`: the WHOLE ``[L, num_blocks, nkv, bs, hd]``
    pool (int8 codes in quantized mode), its scale pool (None in plain
    mode) and the layer's index. The pool is never sliced: the write and
    both kernels index the layer where the pool lies."""
    pool: jnp.ndarray
    scale: Optional[jnp.ndarray]
    layer: jnp.ndarray


class MixedCall(NamedTuple):
    """A call of TWO segments in one row dimension, handed to a family's
    ``apply_paged`` where a ``[b, t]`` call hands ``block_tables`` (its
    ``context_lens`` is then None): tokens ``[1, slots + t]``, the first
    ``slots`` rows ONE decode token of each sequence slot - ``tables
    [slots, blocks]``, ``lens [slots]``, ``active [slots]`` as a decode
    step's - and the last ``t`` rows one sequence's prefill chunk at context
    offset ``chunk_ctx`` through ``chunk_table [blocks]``, ``chunk_valid`` of
    them real (both scalars). ``chunk_slot``: the chunk's sequence slot, for
    a family with recurrent state. The chunk's sequence is not an active
    slot. A family passes the structure on to :func:`paged_attention_step`
    untouched."""
    tables: jnp.ndarray
    lens: jnp.ndarray
    active: jnp.ndarray
    chunk_table: jnp.ndarray
    chunk_ctx: jnp.ndarray
    chunk_valid: jnp.ndarray
    chunk_slot: Optional[jnp.ndarray] = None

    @property
    def slots(self) -> int:
        """The static boundary between the two segments' rows."""
        return self.lens.shape[0]

    def valid(self, rows: int) -> jnp.ndarray:
        """``[1, rows]``: the active slots' rows and the chunk's real ones."""
        chunk = jnp.arange(rows - self.slots) < self.chunk_valid
        return jnp.concatenate([self.active, chunk])[None]

    def split(self, x) -> Tuple:
        """``x [1, slots + t, ...]`` as the decode segment ``[slots, 1,
        ...]`` and the chunk segment ``[1, t, ...]``."""
        return x[0, :self.slots, None], x[:, self.slots:]

    @staticmethod
    def join(decode, chunk):
        """The inverse of :meth:`split`."""
        return jnp.concatenate([decode[:, 0][None], chunk], axis=1)


def row_positions(block_tables, context_lens, t: int) -> jnp.ndarray:
    """Every row's absolute token positions ``[b, t]``: ``context_lens +
    arange(t)`` of a ``[b, t]`` call; of a :class:`MixedCall` (``t`` its
    ``slots + chunk`` rows) ``lens[i]`` for slot i's decode row and
    ``chunk_ctx + j`` for the chunk's row j."""
    if isinstance(block_tables, MixedCall):
        call = block_tables
        return jnp.concatenate(
            [call.lens, call.chunk_ctx + jnp.arange(t - call.slots)])[None]
    return context_lens[:, None] + jnp.arange(t)[None, :]


def gather_rows(x: jnp.ndarray, rows: Optional[jnp.ndarray]) -> jnp.ndarray:
    """The rows of ``x [b, t, H]`` whose logits the call reads: ``rows
    [b, r]`` int32 indices along ``t`` (traced values, a static ``r``) give
    ``[b, r, H]``, for the final norm and the head to run on; None gives
    ``x`` itself. Every family's ``apply_paged`` ends through it, after its
    last layer and before its final norm (a per-row operation: the rows kept
    read what they would have read)."""
    if rows is None:
        return x
    return jnp.take_along_axis(x, rows[:, :, None], axis=1, mode="clip")


def scan_layers(body, x, layers, cache, *extras):
    """The pools' way through a program's layers, for every family: the
    pools are the scan's CARRY, beside ``x`` - one ``[L, ...]`` buffer a
    pool from the program's (donated) argument to its result, written in
    place by each layer and read where it lies. The stacked ``layers``, the
    layer's index and any per-layer ``extras`` (exaone4's windows and rope
    flags) are the scanned inputs. ``body(x, (layer, k_entry, v_entry,
    *extras))`` returns ``(x, (k_entry, v_entry))``, the entries
    :class:`LayerPool` s that it passes through
    :func:`paged_attention_step`. A cache with a THIRD pool, ``kI`` (the
    index keys of a learned token selection, :func:`sparse_attention_step`),
    hands its entry over after the other two and takes it back the same
    way. ``x`` is whatever the family streams through its layers - the
    hidden state, or a pytree with more beside it (``models/zaya.py``: the
    residual stream, a router state layer ``l - 1`` hands layer ``l``, and a
    per-slot pool of tails every layer reads and writes by the state pool's
    row ops). Returns ``(x, cache)``.

    Nothing with a layout preference of its own may touch the pools on the
    way (an XLA scatter on the carry copies the whole ``[L, ...]`` pool a
    layer: PERF.md Findings, PR 29). What the scan itself adds around the
    blocks carries the pool update's name; the blocks' own scopes lie
    inside it."""
    names = ("k", "v") + (("kI",) if "kI" in cache else ())

    def step(carry, scanned):
        x, pools = carry
        layer, index, *rest = scanned
        entries = [LayerPool(pools[n], pools.get(n + "_scale"), index)
                   for n in names]
        x, written = body(x, (layer, *entries, *rest))
        pools = {n: e.pool for n, e in zip(names, written)}
        if written[0].scale is not None:
            pools.update(k_scale=written[0].scale, v_scale=written[1].scale)
        return (x, pools), None

    num_layers = cache["k"].shape[0]
    with jax.named_scope("kv_write"):
        (x, cache), _ = lax.scan(
            step, (x, cache),
            (layers, jnp.arange(num_layers, dtype=jnp.int32)) + extras)
    return x, cache


def layer_plan(layer_types) -> Tuple[int, list, Dict[str, int]]:
    """How :func:`scan_nest` runs a stack of several KINDS of layer:
    ``(periods, runs, layers of each kind a period)`` - the smallest period
    the pattern repeats with, and that period cut, left to right, into RUNS:
    a UNIT of layers repeated ``count`` times, the unit and count that cover
    the most layers from where the run starts (a unit of more than one layer
    only where it repeats). A run is ``(kind, first layer of the kind inside
    the period, count)`` for a unit of one layer, and ``(kinds, first layer
    of its kind inside the period for each of the unit's layers, count)``
    for a longer one. Granite-4.0-H: 4 periods of [5 Mamba, 1 attention, 4
    Mamba]; Nemotron-3-Nano's 52 layers have no period and are 5 x
    ``MEMEM*E``, 3 x ``ME``, ``M``, ``*``, 4 x ``EM``, ``E``."""
    kinds = tuple(layer_types)
    n = len(kinds)
    period = next(p for p in range(1, n + 1) if n % p == 0 and all(
        kinds[i] == kinds[i % p] for i in range(n)))
    kinds = kinds[:period]
    runs, seen, at = [], dict.fromkeys(kinds, 0), 0
    while at < period:
        unit, count = 1, 1
        for u in range(1, max(1, (period - at) // 2) + 1):
            c = 1       # (a slice past the period's end is short: unequal)
            while kinds[at + c * u:at + (c + 1) * u] == kinds[at:at + u]:
                c += 1
            if (u == 1 or c > 1) and u * c > unit * count:
                unit, count = u, c
        firsts = []
        for kind in kinds[at:at + unit]:
            firsts.append(seen[kind])
            seen[kind] += 1
        for kind in kinds[at:at + unit]:     # the unit's other repeats
            seen[kind] += count - 1
        runs.append((kinds[at], firsts[0], count) if unit == 1
                    else (kinds[at:at + unit], tuple(firsts), count))
        at += unit * count
    return n // period, runs, seen


def scan_nest(layer_types, layers, x, pools, blocks):
    """A stack of several kinds of layer as ``layer_types`` spells it
    (:func:`layer_plan`): an outer scan over the pattern's periods whose
    body scans each run, a run's step being its unit's layers one after
    another - so one body a kind of run is compiled whatever the depth.
    ``layers[kind]`` is the weights of that kind's layers, STACKED; a layer
    takes its own by its index into them (what a scan's per-step slice of
    its inputs is), so no period's slab is cut out on the way.
    ``blocks[kind](x, weights, pools, index) -> (x, pools)``, ``index`` the
    layer's among its kind; ``pools`` (None without a cache) is the carry of
    every scan, beside ``x``."""
    periods, runs, per_period = layer_plan(layer_types)

    def run(unit, firsts, carry, p, count):
        each = {kind: unit.count(kind) for kind in unit}

        def step(carry, i):
            for kind, first in zip(unit, firsts):
                # (a unit of one layer: the steps ARE its kind's indices)
                index = i if len(unit) == 1 \
                    else p * per_period[kind] + first + i * each[kind]
                w = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                    a, index, 0, keepdims=False), layers[kind])
                x, pools = carry
                carry = blocks[kind](x, w, pools, index)
            return carry, None

        steps = (p * per_period[unit[0]] + firsts[0] if len(unit) == 1
                 else 0) + jnp.arange(count, dtype=jnp.int32)
        return lax.scan(step, carry, steps)[0]

    def period(carry, p):
        for unit, firsts, count in runs:
            if isinstance(unit, str):
                unit, firsts = (unit,), (firsts,)
            carry = run(unit, firsts, carry, p, count)
        return carry, None

    with jax.named_scope("kv_write"):   # as scan_layers names its scan
        return lax.scan(period, (x, pools),
                        jnp.arange(periods, dtype=jnp.int32))[0]


# --------------------------------------------------------------------------- #
# window and full attention layers in ONE stack of one shape of layer
# (models/cohere2_moe.py, models/mellum.py): what the two families share
# --------------------------------------------------------------------------- #
# layer type -> the kind of KV state it keeps (the cache leaves' suffix)
KINDS = {"full_attention": "full", "sliding_attention": "window"}


def stack_layer_types(layer_types, num_layers: int) -> Tuple[str, ...]:
    """``layer_types`` for every layer of a stack of window and full layers:
    the tuple as given where it names them all, one period of it repeated
    otherwise. Refused: a type :data:`KINDS` does not have, and a stack with
    no full layer (it has no full kind of KV state)."""
    types = tuple(layer_types)
    if num_layers % len(types):
        raise ValueError(f"{num_layers} layers are no whole number "
                         f"of the {len(types)}-layer pattern")
    types = types * (num_layers // len(types))
    unknown = set(types) - set(KINDS)
    if unknown:
        raise ValueError(f"layer_types names {sorted(unknown)}; this family "
                         f"has {sorted(KINDS)}")
    if "full_attention" not in types:
        raise ValueError("a stack of window layers alone has no full kind "
                         "of KV state: this family wants one full layer a "
                         "period at least")
    return types


def stack_plan(types):
    """``(periods, period, runs, layers of each type a period)`` of a stack
    whose layers all have ONE shape (:func:`scan_stack`): the smallest
    period the pattern repeats with and that period's runs of one type as
    ``(type, first layer of the run inside the period, first layer of the
    type inside the period, count)``."""
    n = len(types)
    period = next(p for p in range(1, n + 1) if n % p == 0 and all(
        types[i] == types[i % p] for i in range(n)))
    runs, seen, at = [], {t: 0 for t in KINDS}, 0
    for kind, group in itertools.groupby(types[:period]):
        count = len(list(group))
        runs.append((kind, at, seen[kind], count))
        seen[kind] += count
        at += count
    return n // period, period, runs, seen


def scan_stack(types, x, layers, pools, blocks):
    """A stack of window and full layers as ``types`` spells it, its layers
    of one shape and STACKED together (:func:`scan_nest` is the pattern; it
    takes a stack a kind): an outer scan over the pattern's periods whose
    body scans each run of one type. A layer takes its weights by its index
    into the stacked ``layers`` (what a scan's per-step slice of its inputs
    is), so no period's slab is cut out on the way. ``blocks[type](x,
    weights, pools, layer index, index among its type) -> (x, pools, aux)``;
    ``pools`` (None without a cache) is the carry of every scan, beside
    ``x`` and the summed aux loss."""
    periods, period, runs, per_period = stack_plan(types)

    def run(kind, carry, p, at, first, count):
        def step(carry, i):
            index = p * period + at + i
            w = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                a, index, 0, keepdims=False), layers)
            x, pools, aux = carry
            x, pools, more = blocks[kind](
                x, w, pools, index, p * per_period[kind] + first + i)
            return (x, pools, aux + more), None

        return lax.scan(step, carry, jnp.arange(count, dtype=jnp.int32))[0]

    def one_period(carry, p):
        for kind, at, first, count in runs:
            carry = run(kind, carry, p, at, first, count)
        return carry, None

    with jax.named_scope("kv_write"):   # as scan_layers names its scan
        x, pools, aux = lax.scan(
            one_period, (x, pools, jnp.zeros((), jnp.float32)),
            jnp.arange(periods, dtype=jnp.int32))[0]
    return x, pools, aux


def stack_window_kinds(types, sliding_window: int) -> Dict[str, int]:
    """The kinds of KV state beside the full one, each with its window: what
    the serving engine sizes a pool and an allocator for
    (``inference.engine.ModelFamily.window_kinds``)."""
    return {"window": sliding_window} if "sliding_attention" in types else {}


def init_stack_pools(types, sliding_window: int, num_blocks: int,
                     window_blocks: Optional[Dict[str, int]],
                     num_kv_heads: int, block_size: int, head_size: int,
                     dtype=jnp.bfloat16):
    """The full layers' pools, ``k`` / ``v`` ``[L_full, num_blocks, ...]``,
    and the window layers', ``k_window`` / ``v_window`` ``[L_window,
    window_blocks["window"], ...]`` (the engine sizes them:
    ``inference.ragged.WindowKind.sized``; as many as the full kind's where
    no one says). No quantized-KV mode."""
    blocks = {"full": num_blocks,
              **{kind: (window_blocks or {}).get(kind, num_blocks)
                 for kind in stack_window_kinds(types, sliding_window)}}
    layers = {kind: types.count(layer_type)
              for layer_type, kind in KINDS.items() if kind in blocks}
    return init_kind_pools(layers, blocks, num_kv_heads, block_size,
                           head_size, dtype)


def paged_kind_attention(cache, block_tables, context_lens, valid,
                         max_seq_len: int, windows: Dict[str, int]):
    """``attend(kind, q, k, v, pools, i) -> (mix, pools)``: layer ``i`` of
    ``kind``'s step over the kind's own pools (``k`` / ``v`` or ``k_<kind>``
    / ``v_<kind>`` of ``pools``, the scan's carry), through the kind's own
    table and lengths (:func:`kind_tables`) and, for a window kind, under
    its window (``windows``: ``stack_window_kinds``), in the scope
    ``attn_<kind>``."""
    block_size = cache["k"].shape[-2]
    # (the engine's width of the full kind's table: ``engine_v2``)
    full_width = max(2, -(-max_seq_len // block_size))
    parts = kind_tables(block_tables, context_lens, full_width, block_size)
    tables = {"full": parts[0], **dict.fromkeys(windows, parts[-1])}

    def attend(kind, q, k, v, pools, i):
        suffix = "" if kind == "full" else "_" + kind
        names = ("k" + suffix, "v" + suffix)
        with jax.named_scope("attn_" + kind):
            mix, k_c, v_c = paged_attention_step(
                q, k, v, *(LayerPool(pools[n], None, i) for n in names),
                *tables[kind], None, valid, window=windows.get(kind))
        return mix, {**pools, names[0]: k_c.pool, names[1]: v_c.pool}

    return attend


def dense_kind_attention(cache, cache_len, positions):
    """The v1 engine's dense cache under the same stack: every layer keeps
    the whole context, a window layer masks what lies behind its window.
    ``of(kind, window)`` makes a kind's mask (once a program) and returns
    its ``attend(q, k, v, pools, index) -> (mix, pools)``, ``pools`` the
    ``{"k", "v"}`` ``[L, b, max_len, nkv, hd]`` cache and ``index`` the
    layer's among ALL layers."""
    from ..ops.attention import attention

    kv_pos = jnp.arange(cache["k"].shape[2])[None, None, None, :]
    q_abs = positions[:, None, :, None]

    def write(pool, index, new):
        def one(c, n, s):
            return lax.dynamic_update_slice(c, n.astype(c.dtype), (s, 0, 0))

        layer = jax.vmap(one)(lax.dynamic_index_in_dim(
            pool, index, 0, keepdims=False), new, cache_len)
        return lax.dynamic_update_index_in_dim(pool, layer, index, 0), layer

    def of(kind, window):
        mask = kv_pos <= q_abs
        if window is not None:
            mask = mask & (q_abs - kv_pos < window)

        def attend(q, k, v, pools, index):
            k_pool, k_c = write(pools["k"], index, k)
            v_pool, v_c = write(pools["v"], index, v)
            with jax.named_scope("attn_" + kind):
                mix = attention(q, k_c, v_c, causal=False, mask=mask)
            return mix, {"k": k_pool, "v": v_pool}

        return attend

    return of


def paged_attention_step(q, k, v, k_cache, v_cache, block_tables,
                         context_lens, positions, valid, *,
                         window=None, scale=None) -> Tuple:
    """Write this step's K/V into the block pool, then attend over it.

    q [b, t, nh, hd]; k/v [b, t, nkv, hd]. ``k_cache``/``v_cache`` are the
    layer's :class:`LayerPool` entries (:func:`scan_layers`). ``positions``
    are ``context_lens + arange(t)`` (the module's contract) and ``valid``
    [b, t] is a prefix mask of each sequence's real rows: a padded row
    writes nothing and its output is unspecified. ``window``: optional
    per-layer sliding-window length (int or traced scalar — exaone4 scans
    per-layer windows). ``scale``: the softmax scale of ``q k^T``, handed to
    both kernels (None: ``hd ** -0.5``; Granite's ``attention_multiplier``
    is another). The write is ``paged_kv_write`` (fill-time
    quantization in the same call in quantized mode); single-token decode
    then dispatches the paged flash-decode kernel, every multi-token call
    the paged flash-prefill kernel (each windowed or plain-causal, with the
    dequant fused in quantized mode), all three on the layer's index into
    the ``[L, ...]`` pools. Lane-packed pools (:func:`lane_pack_of`) are
    known by their rows, ``pack`` times as wide as ``k``'s heads. A
    :class:`MixedCall` as ``block_tables`` (q/k/v ``[1, slots + t, ..]``) is
    honoured here and nowhere else: its two segments are this function's two
    calls. Returns (attn_out [b, t, nh, hd], k_cache, v_cache) with the
    written pools in the entries."""
    del positions
    if isinstance(block_tables, MixedCall):
        return _mixed_step(q, k, v, k_cache, v_cache, block_tables,
                           window=window, scale=scale)
    from ..ops import pallas as _pallas_ops  # noqa: F401 (registers)
    from ..ops.registry import get_op

    t = q.shape[1]
    pack = k_cache.pool.shape[-1] // k.shape[-1]
    if pack > 1:
        if scale is None:
            scale = q.shape[-1] ** -0.5     # of the head, not of the row
        q, k, v, unpack = _lane_packed(q, k, v, pack)
    layer = k_cache.layer
    # ``valid`` is a prefix mask, so its sum is each sequence's count of
    # real rows
    n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)
    # int8 pools reach the ops as codes with their scales beside them:
    # quantized on the way in, dequantized in-register on the way out. The
    # ops get the LAYER's scale pools: a ``[.., bs, 1]`` f32 pool has no
    # dense tiled layout (a Mosaic operand pads its last dim to 128 lanes),
    # so the whole ``[L, ...]`` scale pool is never one - the layer's slice,
    # 1/128 of its codes' bytes, is cut out and put back here
    scales = {} if k_cache.scale is None else {
        "k_scale": k_cache.scale[layer], "v_scale": v_cache.scale[layer]}
    with jax.named_scope("kv_write"):   # the pool update, by its own name
        k_pool, v_pool, *written = get_op("paged_kv_write")(
            k, v, k_cache.pool, v_cache.pool, block_tables, context_lens,
            n_valid, layer=layer, **scales)
        scales = dict(zip(scales, written))
        k_scale, v_scale = (
            None if s is None else c.scale.at[layer].set(s)
            for c, s in zip((k_cache, v_cache), written))
    if t == 1:
        out = get_op("paged_decode_attention")(
            q[:, 0], k_pool, v_pool, block_tables, context_lens,
            scale=scale, window=window, layer=layer, **scales)[:, None]
    else:
        # every multi-token call - a prefill chunk at a context offset, a
        # batched prefill, a prefix-cache suffix, a speculative verify
        # window - walks the block table over the live context in one flash
        # kernel. Off a TPU the op is the gathered XLA reference, as for
        # every op.
        out = get_op("paged_prefill_attention")(
            q, k_pool, v_pool, block_tables, context_lens, n_valid,
            scale=scale, window=window, layer=layer, **scales)
    if pack > 1:
        out = unpack(out)
    return (out, LayerPool(k_pool, k_scale, layer),
            LayerPool(v_pool, v_scale, layer))


def _mixed_step(q, k, v, k_cache, v_cache, call: MixedCall, **kw) -> Tuple:
    """:func:`paged_attention_step` of a mixed call: the rows are cut at the
    static segment boundary and each segment is written and attended as the
    ``[1, t]`` chunk call and the ``[slots, 1]`` decode call it would have
    been alone, in that order - the same Mosaic calls on the same operands -
    and the outputs joined. Nothing is padded to a ``[slots, t]`` rectangle
    and nothing but those calls touches the pools."""
    (q_d, q_c), (k_d, k_c), (v_d, v_c) = map(call.split, (q, k, v))
    chunk_rows = (jnp.arange(q_c.shape[1]) < call.chunk_valid)[None]
    out_c, k_cache, v_cache = paged_attention_step(
        q_c, k_c, v_c, k_cache, v_cache, call.chunk_table[None],
        call.chunk_ctx[None], None, chunk_rows, **kw)
    out_d, k_cache, v_cache = paged_attention_step(
        q_d, k_d, v_d, k_cache, v_cache, call.tables, call.lens, None,
        call.active[:, None], **kw)
    return call.join(out_d, out_c), k_cache, v_cache


def _lane_packed(q, k, v, pack: int):
    """A step's operands as lane-packed pools take them, and the way back
    for the output. A packed row is ``pack`` heads' keys (values) side by
    side, which is the projection's own memory order, so K and V are only
    reshaped. A query head meets its own KV head's lanes and zeros
    elsewhere - the other heads' lanes add nothing to its scores -, and of
    the output row (every packed head's weighted values) it keeps its own
    head's lanes. Query heads stay in order: head ``kv * g + i`` is row
    ``(kv % pack) * g + i`` of packed head ``kv // pack``'s group."""
    b, t, nh, hd = q.shape
    nkv = k.shape[2]
    mine = jax.nn.one_hot((jnp.arange(nh) // (nh // nkv)) % pack, pack,
                          dtype=q.dtype)[:, :, None]        # [nh, pack, 1]
    q = (q[:, :, :, None, :] * mine).reshape(b, t, nh, pack * hd)
    k, v = (a.reshape(b, t, nkv // pack, pack * hd) for a in (k, v))

    def unpack(out):
        return jnp.sum(out.reshape(b, t, nh, pack, hd) * mine, axis=3)

    return q, k, v, unpack


# --------------------------------------------------------------------------- #
# a latent (MLA) cache: ONE pool, one row a token a layer
# --------------------------------------------------------------------------- #
def latent_row_width(key_width: int) -> int:
    """Lanes of a latent pool's row: the ``key_width`` numbers a token
    keeps (its normed latent, then its roped key), zero-padded to whole
    128-lane tiles. A Mosaic operand is row-major (:func:`lane_pack_of`'s
    finding) and the device's tiled layout pads a minor dimension to whole
    tiles either way, so the padding is said here and costs what the device
    would have spent unasked."""
    return -(-key_width // 128) * 128


def init_latent_pool(num_layers: int, num_blocks: int, block_size: int,
                     key_width: int, dtype=jnp.bfloat16):
    """``cache["latent"]``: ``[L, num_blocks, 1, block_size, row width]`` -
    the block pools' shape at one "head", so every block-lifecycle op
    (copy-on-write, fork, prefix reuse) carries it as it carries any leaf
    with the block axis."""
    return {"latent": jnp.zeros((num_layers, num_blocks, 1, block_size,
                                 latent_row_width(key_width)), dtype)}


def latent_attention_step(q, row, cache: LayerPool, block_tables,
                          context_lens, valid, *, value_width: int,
                          scale: float) -> Tuple:
    """:func:`paged_attention_step` over a latent pool, in the ABSORBED
    form: multi-query attention at one KV head. ``q [b, t, nh, key_width]``
    is each head's query against a cached row (the up-projection of the keys
    folded in by the family), ``row [b, t, 1, key_width]`` the step's rows to
    cache; a token's values are the first ``value_width`` numbers of its
    row. Both are zero-padded to the pool's row here. The write and both
    walks are the paged ops at ``v_pool`` None (``ops/pallas/
    paged_attention.py``): the key page is read once and serves both
    matmuls. ``scale`` is the family's (the absorbed query is not the width
    the softmax scale comes from). A :class:`MixedCall` splits as it does
    there. Returns ``(out [b, t, nh, value_width], cache)``."""
    if isinstance(block_tables, MixedCall):
        call = block_tables
        (q_d, q_c), (r_d, r_c) = call.split(q), call.split(row)
        chunk_rows = (jnp.arange(q_c.shape[1]) < call.chunk_valid)[None]
        kw = dict(value_width=value_width, scale=scale)
        out_c, cache = latent_attention_step(
            q_c, r_c, cache, call.chunk_table[None], call.chunk_ctx[None],
            chunk_rows, **kw)
        out_d, cache = latent_attention_step(
            q_d, r_d, cache, call.tables, call.lens, call.active[:, None],
            **kw)
        return call.join(out_d, out_c), cache
    from ..ops import pallas as _pallas_ops  # noqa: F401 (registers)
    from ..ops.registry import get_op

    pad = cache.pool.shape[-1] - q.shape[-1]
    q, row = (jnp.pad(a, ((0, 0),) * 3 + ((0, pad),)) for a in (q, row))
    layer = cache.layer
    n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)
    with jax.named_scope("kv_write"):
        pool = get_op("paged_kv_write")(
            row, None, cache.pool, None, block_tables, context_lens, n_valid,
            layer=layer)[0]
    kw = dict(scale=scale, layer=layer, value_width=value_width)
    if q.shape[1] == 1:
        out = get_op("paged_decode_attention")(
            q[:, 0], pool, None, block_tables, context_lens, **kw)[:, None]
    else:
        out = get_op("paged_prefill_attention")(
            q, pool, None, block_tables, context_lens, n_valid, **kw)
    return out, LayerPool(pool, None, layer)


# --------------------------------------------------------------------------- #
# learned token selection (``ops/pallas/paged_sparse_attention.py``): a third
# pool of index keys, and attention over each row's selected tokens alone
# --------------------------------------------------------------------------- #
def init_index_pool(num_layers: int, num_blocks: int, block_size: int,
                    index_head_dim: int, dtype=jnp.bfloat16):
    """The index keys' pool, ``cache["kI"]``: one key head of
    ``index_head_dim`` values a token, its blocks the block tables' own, as
    many tokens a pool row as fill a lane tile (the op's module docstring)."""
    from ..ops.pallas.paged_sparse_attention import index_pool_shape

    return jnp.zeros(index_pool_shape(num_layers, num_blocks, block_size,
                                      index_head_dim), dtype)


def sparse_attention_step(q, k, v, q_idx, k_idx, w_idx, k_cache, v_cache,
                          i_cache, block_tables, context_lens, valid, *,
                          topk: int, scale=None) -> Tuple:
    """:func:`paged_attention_step` of a layer whose indexer chooses what
    each row may read. Beside q, k and v (written and laid out as there):
    ``q_idx [b, t, H, d]`` and ``k_idx [b, t, d]``, the row's roped index
    queries and index key, ``w_idx [b, t, H]`` the index heads' weights, and
    ``i_cache`` the index pool's entry. The step writes K, V and the index
    key, scores every cached token of each row's table (``attn_index``),
    finds each row's exact ``topk`` threshold (``attn_select``) and attends
    over the tokens above it. A :class:`MixedCall` splits as it does there:
    the chunk's rows score and select over the chunk's table, each decode
    row over its own. Returns ``(out, k_cache, v_cache, i_cache)``."""
    if isinstance(block_tables, MixedCall):
        call = block_tables
        parts = [call.split(a) for a in (q, k, v, q_idx, k_idx, w_idx)]
        chunk_rows = (jnp.arange(parts[0][1].shape[1])
                      < call.chunk_valid)[None]
        out_c, k_cache, v_cache, i_cache = sparse_attention_step(
            *(p[1] for p in parts), k_cache, v_cache, i_cache,
            call.chunk_table[None], call.chunk_ctx[None], chunk_rows,
            topk=topk, scale=scale)
        out_d, k_cache, v_cache, i_cache = sparse_attention_step(
            *(p[0] for p in parts), k_cache, v_cache, i_cache, call.tables,
            call.lens, call.active[:, None], topk=topk, scale=scale)
        return call.join(out_d, out_c), k_cache, v_cache, i_cache
    from ..ops import pallas as _pallas_ops  # noqa: F401 (registers)
    from ..ops.pallas import paged_sparse_attention as sparse  # registers
    from ..ops.registry import get_op

    b, t, nh, hd = q.shape
    layer = k_cache.layer
    n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)
    with jax.named_scope("kv_write"):
        k_pool, v_pool, *_ = get_op("paged_kv_write")(
            k, v, k_cache.pool, v_cache.pool, block_tables, context_lens,
            n_valid, layer=layer)
    nkv, bs = k_pool.shape[-3:-1]
    rows = 8 if t == 1 else sparse.prefill_rows(
        t, nh, nkv, hd, bs, block_tables.shape[1])
    with jax.named_scope("attn_index"):
        i_pool = get_op("paged_index_write")(
            k_idx, i_cache.pool, block_tables, context_lens, n_valid,
            layer=layer)
        idx = get_op("paged_index_scores")(
            q_idx, w_idx, i_pool, block_tables, context_lens, n_valid,
            layer=layer, rows=rows)
    with jax.named_scope("attn_select"):
        # a padded row's own position is -1: it selects from nothing
        q_abs = context_lens[:, None] + jnp.arange(rows)[None, :]
        q_abs = jnp.where(jnp.arange(rows)[None, :] < n_valid[:, None],
                          q_abs, -1)
        width = 1 if t == 1 else rows
        tau, cut = get_op("paged_sparse_select")(
            idx[:, :width].reshape(b * width, -1),
            q_abs[:, :width].reshape(-1), topk=topk)
        tau, cut = tau.reshape(b, width), cut.reshape(b, width)
    if t == 1:
        out = get_op("paged_sparse_decode_attention")(
            q[:, 0], k_pool, v_pool, idx, tau[:, 0], cut[:, 0], block_tables,
            context_lens, scale=scale, layer=layer)[:, None]
    else:
        out = get_op("paged_sparse_prefill_attention")(
            q, k_pool, v_pool, idx, tau, cut, block_tables, context_lens,
            n_valid, scale=scale, layer=layer)
    return (out, LayerPool(k_pool, None, layer), LayerPool(v_pool, None, layer),
            LayerPool(i_pool, None, layer))
