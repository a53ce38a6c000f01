"""Kinds of KV state (ISSUE 42): ``inference/ragged.py`` ``StateManager``
with a window kind beside the full kind - blocks given back exactly when
they lie wholly behind the window, never one the kernels can read (what was
given back is poisoned with NaN and the kernels, interpreted, are compared
with the gathered reference over a pool nothing was taken from), both
allocators under ``debug_check``, admission and the never-preempt guard
counting both -, the engine's pools and span arguments, and every refusal
by name.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.inference.engine_v2 import KVKindError, build_engine_v2
from deepspeed_tpu.inference.ragged import StateManager, WindowKind
from deepspeed_tpu.models import cohere2_moe
from deepspeed_tpu.ops.pallas import paged_attention as pa


def manager(window=16, bs=4, call=8, slots=2, width=32, blocks=80):
    kind = WindowKind.sized("window", window, slots, call, bs)
    return StateManager(slots, blocks, bs, width, window_kinds=(kind,)), kind


def test_the_pool_follows_from_slots_window_chunk_and_block():
    """The cell's numbers: a 4096 window, 512-token chunks and 32-token
    blocks are 145 blocks a slot, 16 slots and the trash block 2321."""
    kind = WindowKind.sized("window", 4096, 16, 512, 32)
    assert (kind.blocks_per_seq, kind.num_blocks) == (145, 16 * 145 + 1)
    st, kind = manager()
    assert kind.blocks_per_seq == (16 + 8) // 4 + 1
    assert st.table_width == st.max_blocks_per_seq + 1 + kind.blocks_per_seq


def test_one_kind_is_what_it_was():
    """No window kind: the table is ``max_blocks_per_seq`` wide and nothing
    else is kept."""
    st = StateManager(2, 20, 4, 8)
    d = st.admit(0, 10)
    assert st.table_width == 8 and st.block_table(d).shape == (8,)
    assert d.window_blocks == {} and st.window_kinds == ()
    st.extend(d, 3)
    st.retire(0)
    st.debug_check()


@pytest.mark.parametrize("window,bs,call", [(16, 4, 8), (16, 4, 1),
                                            (12, 8, 8), (32, 8, 16),
                                            (10, 4, 6)])
def test_blocks_go_back_exactly_when_wholly_behind_the_window(window, bs,
                                                              call):
    """A sequence grown call by call (chunks, then single tokens): before
    each call the window kind holds exactly the blocks that contain a
    position some row of the call can read - ``first row - window + 1`` to
    the call's last row -, its table segment is the count of those given
    back and then these, the first live one first, and the trash block after
    them; it never holds more than ``blocks_per_seq``; the full kind keeps
    everything."""
    st, kind = manager(window, bs, call, width=64, blocks=200)
    total = 6 * window + 3
    d = st.admit(7, total)
    width = st.max_blocks_per_seq
    seen = 0
    while seen < total + 12:
        n = min(call, total - seen) if seen < total else 1
        st.extend(d, n)
        table = st.block_table(d)
        held = d.window_blocks["window"]
        live = np.nonzero(held)[0]
        lo = max(0, seen - window + 1) // bs
        hi = (seen + n - 1) // bs
        assert list(live) == list(range(lo, hi + 1)), (seen, n)
        assert len(live) <= kind.blocks_per_seq
        assert table[width] == lo and table.shape == (st.table_width,)
        assert list(table[width + 1:width + 1 + len(live)]) == held[lo:]
        assert not table[width + 1 + len(live):].any()
        assert np.count_nonzero(table[:width]) == len(d.blocks) \
            >= -(-(seen + n) // bs)
        seen += n
        d.seen_tokens = seen
        st.debug_check()
    assert st.window_blocks_released == max(0, seen - 1 - window + 1) // bs
    assert st.window_blocks_live("window") == len(live)
    st.retire(7)
    st.debug_check()
    assert st.window_blocks_live("window") == 0


@pytest.mark.parametrize("hd", [32, 128])
@pytest.mark.parametrize("op", ["decode", "prefill"])
def test_the_kernels_never_read_what_was_given_back(op, hd):
    """The Mosaic kernels, interpreted, over a window pool in which every
    block the manager has given back or never claimed - the trash block
    among them - is NaN: their output is the gathered reference's over a
    pool nothing was ever taken from. (A NaN read under a mask would still
    be a NaN: the walk must not fetch the page at all.) Heads of 32 walk the
    grid of ``BlockSpec`` pages, heads of 128 fetch their own pages - the
    multi-token walk's begin past page 0 once blocks are given back."""
    window, bs, call, nkv, g = 16, 4, 8, 2, 2
    assert pa._fetches_pages(hd, False) == (hd == 128)
    st, kind = manager(window, bs, call, slots=1, width=24, blocks=40)
    rng = np.random.default_rng(0)
    total = 61
    k_all = jnp.asarray(rng.normal(size=(1, total, nkv, hd)), jnp.float32)
    v_all = jnp.asarray(rng.normal(size=(1, total, nkv, hd)), jnp.float32)
    q_all = jnp.asarray(rng.normal(size=(1, total, nkv * g, hd)), jnp.float32)
    clean = [jnp.zeros((40, nkv, bs, hd), jnp.float32) for _ in "kv"]
    pools = [jnp.zeros((kind.num_blocks, nkv, bs, hd), jnp.float32)
             for _ in "kv"]
    d = st.admit(0, total)
    width = st.max_blocks_per_seq
    full = jnp.asarray(st.block_table(d)[None, :width])
    seen = 0
    while seen < total:
        n = min(call, total - seen) if op == "prefill" or seen < 40 else 1
        st.extend(d, n)
        segment = st.block_table(d)[width:]
        table = jnp.asarray(segment[None, 1:])
        ctx, cnt = jnp.asarray([seen], jnp.int32), jnp.asarray([n], jnp.int32)
        # the window layers' lengths count from the first live block
        near = ctx - int(segment[0]) * bs
        rows = slice(seen, seen + n)
        clean = pa.paged_kv_write_xla(k_all[:, rows], v_all[:, rows], *clean,
                                      full, ctx, cnt)[:2]
        pools = pa.paged_kv_write_xla(k_all[:, rows], v_all[:, rows], *pools,
                                      table, near, cnt)[:2]
        held = np.zeros(kind.num_blocks, bool)
        held[[b for b in d.window_blocks["window"] if b]] = True
        poisoned = [jnp.where(held[:, None, None, None], p, jnp.nan)
                    for p in pools]
        if n == 1:
            got = pa.paged_decode_attention(q_all[:, seen], *poisoned, table,
                                            near, window=window)
            want = pa.paged_decode_attention_xla(q_all[:, seen], *clean, full,
                                                 ctx, window=window)
        else:
            got = pa.paged_prefill_attention(q_all[:, rows], *poisoned, table,
                                             near, cnt, window=window)
            want = pa.paged_prefill_attention_xla(q_all[:, rows], *clean,
                                                  full, ctx, cnt,
                                                  window=window)
        assert bool(jnp.isfinite(got).all()), seen
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
        seen += n
        d.seen_tokens = seen
    assert st.window_blocks_released > 0


def test_debug_check_is_over_both_allocators():
    st, kind = manager()
    d = st.admit(0, 40)
    st.extend(d, 8)
    d.seen_tokens = 8
    st.debug_check()
    mine = d.window_blocks["window"]
    kept, mine[0] = mine[0], 0          # a block the window needs, dropped
    with pytest.raises(AssertionError, match="given back and then the held"):
        st.debug_check()
    mine[0] = kept
    st.window_allocators["window"]._free.append(kept)    # held AND free
    with pytest.raises(AssertionError, match="window"):
        st.debug_check()


def test_admission_and_the_guard_count_both_kinds():
    """A free slot and full-kind room do not admit where the window kind
    has none, and the never-preempt guard's shortfall counts the window
    kind's blocks the next tokens need."""
    st, kind = manager(window=16, bs=4, call=8, slots=2, blocks=80)
    alloc = st.window_allocators["window"]
    assert st.can_admit(40)
    taken = alloc.allocate(alloc.free_blocks - 1)
    assert not st.can_admit(40) and st.can_admit(0)     # one block is left
    alloc.free(taken)
    a = st.admit(0, 4)
    st.extend(a, 4)
    a.seen_tokens = 4                   # its next token opens a new block
    assert st.growth_blocks_short([a], n=1) == 0
    taken = alloc.allocate(alloc.free_blocks)
    assert st.growth_blocks_short([a], n=1) == 1
    assert st.growth_blocks_short([a], n=5) == 2
    with pytest.raises(MemoryError):
        st.extend(a, 1)
    alloc.free(taken)
    st.extend(a, 1)
    st.debug_check()


# --- refusals, each by name ------------------------------------------------- #
def test_the_manager_refuses_what_cannot_work_over_a_given_back_block():
    kind = WindowKind.sized("window", 16, 2, 8, 4)
    with pytest.raises(KVKindError, match="inference.prefix_cache"):
        StateManager(2, 40, 4, 16, prefix_cache=True, window_kinds=(kind,))
    st, _ = manager()
    d = st.admit(0, 40)
    with pytest.raises(KVKindError, match="fork"):
        st.fork(0, 1)
    with pytest.raises(KVKindError, match="host_spill"):
        st.enable_host_spill(object(), None, None)
    with pytest.raises(KVKindError, match="adopt_block"):
        st.adopt_block(b"x")


def test_truncate_is_refused_past_the_window_and_works_inside_it():
    st, _ = manager(window=16, bs=4, call=8)
    d = st.admit(0, 60)
    for seen in range(0, 40, 8):
        st.extend(d, 8)
        d.seen_tokens = seen + 8
    st.extend(d, 1)                       # gives back what 40 cannot read
    assert d.window_blocks["window"][:6] == [0] * 6
    d.tokens = list(range(40))
    st.truncate(d, 39)                    # 39's window starts at 24: held
    assert d.seen_tokens == 39
    st.debug_check()
    with pytest.raises(KVKindError, match="truncate.*gave back"):
        st.truncate(d, 30)                # 30's starts at 15: given back


def engine(**config):
    cfg = cohere2_moe.Cohere2MoeConfig.tiny()
    params = cohere2_moe.init(cfg, jax.random.PRNGKey(0))
    return cfg, build_engine_v2(cohere2_moe, cfg, params, config={
        "dtype": "float32", "prefill_bucket": 8, "split_prefill_chunk": 8,
        "ragged": {"max_tracked_sequences": 2, "max_ragged_batch_size": 2,
                   "memory_config_blocks": 70, "block_size": 4}, **config})


@pytest.mark.parametrize("feature,config", [
    ("inference.prefix_cache", {"prefix_cache": {"enabled": True}}),
    ("inference.prefix_cache.host_spill",
     {"prefix_cache": {"enabled": False, "host_spill": True}}),
    ("inference.speculative", {"speculative": {"enabled": True}}),
    ("inference.kv_quant", {"kv_quant": {"enabled": True}}),
])
def test_the_engine_refuses_at_configuration(feature, config):
    with pytest.raises(KVKindError) as e:
        engine(**config)
    assert str(e.value).startswith(feature + " is not available")


@pytest.mark.parametrize("call", ["fork", "export_kv_blocks",
                                  "import_kv_blocks"])
def test_the_engine_refuses_at_the_call(call):
    cfg, eng = engine()
    eng.put(0, list(range(1, 7)))
    with pytest.raises(KVKindError, match=call):
        {"fork": lambda: eng.fork(0, 1),
         "export_kv_blocks": lambda: eng.export_kv_blocks(0),
         "import_kv_blocks": lambda: eng.import_kv_blocks([b"h"], [{}]),
         }[call]()


def test_the_engine_sizes_both_pools_and_says_what_each_kind_reads():
    """``memory_config_blocks`` is the full kind's count, the window kind's
    follows from slots, window, chunk and block; ``decode_step`` and
    ``prefill_chunk`` carry ONE layer's KV tokens of each kind."""
    cfg, eng = engine(trace={"enabled": True})
    kind, = eng.state.window_kinds
    assert kind == WindowKind.sized("window", cfg.sliding_window, 2, 8, 4)
    assert eng.cache["k"].shape[:2] == (1, 70)
    assert eng.cache["k_window"].shape[:2] == (3, kind.num_blocks)
    assert eng._slot_tables.shape[1] == eng.state.table_width \
        == eng.state.max_blocks_per_seq + 1 + kind.blocks_per_seq
    eng.put_split(0, list(range(1, 52)))           # 51 tokens: 7 chunks
    while eng.state.seqs[0].prefilling:
        eng.step()
    for _ in range(3):
        eng.step()
        eng.state.debug_check()
    spans = [e for e in eng.tracer.events() if e["ph"] == "X"]
    chunks = [e["args"] for e in spans if e["name"] == "prefill_chunk"]
    assert len(chunks) == 7
    for a in chunks:
        end = a["ctx"] + a["tokens"]
        assert a["kv_tokens_full"] == end
        assert a["kv_tokens_window"] == end - max(a["ctx"] - 16 + 1, 0)
    steps = [e["args"] for e in spans if e["name"] == "decode_step"
             and e["args"]["batch"]]
    assert steps and all(a["kv_tokens_window"] == 16 for a in steps)
    assert [a["kv_tokens_full"] for a in steps] == [
        a["kv_tokens"] for a in steps]
