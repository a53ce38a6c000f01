"""Tiered memory subsystem tests (docs/memory.md): placement primitives,
TieredStore offload/restore/prefetch with measured transfer overlap,
default-OFF bit-identity pins (train + serving), optimizer host-offload
parity, KV host-spill restore parity + hit-rate acceptance, spill-seam
hardening (exactly-once hash drop, no over-commit), eviction-pressure soak
with debug_check invariants, and the schema/hub/report telemetry surface."""

import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.memory import (HostKVPool, TieredStore, TransferWorker,
                                  move_tree, offloaded_memory_kinds,
                                  to_device, to_host)
from deepspeed_tpu.telemetry.schema import (MEMORY_TIER_SERIES,
                                            validate_events)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": jnp.asarray(rng.standard_normal((16, 8)), jnp.float32),
            "b": {"m": jnp.asarray(rng.standard_normal((32,)), jnp.float32),
                  "v": jnp.asarray(rng.integers(0, 100, (4, 4)), jnp.int32)}}


# --------------------------------------------------------------------------- #
# placement + store primitives
# --------------------------------------------------------------------------- #
def test_placement_roundtrip_exact():
    """Host-tier moves report the logical kind everywhere and roundtrip
    bit-exactly. Under the installed JAX the CPU backend has a pinned host
    space of its own, so the moves are real memory-kind moves here too."""
    tree = _tree()
    host = move_tree(tree, "host")
    assert offloaded_memory_kinds(host) == {"pinned_host"}
    assert all(l.sharding.memory_kind == "pinned_host"
               for l in jax.tree.leaves(host))
    back = move_tree(host, "device")
    assert offloaded_memory_kinds(back) == {"device"}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype and a.sharding == b.sharding
    # unpinned variant reports its own kind
    assert offloaded_memory_kinds(
        move_tree(tree, "host", pin=False)) == {"unpinned_host"}


def test_in_jit_annotations_keep_the_value():
    x = jnp.arange(8.0)
    out = jax.jit(lambda t: to_device(to_host(t)) * 2.0)(x)
    np.testing.assert_array_equal(np.asarray(out), np.arange(8.0) * 2.0)
    # eager forms work too (concrete moves, not annotations)
    np.testing.assert_array_equal(np.asarray(to_device(to_host(x))),
                                  np.arange(8.0))


def test_store_offload_restore_roundtrip_exact():
    store = TieredStore()
    tree = _tree(1)
    total = sum(l.nbytes for l in jax.tree.leaves(tree))
    off = store.offload(tree, "host")
    assert offloaded_memory_kinds(off) == {"pinned_host"}
    assert store.resident_bytes("host") == total
    back = store.restore(off)
    assert offloaded_memory_kinds(back) == {"device"}
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert store.resident_bytes("host") == 0          # accounting returns to 0
    assert store.stats["transfer_d2h_bytes"] == total
    assert store.stats["transfer_h2d_bytes"] == total
    store.close()


def test_store_file_tier_roundtrip(tmp_path):
    from deepspeed_tpu.runtime.swap_tensor.swapper import SwappedTensorMeta

    store = TieredStore(nvme_dir=str(tmp_path))
    tree = _tree(2)
    off = store.offload(tree, "file", name="opt")
    leaves = jax.tree.leaves(off)
    assert all(isinstance(l, SwappedTensorMeta) for l in leaves)
    files = list(tmp_path.rglob("*.swp"))
    assert len(files) == len(leaves)
    assert store.resident_bytes("file") > 0
    back = store.restore(off)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert store.resident_bytes("file") == 0
    assert not list(tmp_path.rglob("*.swp"))          # consumed on restore
    store.close()


def test_transfer_worker_overlap_accounting_fake_clock():
    """Overlap is measured, not asserted: with an injected clock, a transfer
    running inside a compute window counts as hidden, one outside does not,
    and overlap_frac is their exact ratio."""
    state = {"t": 0.0}
    w = TransferWorker(clock=lambda: state["t"])

    def advance(dt):
        def job():
            state["t"] += dt
        return job

    w.compute_begin()                       # window opens at t=0
    w.submit(advance(2.0)).result()         # 2s transfer inside the window
    w.drain()
    w.compute_end()                         # window [0, 2]
    w.submit(advance(3.0)).result()         # 3s transfer outside any window
    w.drain()
    assert w.busy_s == pytest.approx(5.0)
    assert w.overlap_s == pytest.approx(2.0)
    assert w.overlap_frac() == pytest.approx(2.0 / 5.0)
    w.close()


def test_prefetch_hit_and_miss_ordering():
    """A wait() that finds every transfer finished counts a HIT (the copy
    was hidden); a wait() that must block counts a MISS — ordering pinned
    with a gate job holding the FIFO worker."""
    store = TieredStore()
    off = store.offload(_tree(3), "host")
    store.worker.drain()
    h = store.prefetch(off)
    store.worker.drain()                    # transfers complete before wait
    assert h.ready()
    h.wait()
    assert store.stats["prefetch_hits"] == 1
    assert store.stats["prefetch_misses"] == 0

    off2 = store.offload(_tree(4), "host")
    store.worker.drain()
    gate = threading.Event()
    store.worker.submit(lambda: gate.wait(10))   # holds the FIFO
    h2 = store.prefetch(off2)
    assert not h2.ready()
    threading.Timer(0.05, gate.set).start()
    h2.wait()                               # blocked on the gated transfers
    assert store.stats["prefetch_misses"] == 1
    with pytest.raises(RuntimeError):
        h2.wait()                           # single-consumption pin
    store.close()


def test_hostkvpool_lru_cap_and_accounting():
    pool = HostKVPool(max_blocks=2)
    pool.put(b"h1", [np.ones((4,), np.float32)])
    pool.put(b"h2", [np.ones((4,), np.float32) * 2])
    pool.put(b"h3", [np.ones((4,), np.float32) * 3])
    assert len(pool) == 2 and b"h1" not in pool       # LRU evicted
    assert pool.stats["spill_evictions"] == 1
    assert pool.spilled_bytes == 32
    np.testing.assert_array_equal(pool.get(b"h3")[0], np.full((4,), 3.0))
    assert pool.pop(b"h2") is not None
    assert pool.spilled_bytes == 16 and len(pool) == 1


# --------------------------------------------------------------------------- #
# training: default-OFF pin + optimizer host-offload
# --------------------------------------------------------------------------- #
def _train_engine(tiering: bool):
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.runtime.engine import ModelSpec

    mesh_lib.set_mesh(None)

    def loss_fn(params, batch):
        pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    spec = ModelSpec(
        loss_fn=loss_fn,
        init_fn=lambda k: {"w1": jax.random.normal(k, (32, 32)) * 0.1,
                           "w2": jax.random.normal(k, (32, 32)) * 0.1},
        pipeline_capable=False)
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 2},
           "steps_per_print": 0}
    if tiering:
        cfg["memory"] = {"tiering": {"enabled": True,
                                     "optimizer_tier": "host"}}
    engine, *_ = dst.initialize(model=spec, config=cfg,
                                rng=jax.random.PRNGKey(7))
    return engine


def _batch():
    rng = np.random.RandomState(3)
    return {"x": rng.randn(8, 32).astype(np.float32),
            "y": np.zeros((8, 32), np.float32)}


def test_train_default_off_is_inert(devices8):
    """Default config: the tiered path never engages — no transfer worker
    thread, zero tier stats, zero Memory/tier/* telemetry, and the fused
    train step is used (the pre-tiering program)."""
    e = _train_engine(False)
    try:
        batch = _batch()
        e.train_batch(batch)
        assert e._tiered_opt is False
        assert e.tiered_store.worker._thread is None   # never started
        assert all(v == 0 for v in e.tiered_store.stats.values())
        assert e.telemetry.memory_tier_values == {}
        assert offloaded_memory_kinds(e.state.opt_state) == {"device"}
    finally:
        e.destroy()


def test_train_optimizer_host_offload_loss_parity_and_residency(devices8):
    """Optimizer host tier: losses match the in-HBM engine EXACTLY (the
    roundtrip is bit-exact and the step math unchanged), the opt state is
    host-resident between steps, prefetches hide, and the Memory/tier
    telemetry validates against the closed schema."""
    batch = _batch()
    e0 = _train_engine(False)
    base = [float(e0.train_batch(batch).loss) for _ in range(4)]
    e0.destroy()
    e1 = _train_engine(True)
    try:
        tier = [float(e1.train_batch(batch).loss) for _ in range(4)]
        assert base == tier, (base, tier)
        assert offloaded_memory_kinds(e1.state.opt_state) == {"pinned_host"}
        assert all(l.sharding.memory_kind == "pinned_host"
                   for l in jax.tree.leaves(e1.state.opt_state))
        st = e1.tiered_store.stats
        assert st["prefetch_hits"] + st["prefetch_misses"] == 4
        assert st["transfer_h2d_bytes"] > 0
        assert 0.0 <= e1.tiered_store.overlap_frac() <= 1.0
        events = e1.tiered_store.events(4)
        assert validate_events(events) == []
        # the hub drained the same series per step
        assert e1.telemetry.memory_tier_values.get(
            "Memory/tier/prefetch_hits", 0) > 0
        # still trains after an offload_states roundtrip on the same store
        e1.offload_states()
        e1.reload_states()
        out = e1.train_batch(batch)
        assert np.isfinite(float(out.loss))
    finally:
        e1.destroy()


def test_prefetch_scan_host_tier_compose_is_identity(devices8):
    """memory.tiering.param_tier=host rides the layer-prefetch pipeline: on
    a single-memory backend the composed scan is the plain lax.scan bit for
    bit (the to_device copy-in is identity), so the compose can never
    change numerics where there is no host space to win from."""
    from jax import lax

    from deepspeed_tpu.comm import overlap

    layers = {"w": jnp.asarray(
        np.random.default_rng(0).standard_normal((4, 8, 8)), jnp.float32)}

    def body(x, layer):
        y = jnp.tanh(x @ layer["w"])
        return y, jnp.sum(y)

    init = jnp.ones((2, 8), jnp.float32)
    ref = lax.scan(body, init, layers)
    overlap.configure_layer_prefetch(True, depth=1, host_tier=True)
    try:
        out = overlap.prefetch_scan(body, init, layers)
    finally:
        overlap.reset_layer_prefetch()
    np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(out[0]))
    np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(out[1]))


def test_superoffload_registers_host_tier_bytes():
    from deepspeed_tpu.runtime.superoffload import SuperOffloadOptimizer

    store = TieredStore()
    so = SuperOffloadOptimizer({"w": jnp.zeros((64,))}, lr=0.1, store=store)
    assert store.resident_bytes("host") == 3 * 64 * 4   # masters + 2 moments
    so.step({"w": jnp.ones((64,))})
    so._drain(block=True)
    assert store.stats["transfer_d2h_bytes"] >= 64 * 4  # the grad stream
    so.close()
    assert store.resident_bytes("host") == 0
    store.close()


# --------------------------------------------------------------------------- #
# serving: KV host-spill
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def tiny_llama():
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(max_seq_len=256)
    return cfg, llama.init(cfg, jax.random.PRNGKey(0))


def _serving_engine(tiny_llama, spill: bool, retained: int = 2,
                    blocks: int = 64):
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.inference import build_engine_v2
    from deepspeed_tpu.models import llama

    cfg, params = tiny_llama
    mesh_lib.set_mesh(None)
    return build_engine_v2(
        llama, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 16,
                "prefix_cache": {"enabled": True,
                                 "max_retained_blocks": retained,
                                 "host_spill": spill},
                "ragged": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 4,
                           "memory_config_blocks": blocks,
                           "block_size": 16}})


def test_serving_spill_off_is_inert(tiny_llama):
    eng = _serving_engine(tiny_llama, spill=False)
    assert eng._kv_spill is None
    assert eng.state.spill_pool is None
    assert ("spill_write",) not in eng._paged_fns


def test_kv_spill_restore_token_parity_and_hit_rate(tiny_llama):
    """The acceptance pin: a working set larger than max_retained_blocks
    sees a HIGHER prefix hit rate with spill ON than OFF, with
    token-identical streams (restored KV is a bit-exact copy)."""
    from deepspeed_tpu.inference.sampling import SamplingParams

    sp = SamplingParams(greedy=True)
    rng = np.random.RandomState(0)
    cfg = tiny_llama[0]
    prompts = [list(rng.randint(0, cfg.vocab_size, 48)) for _ in range(4)]

    def run(spill):
        eng = _serving_engine(tiny_llama, spill=spill)
        streams = {}
        for round_ in ("first", "second"):
            for i, p in enumerate(prompts):
                uid = i if round_ == "first" else 100 + i
                eng.put(uid, p, sp)
                for _ in range(4):
                    eng.step(sp)
                streams[(round_, i)] = list(eng.state.seqs[uid].generated)
                eng.finish(uid)
        eng.state.debug_check()
        return streams, dict(eng.state.prefix_stats), eng

    s_off, st_off, _ = run(False)
    s_on, st_on, eng = run(True)
    assert s_off == s_on, "spill must be token-identical"
    assert st_on["restores"] > 0 and st_on["spills"] > 0
    assert st_on["hit_tokens"] > st_off["hit_tokens"]
    assert st_on["restored_tokens"] == st_on["restores"] * 16
    # telemetry surface: registered serving + memory-tier series, validated
    events = eng.prefix_cache_events(1)
    assert validate_events(events) == []
    names = {n for n, _, _ in events}
    assert "Serving/prefix_cache/restores" in names
    assert "Serving/prefix_cache/spilled_blocks" in names


def test_spill_then_evict_drops_hash_exactly_once():
    """Regression (spill-seam hardening): eviction spills the block's KV
    under its chain hash and drops the RESIDENT index entry exactly once —
    a hash is resident-canonical or host-spilled, never both; a restore
    moves it back exactly once."""
    from deepspeed_tpu.inference.ragged import StateManager

    kv = {}
    sm = StateManager(max_sequences=4, num_blocks=8, block_size=4,
                      max_blocks_per_seq=4, prefix_cache=True,
                      max_retained_blocks=1)
    pool = HostKVPool()
    sm.enable_host_spill(pool,
                         reader=lambda b: [kv.get(b, np.zeros(1)).copy()],
                         writer=lambda b, data: kv.__setitem__(b, data[0]))
    # two sequences with 4-token (one full block) prompts + decode block
    d1, _ = sm.admit_prompt(1, [1, 2, 3, 4, 9])
    d1.seen_tokens = 5
    kv[d1.blocks[0]] = np.full((1,), 11.0)
    sm.mark_filled(d1)
    h1 = d1.block_hashes[0]
    sm.retire(1)                       # block retained (cap 1)
    assert sm.index._by_hash.get(h1) is not None and h1 not in pool
    d2, _ = sm.admit_prompt(2, [5, 6, 7, 8, 9])
    d2.seen_tokens = 5
    kv[d2.blocks[0]] = np.full((1,), 22.0)
    sm.mark_filled(d2)
    sm.retire(2)                       # over cap → h1's block evicts + spills
    assert h1 in pool and h1 not in sm.index._by_hash
    assert sm.prefix_stats["spills"] == 1
    sm.debug_check()
    # restore on re-admission: hash moves back, pool entry consumed once
    d3, cached = sm.admit_prompt(3, [1, 2, 3, 4, 9])
    assert cached == 4 and sm.prefix_stats["restores"] == 1
    assert h1 not in pool and sm.index._by_hash[h1] == d3.blocks[0]
    np.testing.assert_array_equal(kv[d3.blocks[0]], np.full((1,), 11.0))
    sm.debug_check()


def test_restore_into_full_pool_triggers_eviction_not_overcommit():
    """Regression (spill-seam hardening): restoring a spilled block when
    the free list is empty must obtain capacity through the NORMAL
    eviction path (evicting retained LRU blocks — which themselves spill),
    and degrade to a plain miss when every block is live — never
    over-commit or corrupt the accounting."""
    from deepspeed_tpu.inference.ragged import StateManager

    kv = {}
    sm = StateManager(max_sequences=4, num_blocks=7, block_size=4,
                      max_blocks_per_seq=4, prefix_cache=True,
                      max_retained_blocks=0)   # retain nothing on retire
    pool = HostKVPool()
    sm.enable_host_spill(pool,
                         reader=lambda b: [kv.get(b, np.zeros(1)).copy()],
                         writer=lambda b, data: kv.__setitem__(b, data[0]))
    # cap 0 still spills at eviction time inside _release_block? No: cap 0
    # drops unindexed; use cap 1 semantics instead by filling + evicting.
    sm.index.max_retained = 1
    d1, _ = sm.admit_prompt(1, [1, 2, 3, 4, 9])
    d1.seen_tokens = 5
    kv[d1.blocks[0]] = np.full((1,), 1.0)
    sm.mark_filled(d1)
    sm.retire(1)
    d2, _ = sm.admit_prompt(2, [5, 6, 7, 8, 9])
    d2.seen_tokens = 5
    kv[d2.blocks[0]] = np.full((1,), 2.0)
    sm.mark_filled(d2)
    sm.retire(2)                      # evicts + spills prompt-1's block
    assert len(pool) == 1
    # fill the pool with LIVE sequences: 6 usable blocks, 4 live + 1
    # retained; admitting a spilled-prefix prompt must evict the retained
    # block (spilling it) to make room for the restore — normal path
    d3, _ = sm.admit_prompt(3, [10, 11, 12, 13, 14, 15, 16])  # 2+1 blocks
    d4, cached = sm.admit_prompt(4, [1, 2, 3, 4, 9])          # restore hit
    assert cached == 4 and sm.prefix_stats["restores"] == 1
    sm.debug_check()                  # free+live+retained == pool exactly
    # now EVERY block is live: a further spilled-prefix admission cannot
    # restore — it must degrade to a miss (no over-commit), and with no
    # slots/blocks the admission itself raises cleanly
    assert sm.allocator.free_blocks == 0 and sm.retained_blocks == 0
    with pytest.raises(MemoryError):
        sm.admit(9, 20)
    sm.debug_check()


def test_eviction_pressure_soak_with_spill():
    """Randomized admit/extend/retire churn with the spill tier armed:
    debug_check invariants (including hash-disjointness of pool vs index)
    hold at every step, and spills/restores actually happen."""
    from deepspeed_tpu.inference.ragged import StateManager

    rng = np.random.RandomState(42)
    kv = {}
    sm = StateManager(max_sequences=6, num_blocks=24, block_size=4,
                      max_blocks_per_seq=6, prefix_cache=True,
                      max_retained_blocks=3)
    pool = HostKVPool(max_blocks=32)
    sm.enable_host_spill(pool,
                         reader=lambda b: [kv.get(b, np.zeros(1)).copy()],
                         writer=lambda b, data: kv.__setitem__(b, data[0]))
    prompts = [list(rng.randint(0, 50, 12)) for _ in range(8)]
    uid = 0
    live = []
    for it in range(300):
        op = rng.rand()
        if op < 0.5 and len(live) < 5:
            p = prompts[rng.randint(len(prompts))]
            if sm.can_admit(len(p)):
                uid += 1
                d, cached = sm.admit_prompt(uid, p)
                d.seen_tokens = len(p)
                for i, b in enumerate(d.blocks[:len(p) // 4]):
                    kv.setdefault(b, np.full((1,), float(b)))
                sm.mark_filled(d)
                live.append(uid)
        elif live:
            u = live.pop(rng.randint(len(live)))
            sm.retire(u)
        sm.debug_check()
    assert sm.prefix_stats["spills"] > 0
    assert sm.prefix_stats["restores"] > 0


# --------------------------------------------------------------------------- #
# telemetry surface
# --------------------------------------------------------------------------- #
def test_schema_memory_tier_registry_closed():
    store = TieredStore()
    store.offload(_tree(5), "host")
    store.worker.drain()
    events = store.events(1)
    assert validate_events(events) == []
    assert all(n in MEMORY_TIER_SERIES for n, _, _ in events)
    # unregistered tier series fail validation; other Memory/* stay open
    assert validate_events([("Memory/tier/bogus_series", 1.0, 0)])
    assert validate_events([("Memory/bytes_in_use", 1.0, 0)]) == []
    # the serving kv gauges are registered
    for m in ("kv_spilled_blocks", "kv_spilled_bytes", "kv_spills",
              "kv_restores"):
        assert f"Memory/tier/{m}" in MEMORY_TIER_SERIES
    store.close()


def test_hub_memory_tier_events_and_metrics_snapshot():
    from deepspeed_tpu.runtime.config import parse_config
    from deepspeed_tpu.telemetry import TelemetryHub

    hub = TelemetryHub(parse_config({"train_batch_size": 8}))
    hub.memory_tier_event("kv_spilled_blocks", 3.0, step=1)
    store = TieredStore()
    store.offload(_tree(6), "host")
    store.worker.drain()
    hub.memory_tier_events(store, step=1)
    vals = hub.memory_tier_values
    assert vals["Memory/tier/kv_spilled_blocks"] == 3.0
    assert vals["Memory/tier/resident_bytes_host"] > 0
    rows = hub.metrics_snapshot()
    tier_rows = [r for r in rows if r[0].startswith("Memory/tier/")]
    assert tier_rows and all(r[2] == "gauge" for r in tier_rows)
    store.close()


def test_telemetry_report_memory_section(tmp_path):
    from deepspeed_tpu.monitor.monitor import JSONLMonitor

    class Cfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "job"

    mon = JSONLMonitor(Cfg())
    store = TieredStore()
    off = store.offload(_tree(7), "host")
    store.restore(off)
    mon.write_events(store.events(1))
    mon.write_events([("Memory/tier/kv_spilled_blocks", 5.0, 1),
                      ("Memory/tier/kv_spilled_bytes", 4096.0, 1),
                      ("Memory/tier/kv_spills", 7.0, 1),
                      ("Memory/tier/kv_restores", 2.0, 1),
                      ("Memory/bytes_in_use", 1e6, 1)])
    mon.close()
    store.close()
    script = os.path.join(REPO, "scripts", "telemetry_report.py")
    out = subprocess.run(
        [sys.executable, script, str(tmp_path / "job" / "events.jsonl"),
         "--memory"], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "overlap_frac" in out.stdout
    assert "KV host-spill pool" in out.stdout
    assert "prefetch" in out.stdout
    # --all includes the section too
    out_all = subprocess.run(
        [sys.executable, script, str(tmp_path / "job" / "events.jsonl"),
         "--all"], capture_output=True, text=True, timeout=60)
    assert out_all.returncode == 0, out_all.stderr
    assert "tiered memory" in out_all.stdout


def test_memory_tiering_config_parses():
    from deepspeed_tpu.runtime.config import parse_config

    cfg = parse_config({"train_batch_size": 8,
                        "memory": {"tiering": {"enabled": True,
                                               "optimizer_tier": "host",
                                               "pin_memory": False}}})
    assert cfg.memory.tiering.enabled
    assert cfg.memory.tiering.optimizer_tier == "host"
    assert cfg.memory.tiering.pin_memory is False
    assert cfg.memory.tiering.param_tier == "none"
    # default OFF
    d = parse_config({"train_batch_size": 8})
    assert d.memory.tiering.enabled is False
