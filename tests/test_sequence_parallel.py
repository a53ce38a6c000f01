"""Ulysses + ring attention tests (reference model:
``tests/unit/sequence_parallelism/test_ulysses.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import init_mesh
from deepspeed_tpu.ops.attention import attention
from deepspeed_tpu.sequence import DistributedAttention, ring_attention, ulysses_attention
from deepspeed_tpu.sequence.ring import (measure_ring_overlap,
                                         ring_attention_spmd,
                                         ring_block_pair_counts,
                                         zigzag_inverse_perm, zigzag_perm)


def _qkv(b=2, s=32, h=8, d=16, kv_heads=None, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv_heads or h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv_heads or h, d), jnp.float32)
    return q, k, v


def test_ulysses_matches_full_attention(devices8):
    init_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv()
    ref = attention(q, k, v, causal=True)
    out = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_ulysses_uneven_heads_fallback(devices8):
    init_mesh({"data": 1, "seq": 8})
    q, k, v = _qkv(h=6, kv_heads=6)  # 6 heads not divisible by sp=8
    ref = attention(q, k, v, causal=True)
    out = jax.jit(lambda q, k, v: ulysses_attention(q, k, v, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_distributed_attention_wrapper(devices8):
    init_mesh({"data": 2, "seq": 4})
    da = DistributedAttention()
    q, k, v = _qkv(seed=1)
    ref = attention(q, k, v, causal=True)
    out = jax.jit(lambda q, k, v: da(q, k, v, causal=True))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_full(devices8, causal):
    init_mesh({"data": 1, "seq": 8})
    q, k, v = _qkv(s=64, seed=2)
    ref = attention(q, k, v, causal=causal)
    out = ring_attention_spmd(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_gqa(devices8):
    init_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(s=32, h=8, kv_heads=2, seed=3)
    ref = attention(q, k, v, causal=True)
    out = ring_attention_spmd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_ring_attention_grads_flow(devices8):
    init_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(s=16, seed=4)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_spmd(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-4)


def test_sp1_mesh_passthrough(devices8):
    init_mesh({"data": 8})
    q, k, v = _qkv(seed=5)
    ref = attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(ulysses_attention(q, k, v, causal=True)), np.asarray(ref),
        rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ring_attention_spmd(q, k, v, causal=True)), np.asarray(ref),
        rtol=1e-6)


# --------------------------------------------------------------------------- #
# zigzag layout + overlap pipelining (docs/performance.md "Million-token
# context"): schedule balance, parity vs the dense oracle, and the
# silent-dense-fallback marker
# --------------------------------------------------------------------------- #
def test_ring_zigzag_schedule_balance():
    """The load-balance pin: causal zigzag gives every rank exactly 2P+1
    flash pairs (the simulation mirrors the traced ``lax.cond`` gates 1:1)
    where the contiguous layout skews P:1 — rank P-1 is the straggler the
    whole ring waits on. Also pins the shuffle/unshuffle permutations as
    exact inverses."""
    for p in (2, 4, 8):
        zz = ring_block_pair_counts(p, "zigzag", causal=True)
        ct = ring_block_pair_counts(p, "contiguous", causal=True)
        assert zz == [2 * p + 1] * p                 # balanced, every rank
        assert ct == list(range(1, p + 1))           # P:1 skew
        assert max(ct) / min(ct) == p
        # non-causal visits every block fully regardless of layout
        assert ring_block_pair_counts(p, "zigzag", causal=False) == [p] * p
        assert ring_block_pair_counts(p, "contiguous",
                                      causal=False) == [p] * p
    perm, inv = zigzag_perm(64, 8), zigzag_inverse_perm(64, 8)
    assert (perm[inv] == np.arange(64)).all()
    assert (inv[perm] == np.arange(64)).all()


@pytest.mark.parametrize("layout,overlap", [("contiguous", True),
                                            ("zigzag", False),
                                            ("zigzag", True)])
def test_ring_layouts_match_full(devices8, layout, overlap):
    init_mesh({"data": 1, "seq": 8})
    q, k, v = _qkv(s=64, seed=6)
    ref = attention(q, k, v, causal=True)
    out = ring_attention_spmd(q, k, v, causal=True, layout=layout,
                              overlap=overlap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_zigzag_falls_back_to_contiguous_when_inapplicable(devices8):
    """zigzag is a causal-schedule optimization: non-causal requests and
    shapes not divisible by 2P must route through the contiguous core and
    still match the dense oracle exactly."""
    init_mesh({"data": 1, "seq": 8})
    q, k, v = _qkv(s=64, seed=7)
    ref = attention(q, k, v, causal=False)
    out = ring_attention_spmd(q, k, v, causal=False, layout="zigzag",
                              overlap=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    q, k, v = _qkv(s=40, seed=8)  # 40 % (2*8) != 0 → contiguous
    ref = attention(q, k, v, causal=True)
    out = ring_attention_spmd(q, k, v, causal=True, layout="zigzag")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_zigzag_gqa(devices8):
    init_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(s=32, h=8, kv_heads=2, seed=8)
    ref = attention(q, k, v, causal=True)
    out = ring_attention_spmd(q, k, v, causal=True, layout="zigzag",
                              overlap=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("layout", ["contiguous", "zigzag"])
def test_ring_overlap_grads_match_dense(devices8, layout):
    init_mesh({"data": 2, "seq": 4})
    q, k, v = _qkv(s=16, seed=9)

    def loss_ring(q, k, v):
        return jnp.sum(ring_attention_spmd(q, k, v, causal=True,
                                           layout=layout, overlap=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention(q, k, v, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_ring_dense_fallback_marker(devices8):
    """A no-seq-axis mesh densifies — that must leave a persistent
    ``Comm/ring/dense_fallback`` telemetry marker (it used to be silent)."""
    from deepspeed_tpu.comm import comm as comm_mod

    init_mesh({"data": 8})
    tel = comm_mod.get_telemetry()
    before = tel.ring_stats.get("dense_fallback", 0.0)
    q, k, v = _qkv(seed=10)  # same shapes as the passthrough test (jit hit)
    ring_attention_spmd(q, k, v, causal=True)
    assert tel.ring_stats.get("dense_fallback", 0.0) == before + 1.0
    names = [e[0] for e in tel.events(step=0)]
    assert "Comm/ring/dense_fallback" in names


def test_measure_ring_overlap_pipelined_vs_serialized(devices8):
    """The measured per-hop overlap fraction: pipelined must hide a nonzero
    share of the KV transfer under compute; serialized must hide none. The
    value lands in ``Comm/ring/overlap_frac`` for the report."""
    from deepspeed_tpu.comm import comm as comm_mod

    on = measure_ring_overlap(overlap=True, seq=512, reps=2)
    off = measure_ring_overlap(overlap=False, seq=512, reps=2)
    assert on["overlap"] and not off["overlap"]
    assert on["overlap_frac"] > 0.0
    assert off["overlap_frac"] == 0.0
    assert comm_mod.get_telemetry().ring_stats["overlap_frac"] == \
        off["overlap_frac"]  # last write wins (accumulate=False gauge)
