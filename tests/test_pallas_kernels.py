"""Pallas kernel correctness vs the XLA reference implementations.

Runs on the CPU test mesh in interpret mode (the registry only auto-selects
pallas on real TPU; here we call the kernels directly). Mirrors the
reference's kernel unit tests (``tests/unit/ops/``) which compare CUDA kernels
against torch reference implementations.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu.ops.attention import attention_xla
from deepspeed_tpu.ops.norms import layer_norm_xla, rms_norm_xla
from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
from deepspeed_tpu.ops.pallas.norms import layer_norm_pallas, rms_norm_pallas
from deepspeed_tpu.ops.pallas.quantize import (dequantize_int8_pallas,
                                               quantize_int8_pallas)
from deepspeed_tpu.ops.quantization import quantize_int8_xla


def rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, dtype)


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("seq", [128, 192])
    def test_forward_matches_xla(self, causal, seq):
        b, h, d = 2, 4, 64
        q = rand(0, (b, seq, h, d))
        k = rand(1, (b, seq, h, d))
        v = rand(2, (b, seq, h, d))
        out = flash_attention(q, k, v, causal=causal)
        ref = attention_xla(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_gqa_and_offset(self):
        b, sq, skv, h, kvh, d = 1, 64, 128, 8, 2, 64
        q = rand(0, (b, sq, h, d))
        k = rand(1, (b, skv, kvh, d))
        v = rand(2, (b, skv, kvh, d))
        out = flash_attention(q, k, v, causal=True, q_offset=skv - sq)
        ref = attention_xla(q, k, v, causal=True, q_offset=skv - sq)
        np.testing.assert_allclose(out, ref, atol=2e-3, rtol=2e-3)

    def test_grads_match_xla(self):
        b, seq, h, d = 1, 128, 2, 64
        q = rand(0, (b, seq, h, d))
        k = rand(1, (b, seq, h, d))
        v = rand(2, (b, seq, h, d))

        def loss_pallas(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

        def loss_xla(q, k, v):
            return jnp.sum(attention_xla(q, k, v, causal=True) ** 2)

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gp, gx):
            np.testing.assert_allclose(a, b_, atol=5e-3, rtol=5e-3)

    def test_bf16(self):
        b, seq, h, d = 2, 128, 4, 64
        q = rand(0, (b, seq, h, d), jnp.bfloat16)
        k = rand(1, (b, seq, h, d), jnp.bfloat16)
        v = rand(2, (b, seq, h, d), jnp.bfloat16)
        out = flash_attention(q, k, v, causal=True)
        ref = attention_xla(q, k, v, causal=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(out.astype(np.float32),
                                   ref.astype(np.float32), atol=3e-2, rtol=3e-2)


class TestNorms:
    def test_rms_norm(self):
        x = rand(0, (4, 96, 256))
        w = 1.0 + 0.1 * rand(1, (256,))
        np.testing.assert_allclose(rms_norm_pallas(x, w), rms_norm_xla(x, w),
                                   atol=1e-5, rtol=1e-5)

    def test_rms_norm_grad(self):
        x = rand(0, (8, 128))
        w = 1.0 + 0.1 * rand(1, (128,))

        gp = jax.grad(lambda x, w: jnp.sum(rms_norm_pallas(x, w) ** 2),
                      argnums=(0, 1))(x, w)
        gx = jax.grad(lambda x, w: jnp.sum(rms_norm_xla(x, w) ** 2),
                      argnums=(0, 1))(x, w)
        np.testing.assert_allclose(gp[0], gx[0], atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gp[1], gx[1], atol=1e-4, rtol=1e-4)

    def test_layer_norm(self):
        x = rand(0, (4, 32, 256))
        w = 1.0 + 0.1 * rand(1, (256,))
        b = 0.1 * rand(2, (256,))
        np.testing.assert_allclose(layer_norm_pallas(x, w, b),
                                   layer_norm_xla(x, w, b), atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("n", [1, 3, 7, 13])
    def test_odd_row_counts(self, n):
        """Decode-sized row counts (not %8) ride the pad_rows path — Mosaic
        rejects row blocks of 1..7, so these shapes must pad and slice back."""
        x = rand(0, (n, 256))
        w = 1.0 + 0.1 * rand(1, (256,))
        b = 0.1 * rand(2, (256,))
        np.testing.assert_allclose(rms_norm_pallas(x, w), rms_norm_xla(x, w),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(layer_norm_pallas(x, w, b),
                                   layer_norm_xla(x, w, b), atol=1e-5, rtol=1e-5)

    def test_layer_norm_grad(self):
        x = rand(0, (16, 128))
        w = 1.0 + 0.1 * rand(1, (128,))
        b = 0.1 * rand(2, (128,))
        gp = jax.grad(lambda *a: jnp.sum(layer_norm_pallas(*a) ** 2),
                      argnums=(0, 1, 2))(x, w, b)
        gx = jax.grad(lambda *a: jnp.sum(layer_norm_xla(*a) ** 2),
                      argnums=(0, 1, 2))(x, w, b)
        for a, b_ in zip(gp, gx):
            np.testing.assert_allclose(a, b_, atol=1e-4, rtol=1e-4)


class TestQuantize:
    def test_roundtrip_error_small(self):
        x = rand(0, (64, 2048))
        q, s = quantize_int8_pallas(x, group_size=2048)
        back = dequantize_int8_pallas(q, s, group_size=2048)
        err = jnp.max(jnp.abs(back - x))
        amax = jnp.max(jnp.abs(x))
        assert err <= amax / 127.0 + 1e-6

    def test_matches_xla_impl(self):
        x = rand(0, (16, 512))
        qp, sp = quantize_int8_pallas(x, group_size=512)
        qx, sx = quantize_int8_xla(x, group_size=512)
        np.testing.assert_array_equal(np.asarray(qp), np.asarray(qx))
        np.testing.assert_allclose(sp, sx, rtol=1e-6)

    def test_odd_group_count_roundtrip(self):
        """Group counts not divisible by 8 pad through pad_rows and slice
        back — values AND scales must come back at the original count."""
        x = rand(0, (5 * 256,))
        q, s = quantize_int8_pallas(x, group_size=256)
        assert q.shape == x.shape and s.shape == (5,)
        qx, sx = quantize_int8_xla(x, group_size=256)
        np.testing.assert_array_equal(np.asarray(q), np.asarray(qx))
        np.testing.assert_allclose(s, sx, rtol=1e-6)
        back = dequantize_int8_pallas(q, s, group_size=256)
        err = jnp.max(jnp.abs(back - x.reshape(back.shape)))
        assert err <= jnp.max(jnp.abs(x)) / 127.0 + 1e-6

    def test_zero_input(self):
        x = jnp.zeros((4, 256))
        q, s = quantize_int8_pallas(x, group_size=256)
        assert np.all(np.asarray(q) == 0)
        back = dequantize_int8_pallas(q, s, group_size=256)
        assert np.all(np.asarray(back) == 0)


@pytest.mark.parametrize("nh,nkv", [(8, 4), (8, 8), (8, 1), (6, 2)])
def test_paged_decode_attention_matches_dense(nh, nkv):
    """Block-table-indexed flash-decode kernel vs dense gather reference
    (reference inference/v2/kernels/ragged_ops), at GQA, MHA, MQA and an
    odd query group: every KV head of a sequence rides one grid step."""
    from deepspeed_tpu.ops.pallas.paged_attention import paged_decode_attention

    rs = np.random.RandomState(0)
    B, hd, bs, nblocks, max_blocks = 3, 64, 16, 32, 4
    q = jnp.asarray(rs.randn(B, nh, hd).astype(np.float32))
    kp = jnp.asarray(rs.randn(nblocks, nkv, bs, hd).astype(np.float32))
    vp = jnp.asarray(rs.randn(nblocks, nkv, bs, hd).astype(np.float32))
    tables = jnp.asarray(rs.choice(np.arange(1, nblocks), (B, max_blocks),
                                   replace=False).astype(np.int32))
    ctx = jnp.asarray([5, 30, 63], np.int32)
    out = np.asarray(paged_decode_attention(q, kp, vp, tables, ctx))

    kg = np.asarray(kp)[np.asarray(tables)].swapaxes(2, 3).reshape(
        B, max_blocks * bs, nkv, hd)
    vg = np.asarray(vp)[np.asarray(tables)].swapaxes(2, 3).reshape(
        B, max_blocks * bs, nkv, hd)
    g = nh // nkv
    for b in range(B):
        n = int(ctx[b]) + 1
        for h in range(nh):
            kk, vv = kg[b, :n, h // g], vg[b, :n, h // g]
            s = (np.asarray(q)[b, h] @ kk.T) * (hd ** -0.5)
            p = np.exp(s - s.max())
            p /= p.sum()
            np.testing.assert_allclose(out[b, h], p @ vv, atol=2e-5)


def test_flash_attention_bias_fwd_bwd_parity():
    """Additive-bias flash path (evoformer pair bias): forward AND all four
    gradients (q/k/v/bias) match the XLA reference."""
    from deepspeed_tpu.ops.attention import attention_xla
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rs = np.random.RandomState(0)
    b, s, h, d = 2, 64, 4, 32
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    bias = jnp.asarray(rs.randn(1, h, s, s).astype(np.float32)) * 0.5

    def ref(q, k, v, bias):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5) + bias
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    def ker(q, k, v, bias):
        return flash_attention(q, k, v, causal=False, bias=bias)

    np.testing.assert_allclose(np.asarray(ker(q, k, v, bias)),
                               np.asarray(ref(q, k, v, bias)),
                               rtol=2e-5, atol=2e-5)
    co = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    g_ref = jax.grad(lambda *a: jnp.sum(ref(*a) * co), argnums=(0, 1, 2, 3))(
        q, k, v, jnp.broadcast_to(bias, (b, h, s, s)))
    g_ker = jax.grad(lambda *a: jnp.sum(ker(*a) * co), argnums=(0, 1, 2, 3))(
        q, k, v, jnp.broadcast_to(bias, (b, h, s, s)))
    for gr, gk, name in zip(g_ref, g_ker, "qkvb"):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_flash_attention_bias_causal():
    """Bias + causal masking compose (causal block-skip zeroes dbias)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rs = np.random.RandomState(1)
    b, s, h, d = 1, 32, 2, 16
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    bias = jnp.asarray(rs.randn(b, h, s, s).astype(np.float32))

    def ref(bias):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5) + bias
        cm = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(cm[None, None], logits, -1e30)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = flash_attention(q, k, v, causal=True, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(bias)),
                               rtol=2e-5, atol=2e-5)
    db_ref = jax.grad(lambda bb: jnp.sum(ref(bb) ** 2))(bias)
    db_ker = jax.grad(lambda bb: jnp.sum(
        flash_attention(q, k, v, causal=True, bias=bb) ** 2))(bias)
    np.testing.assert_allclose(np.asarray(db_ker), np.asarray(db_ref),
                               rtol=2e-4, atol=2e-4)


def test_evoformer_kernel_path_matches_xla():
    """evoformer_attention(use_kernel=True) == einsum reference, incl. the
    pair-bias gradient (the DS4Sci differentiable-bias property)."""
    from deepspeed_tpu.ops.evoformer_attn import evoformer_attention

    rs = np.random.RandomState(2)
    S, r, h, d = 3, 24, 2, 16
    q = jnp.asarray(rs.randn(1, S, r, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(1, S, r, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(1, S, r, h, d).astype(np.float32))
    pair = jnp.asarray(rs.randn(1, 1, h, r, r).astype(np.float32))

    out_x = evoformer_attention(q, k, v, [pair], use_kernel=False)
    out_k = evoformer_attention(q, k, v, [pair], use_kernel=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_x),
                               rtol=2e-5, atol=2e-5)
    gx = jax.grad(lambda p: jnp.sum(
        evoformer_attention(q, k, v, [p], use_kernel=False) ** 2))(pair)
    gk = jax.grad(lambda p: jnp.sum(
        evoformer_attention(q, k, v, [p], use_kernel=True) ** 2))(pair)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gx),
                               rtol=2e-4, atol=2e-4)


def test_blocksparse_kernel_matches_dense_mask():
    """Block-skipping sparse flash kernel == dense-masked reference, for
    sliding-window and bigbird layouts, causal and not; grads exact."""
    from deepspeed_tpu.ops.sparse_attention import (bigbird_layout,
                                                    blocksparse_attention,
                                                    sliding_window_layout)

    rs = np.random.RandomState(3)
    b, s, h, d, bs = 2, 128, 2, 32, 16
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    for layout, causal in ((sliding_window_layout(s // bs, 2), True),
                           (bigbird_layout(s // bs, 2, 1, 1), False)):
        ref = blocksparse_attention(q, k, v, layout, bs, causal=causal,
                                    use_kernel=False)
        ker = blocksparse_attention(q, k, v, layout, bs, causal=causal,
                                    use_kernel=True)
        np.testing.assert_allclose(np.asarray(ker), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        g_ref = jax.grad(lambda q_, k_, v_: jnp.sum(blocksparse_attention(
            q_, k_, v_, layout, bs, causal=causal, use_kernel=False) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        g_ker = jax.grad(lambda q_, k_, v_: jnp.sum(blocksparse_attention(
            q_, k_, v_, layout, bs, causal=causal, use_kernel=True) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for gr, gk, name in zip(g_ref, g_ker, "qkv"):
            np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                       rtol=2e-4, atol=2e-4, err_msg=name)
    # empty q rows are rejected, not silently inconsistent
    import pytest as _pytest

    empty = np.zeros((s // bs, s // bs), bool)
    empty[0, 0] = True
    with _pytest.raises(ValueError, match="attend to no kv block"):
        blocksparse_attention(q, k, v, empty, bs, causal=True)


def test_flash_block_preference_order(monkeypatch, tmp_path):
    """_block precedence: explicit pref > DSTPU_FLASH_BLOCK env > measured
    .dstpu_tuned.json (attn_sweep artifact) > compiled-in 512."""
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    monkeypatch.delenv("DSTPU_FLASH_BLOCK", raising=False)
    # compiled-in default (empty tuned cache, no file read)
    monkeypatch.setattr(fa, "_TUNED_CACHE", {"flash_block": 512})
    assert fa._block(4096) == 512
    # tuned artifact wins over the default
    monkeypatch.setattr(fa, "_TUNED_CACHE", {"flash_block": 1024})
    assert fa._block(4096) == 1024
    # env wins over tuned
    monkeypatch.setenv("DSTPU_FLASH_BLOCK", "256")
    assert fa._block(4096) == 256
    # explicit pref wins over everything
    assert fa._block(4096, pref=128) == 128
    # short sequences clamp to the next pow2 regardless of source
    monkeypatch.delenv("DSTPU_FLASH_BLOCK")
    assert fa._block(96) == 128
    # the file loader itself: valid artifact is read once
    import json as _json

    tuned = tmp_path / ".dstpu_tuned.json"
    tuned.write_text(_json.dumps({"flash_block": 768}))
    monkeypatch.setattr(fa, "_TUNED_CACHE", {})
    real_join = fa.os.path.join
    monkeypatch.setattr(
        fa.os.path, "join",
        lambda *a: str(tuned) if a[-1] == ".dstpu_tuned.json"
        else real_join(*a))
    assert fa._tuned_default() == 768


def test_blocksparse_bwd_gqa_and_empty_kv_columns():
    """Round-5 skipping backward: GQA-narrow KV gets group-summed grads
    identical to the dense-masked reference, and a kv block NO q block
    attends to receives exactly zero dk/dv."""
    from deepspeed_tpu.ops.sparse_attention import blocksparse_attention

    rs = np.random.RandomState(7)
    b, s, h, hkv, d, bs = 2, 128, 4, 2, 32, 16
    nb = s // bs
    q = jnp.asarray(rs.randn(b, s, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, s, hkv, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, s, hkv, d).astype(np.float32))
    # row i attends block 0 and itself — except row 1, which attends ONLY
    # block 0, leaving COLUMN 1 with no attenders
    layout = np.eye(nb, dtype=bool)
    layout[:, 0] = True
    layout[1, 1] = False
    for use_kernel in (False, True):
        g = jax.grad(lambda q_, k_, v_: jnp.sum(blocksparse_attention(
            q_, k_, v_, layout, bs, causal=False,
            use_kernel=use_kernel) ** 2), argnums=(0, 1, 2))(q, k, v)
        if not use_kernel:
            g_ref = g
    for gr, gk, name in zip(g_ref, g, "qkv"):
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
    # the unattended kv block's grads are exactly zero
    dk, dv = np.asarray(g[1]), np.asarray(g[2])
    assert (dk[:, bs:2 * bs] == 0).all() and (dv[:, bs:2 * bs] == 0).all()
    assert np.abs(dk).sum() > 0  # and the rest is not trivially zero


def test_paged_decode_sliding_window():
    """Windowed paged decode (mistral/exaone4 serving): kernel == gather
    reference with only the last `window` positions visible, for static
    AND traced window values; window >= ctx degenerates to full causal."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_decode_attention_xla)

    rs = np.random.RandomState(11)
    B, nh, nkv, hd, bs, nblocks, max_blocks = 3, 8, 4, 128, 32, 24, 6
    q = jnp.asarray(rs.randn(B, nh, hd).astype(np.float32))
    kp = jnp.asarray(rs.randn(nblocks, nkv, bs, hd).astype(np.float32))
    vp = jnp.asarray(rs.randn(nblocks, nkv, bs, hd).astype(np.float32))
    bt = jnp.asarray(rs.choice(np.arange(1, nblocks), (B, max_blocks),
                               replace=False).astype(np.int32))
    cl = jnp.asarray([5, 77, 170], np.int32)
    for w in (16, 64, 4096):
        out = paged_decode_attention(q, kp, vp, bt, cl, window=w)
        ref = paged_decode_attention_xla(q, kp, vp, bt, cl, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"w={w}")
    # traced window (exaone4 scans per-layer windows) under jit
    f = jax.jit(lambda w: paged_decode_attention(q, kp, vp, bt, cl,
                                                 window=w))
    np.testing.assert_allclose(
        np.asarray(f(jnp.asarray(64, jnp.int32))),
        np.asarray(paged_decode_attention_xla(q, kp, vp, bt, cl, window=64)),
        rtol=2e-5, atol=2e-5)
    # windowed != unwindowed when the window actually clips
    full = paged_decode_attention(q, kp, vp, bt, cl)
    win = paged_decode_attention(q, kp, vp, bt, cl, window=16)
    assert np.abs(np.asarray(full[2]) - np.asarray(win[2])).max() > 1e-3


# --- the pools are one [L, ...] buffer: the kernels index the layer, the
# --- write overlays the pages a step touches (ISSUE 29)
def _layered_pools(rs, L=3, nblocks=24, nkv=2, bs=8, hd=32):
    """K and V pools whose every layer holds different rows."""
    shape = (L, nblocks, nkv, bs, hd)
    return (jnp.asarray(rs.randn(*shape).astype(np.float32)),
            jnp.asarray(rs.randn(*shape).astype(np.float32)))


def _tables(rs, b, max_blocks, nblocks):
    """Distinct non-trash blocks for every sequence of the call."""
    return jnp.asarray(rs.permutation(np.arange(1, nblocks))[:b * max_blocks]
                       .reshape(b, max_blocks).astype(np.int32))


@pytest.mark.parametrize("window", [None, 12])
@pytest.mark.parametrize("op", ["decode", "prefill"])
def test_paged_kernels_read_the_layer_they_are_given(op, window):
    """Both kernels take the ``[L, ...]`` pools and a (traced) layer: at
    every layer they agree with their XLA reference and with the kernel on
    that layer's pool alone, and the layers' results differ - a kernel that
    read layer 0 for every layer fails."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    rs = np.random.RandomState(3)
    kp, vp = _layered_pools(rs)
    B, nh, hd, t = 2, 4, 32, 5
    bt = _tables(rs, B, 6, 24)
    ctx = jnp.asarray([9, 30], jnp.int32)
    kw = {} if window is None else {"window": window}
    if op == "decode":
        q = jnp.asarray(rs.randn(B, nh, hd).astype(np.float32))
        kernel, ref, args = (pa.paged_decode_attention,
                             pa.paged_decode_attention_xla, (bt, ctx))
    else:
        q = jnp.asarray(rs.randn(B, t, nh, hd).astype(np.float32))
        kernel, ref, args = (pa.paged_prefill_attention,
                             pa.paged_prefill_attention_xla,
                             (bt, ctx, jnp.asarray([t, 3], jnp.int32)))
    at = jax.jit(lambda layer: kernel(q, kp, vp, *args, layer=layer, **kw))
    outs = []
    for layer in range(3):
        got = np.asarray(at(jnp.asarray(layer, jnp.int32)))
        want = np.asarray(ref(q, kp, vp, *args, layer=layer, **kw))
        alone = np.asarray(kernel(q, kp[layer], vp[layer], *args, **kw))
        if op == "prefill":      # a padded row's output is unspecified
            got, want, alone = got[1, :3], want[1, :3], alone[1, :3]
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(got, alone)
        outs.append(got)
    assert np.abs(outs[0] - outs[1]).max() > 1e-2
    assert np.abs(outs[1] - outs[2]).max() > 1e-2


# --- the multi-token walk ends where the context ends (ISSUE 45) ------------ #
# t, query heads, KV heads, table width, contexts, real rows, window, int8
# pools - at head size 128 and blocks of 32, so the tiles are the cells'.
# What each case must cross: the chat and OLMoE cells' chunk shapes whole;
# command-a's GROUP of 16 (1 024 query rows a tile, so the wide tile is the
# 16 pages its VMEM budget leaves, not 32) over two query tiles, with five
# wide tiles of a context that ends inside one (full) and nine under the
# cell's 4 096 window and its 145-block table (window). The cell's own 128
# heads over 8 and 8 000 tokens - 1 088 interpreted grid steps, 197 s a run
# - are held on the chip: the benchmark's `correct` in every check, and
# `test_chip_compile` compiles them for the described chip.
PREFILL_WALKS = {
    "chat_chunk": (256, 32, 8, 256, [300], [256], None, False),
    "chat_last_chunk_padded": (256, 32, 8, 256, [512], [77], None, False),
    "command_a_full": (128, 32, 2, 1024, [2000], [128], None, False),
    "command_a_window": (128, 32, 2, 145, [4100], [128], 4096, False),
    "olmoe_chunk": (256, 16, 16, 128, [1500], [256], None, False),
    "batched_unequal_dummy": (40, 32, 8, 256, [0, 700, 0, 64],
                              [40, 25, 0, 7], None, False),
    "verify_window_int8": (5, 32, 8, 256, [100, 900, 3000, 10], [5] * 4,
                           "traced", True),
    "window_starts_past_tile_0": (16, 8, 2, 64, [1500], [16], 300, False),
    "context_fills_the_table": (64, 8, 2, 16, [448], [64], None, False),
    "context_zero": (256, 32, 8, 256, [0], [256], None, False),
}
# an interpreted grid step costs what a compiled one does not: command-a's
# 1 024-block table is 256 steps of the table-wide grid even at two KV heads,
# so that walk is held to the table-wide grid on the chip alone (PERF.md,
# PR 45)
CHEAP_WALKS = sorted(set(PREFILL_WALKS) - {"command_a_full"})


def _prefill_walk(case):
    """``(walk(ctx, lens, tables=) -> out, reference(kv head) -> its query
    group's out, ctx, lens)`` of one case: the kernel and, a KV head at a
    time and over the table entries the contexts reach (every position past
    them is masked for every real row; the whole width's f32 scores of
    command-a's full layer are 8.6 GB), its XLA reference."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.quantization import kv_quantize_int8

    t, nh, nkv, table, ctx, lens, window, int8 = PREFILL_WALKS[case]
    bs, hd, b = 32, 128, len(ctx)
    reach = max(1, max(-(-(c + n) // bs) for c, n in zip(ctx, lens)))
    rs = np.random.RandomState(11)
    q = jnp.asarray(rs.randn(b, t, nh, hd).astype(np.float32))
    pools = [jnp.asarray(rs.randn(reach * b + 1, nkv, bs, hd)
                         .astype(np.float32)) for _ in range(2)]
    tables = jnp.asarray(rs.randint(1, reach * b + 1, (b, table)), jnp.int32)
    scales = []
    if int8:
        (pools[0], ks), (pools[1], vs) = (kv_quantize_int8(p, hd)
                                          for p in pools)
        scales = [ks, vs]
    kw = {} if window is None else {
        "window": jnp.int32(4096) if window == "traced" else window}
    ctx, lens = jnp.asarray(ctx, jnp.int32), jnp.asarray(lens, jnp.int32)

    def walk(ctx, lens):
        return pa.paged_prefill_attention(
            q, *pools, tables, ctx, lens, **kw,
            **dict(zip(("k_scale", "v_scale"), scales)))

    def reference(h):
        g = nh // nkv
        return pa.paged_prefill_attention_xla(
            q[:, :, h * g:(h + 1) * g], *(p[:, h:h + 1] for p in pools),
            tables[:, :min(reach, table)], ctx, lens, **kw,
            **dict(zip(("k_scale", "v_scale"),
                       (s[:, h:h + 1] for s in scales))))

    return walk, reference, ctx, lens


def _walk_tile(case):
    """Pages of the KV tile the case's walk takes, by hand. These cases'
    pools are float32 at 128 lanes, so the walk fetches its own pages and
    takes, whatever its length, the widest tile the table holds ONE of: 32
    pages (1 024 keys), and 16 where the VMEM budget says so - under 1 024
    query rows (command-a's group of 16, chat's 256 tokens at a group of 4)
    a 1 024-key step of float32 pages is past it - or the table is 16
    blocks. int8 pools keep the grid of BlockSpec
    pages and have no wide tile: the 8 (256 keys) every walk took before
    ISSUE 48."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    t, nh, nkv, table, ctx, lens, _, int8 = PREFILL_WALKS[case]
    rows = nh // nkv * pa._prefill_tiles(t, nh // nkv, 128, 32, table)[0]
    wide = 16 if rows == 1024 else 32
    return 8 if int8 else min(wide, 1 << (table.bit_length() - 1))


def _walk_bound(case):
    """KV tiles the case's grid must take: the longest ``context + real
    rows`` in tiles of the walk's own width, at least one, at most the
    table's."""
    t, nh, nkv, table, ctx, lens, *_ = PREFILL_WALKS[case]
    longest = max(c + n for c, n in zip(ctx, lens))
    pages = _walk_tile(case)
    return min(max(-(-longest // (32 * pages)), 1), -(-table // pages))


def _taken_walk(walk, ctx, lens, pools=2, bs=32):
    """``(the pallas_call equation, its grid bound's value, pages a KV tile,
    whether the program chose between two)`` of the walk the program TAKES
    at these operands: where the call is a ``cond`` over two walks, the
    branch its own predicate picks, read out of the jaxpr. ``pools``: the
    pools a step reads a page of (int8 pools' scale pools too). The walk
    that fetches its own pages (one jitted call; ISSUE 62) has no bound on
    its grid - None - and its tile is its scratch's, in blocks of ``bs``."""
    from jax.extend import core as jex_core

    def upto(jaxpr, consts, at, outvars, *args):
        head = jex_core.Jaxpr(jaxpr.constvars, jaxpr.invars, outvars,
                              jaxpr.eqns[:at], debug_info=jaxpr.debug_info)
        return jax.core.eval_jaxpr(head, consts, *args)

    def a_walk(e):
        return e.primitive.name in ("pallas_call", "cond") or (
            e.primitive.name == "jit"
            and e.params["name"] == "_own_pages_walk")

    closed = jax.make_jaxpr(walk)(ctx, lens)
    jaxpr, consts, args = closed.jaxpr, closed.consts, (ctx, lens)
    (at, eqn), = [(i, e) for i, e in enumerate(jaxpr.eqns) if a_walk(e)]
    chose = eqn.primitive.name == "cond"
    if chose:
        index, *args = upto(jaxpr, consts, at, list(eqn.invars), *args)
        assert len(eqn.params["branches"]) == 2
        branch = eqn.params["branches"][int(index)]
        jaxpr, consts = branch.jaxpr, branch.consts
        (at, eqn), = [(i, e) for i, e in enumerate(jaxpr.eqns)
                      if a_walk(e)]
    if eqn.primitive.name == "jit":     # the walk that fetches its own pages
        eqn, = [e for e in eqn.params["jaxpr"].jaxpr.eqns
                if e.primitive.name == "pallas_call"]
        mapping = eqn.params["grid_mapping"]
        assert mapping.num_inputs == 1 + pools      # q and the pools, whole
        tile = eqn.params["jaxpr"].invars[      # [2, pages * bs, hd]
            mapping.num_index_operands + mapping.num_inputs
            + mapping.num_outputs].aval.shape
        return eqn, None, tile[1] // bs, chose
    (n_live,) = upto(jaxpr, consts, at, [eqn.invars[0]], *args)
    pages = (eqn.params["grid_mapping"].num_inputs - 1) // pools   # less q
    return eqn, n_live, pages, chose


@pytest.mark.parametrize("case", sorted(PREFILL_WALKS))
def test_prefill_walk_that_ends_with_the_context_agrees_with_xla(case):
    """``paged_prefill``'s grid ends with the longest context's last tile,
    not the table's: at the serve cells' chunk shapes, a padded last chunk,
    a batched call of unequal lengths with zero-length dummies, the int8
    verify window under a traced window, a window whose live range starts
    past tile 0, a context that fills its table and one of 0, every REAL
    row is the XLA reference's and every row is finite (a dummy's walk
    still takes the one step that initialises and writes it)."""
    walk, reference, ctx, lens = _prefill_walk(case)
    out = np.asarray(jax.jit(walk)(ctx, lens))
    assert np.isfinite(out).all()
    nkv = PREFILL_WALKS[case][2]
    want = np.concatenate([np.asarray(reference(h)) for h in range(nkv)],
                          axis=2)
    for b, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(out[b, :n], want[b, :n], rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("case", CHEAP_WALKS)
def test_prefill_walk_is_bit_for_bit_the_table_wide_grids(case, monkeypatch):
    """The steps the bound takes out computed nothing: real rows are the
    table-wide grid's to the bit (``_table_walk`` handed the table's static
    tile count, as the parent handed it) - and where the walk fetches its
    own pages (ISSUE 62: every case but the int8 one) it is that grid of
    ``BlockSpec`` pages' to the bit too: the same tiles, the same sums."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    walk, _, ctx, lens = _prefill_walk(case)
    out = np.asarray(jax.jit(walk)(ctx, lens))
    table_walk = pa._table_walk
    _grid_at_the_own_walks_tile(monkeypatch)
    monkeypatch.setattr(
        pa, "_table_walk", lambda *a, n_kv, pages, **kw: table_walk(
            *a, n_kv=-(-a[3].shape[1] // pages), pages=pages, **kw))
    wide = np.asarray(jax.jit(walk)(ctx, lens))
    for b, n in enumerate(np.asarray(lens)):
        np.testing.assert_array_equal(out[b, :n], wide[b, :n])


@pytest.mark.parametrize("case", sorted(PREFILL_WALKS))
def test_prefill_grids_last_dimension_is_traced(case):
    """On the grid of ``BlockSpec`` pages (the int8 case) the
    ``pallas_call``'s last grid dimension is a value of the program,
    computed from the call's own ``context_lens`` and ``lengths`` (no new
    argument, one compilation for every context), and it is the tiles of the
    longest context - at the KV tile the program takes for that context
    (``tests`` of ISSUE 48 below: 1 024 keys from two such tiles on, chosen
    by a ``cond`` on the same bound). Where the walk fetches its own pages
    there is no such dimension: each (sequence, KV head, query tile) walks
    to its own end at the wide tile. ``prefill_tile_counts`` says the same
    on the host."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    walk, _, ctx, lens = _prefill_walk(case)
    t, nh, nkv, table, _, _, window, int8 = PREFILL_WALKS[case]
    call, n_live, pages, chose = _taken_walk(walk, ctx, lens,
                                             4 if int8 else 2)
    assert pages == _walk_tile(case) and not chose
    mapping = call.params["grid_mapping"]
    how = dict(itemsize=1 if int8 else 4, quant=int8)
    live, taken, wide = pa.prefill_tile_counts(
        np.asarray(ctx), np.asarray(lens), t, nh, (nkv, 32, 128), table,
        4096 if window == "traced" else window, **how)
    assert pa.prefill_kv_pages(np.asarray(ctx), np.asarray(lens), t, nh,
                               (nkv, 32, 128), table, **how) == pages
    walks = math.prod(mapping.grid[:3])
    assert wide == walks * -(-table // pages) and 0 <= live <= taken <= wide
    if pa._fetches_pages(128, int8):
        # ISSUE 62: a grid step is one (sequence, KV head, query tile)'s
        # whole walk, to its OWN last tile - no dimension of KV tiles, no
        # bound on one, and no step taken that holds no context
        assert not int8 and n_live is None and len(mapping.grid) == 3 \
            and mapping.num_dynamic_grid_bounds == 0 and taken == live
        return
    assert mapping.num_dynamic_grid_bounds == 1
    assert all(isinstance(n, int) for n in mapping.grid[:3]) \
        and not isinstance(mapping.grid[3], int)
    assert n_live.dtype == jnp.int32 and int(n_live) == _walk_bound(case)
    assert taken == walks * _walk_bound(case)


# --- a long walk takes a wide KV tile (ISSUE 48) ---------------------------- #
# Small tiles (``_small_tiles``): blocks of 8 tokens, a narrow KV tile of 2
# pages (16 keys), a wide one of 8 (64 keys), query tiles of 16 tokens at a
# group of 2; the table is 24 blocks, three wide tiles, so a walk is LONG from
# 128 keys on. t, contexts, real rows and what differs from 4 query / 2 KV
# heads of 32 over bf16-free float32 pools.
WIDE_WALKS = {
    "plain": dict(ctx=[150], lens=[16]),
    "three_query_tiles": dict(t=40, ctx=[140], lens=[40]),
    "window_static": dict(ctx=[150], lens=[16], window=40),
    "window_traced": dict(ctx=[150], lens=[16], window=40, traced=True),
    "window_wider_than_a_wide_tile": dict(ctx=[170], lens=[16], window=100),
    "padded_rows_and_zero_length_dummies": dict(ctx=[140, 0, 30, 0],
                                                lens=[9, 0, 16, 0]),
    "latent_pool": dict(ctx=[150], lens=[16], nh=4, nkv=1, value_width=16),
    "int8_pools": dict(ctx=[150], lens=[16], ngroups=1),
    "int8_two_groups_traced_window": dict(ctx=[150], lens=[16], ngroups=2,
                                          window=40, traced=True),
    "verify_window_t4": dict(t=4, ctx=[130, 10, 171], lens=[4, 4, 4]),
    "on_a_wide_tile_edge": dict(ctx=[112], lens=[16]),      # ends at key 128
    "one_key_past_the_edge": dict(ctx=[113], lens=[16]),
    "mha_group_of_1": dict(ctx=[150], lens=[16], nh=2, nkv=2),
}
# the same shapes a key short of a long walk, and what decides it
NARROW_WALKS = {
    "one_key_short_of_two_wide_tiles": dict(ctx=[111], lens=[16]),
    "real_rows_count_and_padded_rows_do_not": dict(ctx=[120], lens=[7]),
    "every_sequence_short": dict(ctx=[100, 0, 60], lens=[16, 0, 16]),
    "context_zero": dict(ctx=[0], lens=[16]),
}
LONGEST_DECIDES = {
    "one_long_sequence_of_three": dict(ctx=[3, 112, 40], lens=[16, 16, 16]),
}


def _small_tiles(monkeypatch):
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_KV_TOKENS", 16)
    monkeypatch.setattr(pa, "_WIDE_KV_TOKENS", 64)
    monkeypatch.setattr(pa, "_Q_ROWS", 32)


def _tile_walk(c):
    """``(walk(ctx, lens), reference(), ctx, lens)`` of one small case. Table
    entries past a sequence's blocks (every entry of a zero-length dummy)
    point at a poisoned block in the kernel's copy - NaN rows, or NaN scales
    over int8 codes - and at the trash block in the reference's (``room``:
    a context the sequences' blocks reach to, where the case's is shorter)."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.quantization import kv_quantize_int8

    c = dict(dict(t=16, nh=4, nkv=2, window=None, traced=False, ngroups=0,
                  value_width=None, room=0, hd=32, table=24), **c)
    t, nh, nkv, bs, hd, mb, nb = (c["t"], c["nh"], c["nkv"], 8, c["hd"],
                                  c["table"], 64)
    rs = np.random.RandomState(7)
    b, poison = len(c["ctx"]), nb - 1
    q = jnp.asarray(rs.randn(b, t, nh, hd).astype(np.float32))
    lead = (c["layers"],) if c.get("layers") else ()
    pools = [jnp.asarray(rs.randn(*lead, nb, nkv, bs, hd).astype(np.float32))
             for _ in range(1 if c["value_width"] else 2)]
    tables = np.zeros((b, mb), np.int32)
    poisoned = np.full((b, mb), poison, np.int32)
    for i, (x, n) in enumerate(zip(c["ctx"], c["lens"])):
        need = -(-(max(x, c["room"]) + n) // bs) if n else 0
        tables[i, :need] = poisoned[i, :need] = rs.randint(1, poison, need)
    scales = bad_scales = []
    if c["ngroups"]:
        (pools[0], ks), (pools[1], vs) = (
            kv_quantize_int8(p, hd // c["ngroups"]) for p in pools)
        scales = [ks, vs]
        bad, bad_scales = pools, [s.at[poison].set(jnp.nan) for s in scales]
    else:
        bad = [p.at[..., poison, :, :, :].set(jnp.nan) for p in pools]
    if c["value_width"]:
        pools, bad = pools + [None], bad + [None]
    window = c["window"]
    kw = {} if c["value_width"] is None else {"value_width": c["value_width"]}
    if lead:
        kw["layer"] = lead[0] - 1
    ctx = jnp.asarray(c["ctx"], jnp.int32)
    lens = jnp.asarray(c["lens"], jnp.int32)

    def walk(ctx, lens, window=window):
        return pa.paged_prefill_attention(
            q, *bad, jnp.asarray(poisoned), ctx, lens, window=window, **kw,
            **dict(zip(("k_scale", "v_scale"), bad_scales)))

    def reference():
        return pa.paged_prefill_attention_xla(
            q, *pools, jnp.asarray(tables), ctx, lens, window=window, **kw,
            **dict(zip(("k_scale", "v_scale"), scales)))

    if c["traced"]:       # the window is a value of the program
        traced = walk
        walk = lambda ctx, lens: traced(ctx, lens, jnp.int32(window))  # noqa
    return walk, reference, ctx, lens


@pytest.mark.parametrize("case", sorted(WIDE_WALKS))
def test_long_walk_at_the_wide_tile_agrees_with_xla_and_the_narrow_tile(
        case, monkeypatch):
    """A walk whose bound reaches two wide tiles takes the wide one, and
    every REAL row of its result is the XLA reference's and - to float32
    rounding: the flash sum runs in another order - the narrow tile's:
    plain, under a static and a traced window (narrower and wider than a
    wide tile), padded rows beside zero-length dummies, a latent pool's
    values out of its key page, the
    verify window's four rows, a context that ends exactly on a wide tile's
    edge and one key past it, a group of one. int8 pools - one and two scale
    groups - have no wide tile: the same long walk keeps its one narrow walk
    and agrees. No step reads a table entry past its sequence's blocks (they
    are poisoned)."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    _small_tiles(monkeypatch)
    c = WIDE_WALKS[case]
    walk, reference, ctx, lens = _tile_walk(c)
    pools = 4 if c.get("ngroups") else 1 if c.get("value_width") else 2
    _, n_live, pages, chose = _taken_walk(walk, ctx, lens, pools)
    longest = int(np.max(np.asarray(ctx) + np.asarray(lens)))
    wide = 2 if c.get("ngroups") else 8
    assert chose == (wide == 8) and pages == wide \
        and int(n_live) == -(-longest // (8 * wide))
    out = np.asarray(jax.jit(walk)(ctx, lens))
    assert np.isfinite(out).all()
    want = np.asarray(reference())
    monkeypatch.setattr(pa, "_wide_pages",
                        lambda rows, hd, bs, mb, narrow, *a: narrow)
    walk, *_ = _tile_walk(c)        # a new function: nothing traced is kept
    _, n_narrow, pages, chose = _taken_walk(walk, ctx, lens, pools)
    assert not chose and pages == 2 and int(n_narrow) == -(-longest // 16)
    narrow = np.asarray(jax.jit(walk)(ctx, lens))
    for b, n in enumerate(np.asarray(lens)):
        np.testing.assert_allclose(out[b, :n], want[b, :n], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(out[b, :n], narrow[b, :n], rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("case", sorted({**NARROW_WALKS, **LONGEST_DECIDES}))
def test_the_program_takes_the_wide_tile_from_two_wide_tiles_on(case,
                                                                monkeypatch):
    """ONE program, two walks: the ``cond``'s own predicate - the bound the
    grid already has, ``max(context_lens + lengths)`` against two wide tiles
    - picks the 16-key tile under 128 keys and the 64-key one from there on,
    the longest sequence of a batch deciding for all of it; the same jitted
    program serves both sides of the threshold (one compilation) and agrees
    with the reference on each; the host's mirror says which it took."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    _small_tiles(monkeypatch)
    c = dict({**NARROW_WALKS, **LONGEST_DECIDES}[case], room=150)
    walk, reference, ctx, lens = _tile_walk(c)
    long = case in LONGEST_DECIDES
    _, n_live, pages, chose = _taken_walk(walk, ctx, lens)
    longest = int(np.max(np.asarray(ctx) + np.asarray(lens)))
    assert chose and pages == (8 if long else 2) and (longest >= 128) == long
    assert int(n_live) == max(-(-longest // (8 * pages)), 1)
    assert pa.prefill_kv_pages(c["ctx"], c["lens"], 16, 4, (2, 8, 32), 24,
                               itemsize=4) == pages
    f = jax.jit(walk)
    out, want = np.asarray(f(ctx, lens)), np.asarray(reference())
    for b, n in enumerate(c["lens"]):
        np.testing.assert_allclose(out[b, :n], want[b, :n], rtol=2e-5,
                                   atol=2e-5)
    # the other side of the threshold, through the same compiled program
    other = jnp.where(jnp.arange(len(c["ctx"])) == np.argmax(c["ctx"]),
                      30 if long else 150, ctx)
    walk2, reference2, _, _ = _tile_walk(dict(c, ctx=np.asarray(other)
                                              .tolist()))
    assert _taken_walk(walk2, other, lens)[2] == (2 if long else 8)
    out2, want2 = np.asarray(f(other, lens)), np.asarray(reference2())
    assert f._cache_size() == 1
    for b, n in enumerate(c["lens"]):
        np.testing.assert_allclose(out2[b, :n], want2[b, :n], rtol=2e-5,
                                   atol=2e-5)


@pytest.mark.parametrize("ctx,builds", [(0, False), (111, False),
                                        (112, True)])
def test_a_constant_context_says_whether_there_is_a_wide_walk_to_build(
        ctx, builds, monkeypatch):
    """A context that is a CONSTANT of the program (numpy: a one-shot
    prefill's zeros) bounds the walk statically - it plus the call's rows -
    and a walk that cannot reach two wide tiles is the one narrow
    ``pallas_call``, no ``cond`` and no second kernel to trace, lower and
    compile; one that can keeps the program's choice. The result is the
    reference's either way."""
    _small_tiles(monkeypatch)
    walk, reference, _, lens = _tile_walk(dict(ctx=[ctx], lens=[16],
                                               room=150))
    const = np.asarray([ctx], np.int32)
    names = [e.primitive.name for e in jax.make_jaxpr(
        lambda lens: walk(const, lens))(lens).jaxpr.eqns]
    assert ("cond" in names) == builds \
        and ("pallas_call" in names) == (not builds)
    out = np.asarray(jax.jit(lambda lens: walk(const, lens))(lens))
    np.testing.assert_allclose(out[0], np.asarray(reference())[0], rtol=2e-5,
                               atol=2e-5)


def test_wide_tile_comes_from_the_shapes_and_leaves_the_query_tiles():
    """``_wide_pages`` at the cells' geometries: 1 024 keys a step where
    1 024 rows of head size 128 walk bf16 pools (command-a both table kinds,
    chat, OLMoE), less where the budget says (a.x-k1's 640-lane latent rows
    keep their 256, float32 pools under 1 024 rows get 512) or the table
    holds no such tile (ISSUE 62: ONE of it where the walk fetches its own
    pages, a long walk of them on the grid of BlockSpec pages - heads of
    64), the narrow tile for int8 pools; the query tiles - which Keye's
    kernels size their scores by - are what they were."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse

    wide = pa._wide_pages
    assert wide(1024, 128, 32, 1024, 8, 2, False) == 32     # command-a, full
    assert wide(1024, 128, 32, 145, 8, 2, False) == 32      # ... window kind
    assert wide(1024, 128, 32, 256, 8, 2, False) == 32      # chat, Mixtral
    assert wide(256, 128, 32, 128, 8, 2, False) == 32       # OLMoE (group 1)
    assert wide(64, 128, 32, 256, 8, 2, False) == 32        # a verify window
    assert wide(1024, 128, 32, 31, 8, 2, False) == 16       # a 992-key table
    assert wide(1024, 128, 32, 48, 8, 2, False) == 32       # 1 536 keys
    assert wide(1024, 128, 32, 49, 8, 2, False) == 32       # Mellum's window
    assert wide(1024, 64, 32, 31, 8, 2, False) == 8         # ... heads of 64:
    assert wide(1024, 64, 32, 48, 8, 2, False) == 16        # the grid's rule
    assert wide(1024, 64, 32, 64, 8, 2, False) == 32
    assert wide(1024, 128, 512, 16, 1, 2, False) == 2       # 512-key pages
    assert wide(1024, 128, 32, 3, 3, 2, False) == 3         # a short table
    assert wide(1024, 640, 128, 256, 2, 2, False, 1) == 2   # a.x-k1's latent
    assert wide(1024, 128, 32, 1024, 8, 4, False) == 16     # float32 pools
    assert wide(1024, 128, 32, 256, 8, 1, True) == 8        # int8 pools
    assert pa._prefill_tiles(512, 16, 128, 32, 1024)[:2] == (64, 8)
    assert pa._prefill_tiles(512, 16, 128, 32, 145)[:2] == (64, 8)
    assert pa._prefill_tiles(512, 64, 640, 128, 256) == (16, 32, 2)
    assert pa._prefill_tiles(512, 8, 128, 32, 1024) == (128, 4, 8)   # Keye
    assert sparse.prefill_rows(512, 32, 4, 128, 32, 1024) == 512
    assert (pa._MAX_PAGES, pa._KV_TOKENS, sparse._PREFILL_PAGES) \
        == (8, 256, 32)


# --- the multi-token walk fetches its own pages (ISSUE 62) ------------------ #
# ``_tile_walk``'s small tiles at head size 128 (whole lane tiles, so the
# walk fetches its own pages): blocks of 8, a table of 24, query tiles of 16
# tokens, and ONE KV tile of 8 pages (64 keys) whatever the walk's length.
OWN_PAGES_WALKS = {
    "plain": dict(ctx=[150], lens=[16]),
    # either side of the bound the grid of BlockSpec pages chooses its tile
    # by: this walk takes the wide tile on both
    "one_key_short_of_two_wide_tiles": dict(ctx=[111], lens=[16]),
    "two_wide_tiles": dict(ctx=[112], lens=[16]),
    "a_walk_of_one_page": dict(ctx=[3], lens=[5]),
    "context_zero": dict(ctx=[0], lens=[16]),
    "context_fills_the_table": dict(ctx=[176], lens=[16]),
    "three_query_tiles": dict(t=40, ctx=[140], lens=[40]),
    # B = 1 and fewer real rows than the call's: query tiles 1 and 2 hold
    # none, fetch nothing and write zeros
    "padded_last_chunk": dict(t=40, ctx=[100], lens=[7]),
    "zero_length_dummies": dict(ctx=[140, 0, 30, 0], lens=[9, 0, 16, 0]),
    "every_sequence_a_dummy": dict(ctx=[0, 0], lens=[0, 0]),
    "window_static": dict(ctx=[150], lens=[16], window=40),
    "window_traced": dict(ctx=[150], lens=[16], window=40, traced=True),
    "window_wider_than_a_tile": dict(ctx=[170], lens=[16], window=100),
    # position 171 - 20: the walk begins in tile 2 of 3
    "window_walk_begins_past_tile_0": dict(ctx=[170, 20], lens=[16, 16],
                                           window=20),
    # a window kind's table: 21 blocks, not whole tiles - the last tile is
    # five pages, and the walk begins past page 0
    "window_kinds_short_table": dict(ctx=[150], lens=[16], window=100,
                                     table=21),
    "verify_window_t5_b16": dict(
        t=5, ctx=[0, 3, 59, 60, 63, 64, 100, 127, 128, 150, 187, 1, 64, 9,
                  120, 31], lens=[5] * 16),
    "group_16": dict(ctx=[150], lens=[16], nh=32, nkv=2),
    "group_8": dict(ctx=[150], lens=[16], nh=8, nkv=1),
    "group_4": dict(ctx=[150, 70], lens=[16, 16], nh=8, nkv=2),
    "group_1": dict(ctx=[150], lens=[16], nh=2, nkv=2),
    "layer_of_a_5d_pool": dict(ctx=[150], lens=[16], layers=3),
    "latent_pool": dict(ctx=[150, 10], lens=[16, 16], nh=4, nkv=1, hd=256,
                        value_width=128),
    "latent_pool_640_lanes": dict(ctx=[150], lens=[16], nh=8, nkv=1, hd=640,
                                  value_width=512),
    "latent_pool_windowed": dict(ctx=[150], lens=[16], nh=4, nkv=1, hd=256,
                                 value_width=128, window=20),
}
# int8 pools and plain pools of heads under a lane tile: the grid stays
GRID_WALKS = {
    "int8_pools": dict(ctx=[150], lens=[16], hd=128, ngroups=1),
    "head_64": dict(ctx=[150, 20], lens=[16, 9], hd=64),
    "head_64_windowed": dict(ctx=[150], lens=[16], hd=64, window=40),
}


def _grid_at_the_own_walks_tile(monkeypatch):
    """The grid of ``BlockSpec`` pages, forced, at the tile the walk that
    fetches its own pages takes (the widest the table holds ONE of,
    whatever the walk's length): what that walk must be to the bit."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_fetches_pages", lambda *a: False)
    monkeypatch.setattr(pa, "_WIDE_WALK_TILES", 1)
    monkeypatch.setattr(pa, "_takes_wide", lambda *a: True)


def _tiles_by_hand(c, tq, kv):
    """KV tiles a KV head that hold context a real row attends, a loop a
    (sequence, query tile): from its first row's window (tile 0 without
    one) to its last REAL row."""
    n_tiles = 0
    for ctx, n in zip(c["ctx"], c["lens"]):
        for q_lo in range(0, -(-c.get("t", 16) // tq) * tq, tq):
            if q_lo < n:
                first = max(ctx + q_lo + 1 - c["window"], 0) // kv \
                    if c.get("window") else 0
                n_tiles += (ctx + min(q_lo + tq, n) - 1) // kv - first + 1
    return n_tiles


@pytest.mark.parametrize("case", sorted(OWN_PAGES_WALKS))
def test_prefill_walk_that_fetches_its_own_pages(case, monkeypatch):
    """``paged_prefill`` where ``_fetches_pages`` holds (interpreted: the
    interpreter runs its DMAs, its semaphores and its SMEM carry): ONE
    jitted ``pallas_call`` on a grid (sequences, KV heads, query tiles) with
    no dimension of KV tiles, the pools whole operands, at the wide tile on
    both sides of the bound the grid of ``BlockSpec`` pages chooses by.
    Every real row is the XLA reference's and - TO THE BIT - that grid's at
    the same tile (the same tiles, the same flash sums), every row is
    finite, and a query tile with no real row is zeros: every query group,
    a latent pool (640 lanes too), a layer of a 5-D pool, the verify window
    at 16 sequences, a padded last chunk, zero-length dummies, static and
    traced windows, walks that begin past tile 0 and a table that is not
    whole tiles; table entries past a sequence's blocks are poisoned, and a
    walk that fetched one - even under its mask - would read NaN. The host's
    mirror counts the tiles the walk takes: the ones that hold context."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    _small_tiles(monkeypatch)
    c = dict(dict(hd=128, t=16, nh=4, nkv=2), **OWN_PAGES_WALKS[case])
    walk, reference, ctx, lens = _tile_walk(c)
    pools = 1 if c.get("value_width") else 2
    call, n_live, pages, chose = _taken_walk(walk, ctx, lens, pools, bs=8)
    mapping = call.params["grid_mapping"]
    n_qt = -(-c["t"] // 16)
    assert (n_live, pages, chose) == (None, 8, False) \
        and mapping.grid == (len(c["ctx"]), c["nkv"], n_qt) \
        and mapping.num_dynamic_grid_bounds == 0
    out = np.asarray(jax.jit(walk)(ctx, lens))
    want = np.asarray(reference())
    assert out.shape == want.shape and np.isfinite(out).all()
    how = dict(itemsize=4, pools=pools)
    shape = (c["nkv"], 8, c["hd"])
    table = c.get("table", 24)
    live, taken, wide = pa.prefill_tile_counts(
        c["ctx"], c["lens"], c["t"], c["nh"], shape, table,
        c.get("window"), **how)
    assert live == taken == c["nkv"] * _tiles_by_hand(c, 16, 64) \
        and wide == len(c["ctx"]) * c["nkv"] * n_qt * -(-table // 8)
    assert pa.prefill_kv_pages(c["ctx"], c["lens"], c["t"], c["nh"], shape,
                               table, **how) == 8
    _grid_at_the_own_walks_tile(monkeypatch)
    walk, *_ = _tile_walk(c)        # a new function: nothing traced is kept
    assert _taken_walk(walk, ctx, lens, pools)[1:3] == (
        max(-(-int(np.max(np.add(c["ctx"], c["lens"]))) // 64), 1), 8)
    grid = np.asarray(jax.jit(walk)(ctx, lens))
    for b, n in enumerate(c["lens"]):
        np.testing.assert_allclose(out[b, :n], want[b, :n], rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_array_equal(out[b, :n], grid[b, :n])
        # whole query tiles of padding: nothing fetched, zeros written
        assert not out[b, -(-n // 16) * 16:].any()


@pytest.mark.parametrize("case", sorted(GRID_WALKS))
def test_int8_pools_and_narrow_heads_keep_the_grid_of_blockspec_pages(
        case, monkeypatch):
    """Mosaic slices a page out of a pool for a DMA only where the pool's
    rows are whole 128-lane tiles: int8 pools (their f32 scale pages) and
    heads of 64 keep the grid ``(sequences, KV heads, query tiles, KV
    tiles)`` with its traced bound, its two tile widths and its counts -
    every (sequence, KV head, query tile) as far as the longest."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    _small_tiles(monkeypatch)
    c = dict(dict(t=16, nh=4, nkv=2), **GRID_WALKS[case])
    quant = bool(c.get("ngroups"))
    assert not pa._fetches_pages(c["hd"], quant)
    walk, reference, ctx, lens = _tile_walk(c)
    call, n_live, pages, chose = _taken_walk(walk, ctx, lens,
                                             4 if quant else 2)
    mapping = call.params["grid_mapping"]
    assert (int(n_live), pages, chose) == ((11, 2, False) if quant
                                           else (3, 8, True)) \
        and len(mapping.grid) == 4 and mapping.num_dynamic_grid_bounds == 1
    live, taken, _ = pa.prefill_tile_counts(
        c["ctx"], c["lens"], 16, 4, (2, 8, c["hd"]), 24, c.get("window"),
        itemsize=1 if quant else 4, quant=quant)
    assert taken == len(c["ctx"]) * 2 * int(n_live) and 0 < live <= taken \
        and (live < taken) == (case != "int8_pools")
    out, want = np.asarray(jax.jit(walk)(ctx, lens)), np.asarray(reference())
    for b, n in enumerate(c["lens"]):
        np.testing.assert_allclose(out[b, :n], want[b, :n], rtol=2e-5,
                                   atol=2e-5)


# --- the decode walk fetches its own pages (ISSUE 49) ----------------------- #
# Blocks of 8 tokens, a table 20 wide and a KV tile held to 64 tokens: two
# whole tiles and half a third. A context of n is n cached tokens plus the
# current one, so 62 is one token short of a tile, 63 exactly a tile, 64 the
# first token of the second and 159 exactly the table.
_ROUND_A_TILE = [0, 62, 63, 64, 159]
DECODE_WALKS = {
    "group_1": dict(nkv=4, g=1),
    "group_4": dict(nkv=2, g=4),
    "group_8": dict(nkv=1, g=8),
    "group_16": dict(nkv=2, g=16),
    "group_64_latent": dict(nkv=1, g=64, hd=256, vd=128),
    "ragged_beside_empty_slots": dict(nkv=2, g=4, ctx=[159, 0, 5, 0, 100]),
    "every_slot_empty": dict(nkv=2, g=4, ctx=[0, 0, 0]),
    "window_static": dict(nkv=2, g=2, window=9),
    "window_wider_than_a_tile": dict(nkv=2, g=2, window=70),
    "window_traced": dict(nkv=2, g=2, window=9, traced=True),
    # ctx 159 under a window of 9: the walk begins at page 18 of 20
    "window_walk_begins_past_page_0": dict(nkv=2, g=4, ctx=[159, 150, 100],
                                           window=9),
    "layer_of_a_5d_pool": dict(nkv=2, g=4, layers=3),
    "latent_pool": dict(nkv=1, g=8, hd=256, vd=128, layers=2),
    "latent_pool_windowed": dict(nkv=1, g=8, hd=256, vd=128, window=20),
    # two heads of 64 side by side in a 128-lane row: a query row is zero on
    # the other head's lanes and the scale is the head's
    "lane_packed_head_64": dict(nkv=2, g=8, pack=2),
    "two_head_blocks": dict(nkv=4, g=2, vmem=288 << 10),
    # plain pools of heads narrower than a lane tile keep the grid of
    # BlockSpec pages, as int8 pools do
    "head_64_in_plain_pools": dict(nkv=2, g=4, hd=64),
    "latent_rows_off_a_lane_tile": dict(nkv=1, g=8, hd=192, vd=128),
    "int8_pools": dict(nkv=2, g=4, ngroups=1),
    "int8_pools_windowed": dict(nkv=2, g=4, ngroups=2, window=20),
}


def _decode_walk_case(case):
    """``(kernel's operands, reference's operands, keywords)``: the
    kernel's tables hold garbage past each sequence's last block - a block
    of NaN rows (NaN scales over int8 codes), an index past the pool and a
    negative one, in turn - where the reference's hold the trash block."""
    from deepspeed_tpu.ops.quantization import kv_quantize_int8

    c = dict(dict(hd=128, vd=None, ctx=_ROUND_A_TILE, window=None,
                  traced=False, layers=0, pack=1, ngroups=0, vmem=None),
             **DECODE_WALKS[case])
    rng = np.random.default_rng(7)
    nkv, g, hd, bs, mb, nb = c["nkv"], c["g"], c["hd"], 8, 20, 48
    B, poison = len(c["ctx"]), nb - 1
    lead = (c["layers"],) if c["layers"] else ()
    q = rng.standard_normal((B, nkv * g, hd)).astype(np.float32)
    if c["pack"] > 1:                   # head i of a row keeps its own lanes
        lanes = np.arange(hd) // (hd // c["pack"])
        q = q * (lanes[None, :] == (np.arange(g) % c["pack"])[:, None])[
            None, None].repeat(nkv, 1).reshape(1, nkv * g, hd)
    pools = [jnp.asarray(rng.standard_normal(lead + (nb, nkv, bs, hd)),
                         jnp.float32) for _ in range(1 if c["vd"] else 2)]
    tables = np.zeros((B, mb), np.int32)
    garbage = np.resize(np.asarray([poison, 10 ** 6, -3], np.int32), (B, mb))
    for b, x in enumerate(c["ctx"]):
        need = x // bs + 1
        tables[b, :need] = garbage[b, :need] = rng.integers(1, poison, need)
    kw = {"value_width": c["vd"]} if c["vd"] else {}
    if c["layers"]:
        kw["layer"] = c["layers"] - 1
    if c["pack"] > 1:
        kw["scale"] = (hd // c["pack"]) ** -0.5
    bad = [p.at[..., poison, :, :, :].set(jnp.nan) for p in pools]
    kw_bad = kw
    if c["ngroups"]:
        (kp, ks), (vp, vs) = (kv_quantize_int8(p, hd // c["ngroups"])
                              for p in pools)
        pools = bad = [kp, vp]
        kw, kw_bad = (dict(kw, k_scale=a, v_scale=b) for a, b in (
            (ks, vs), (ks.at[poison].set(jnp.nan),
                       vs.at[poison].set(jnp.nan))))
    pools, bad = (ps + [None] * (2 - len(ps)) for ps in (pools, bad))
    ctx = jnp.asarray(c["ctx"], jnp.int32)
    return (c, (jnp.asarray(q), *bad, jnp.asarray(garbage), ctx), kw_bad,
            (jnp.asarray(q), *pools, jnp.asarray(tables), ctx), kw)


@pytest.mark.parametrize("case", sorted(DECODE_WALKS))
def test_decode_walk_that_fetches_its_own_pages_agrees_with_xla(
        case, monkeypatch):
    """``paged_decode`` (interpreted: the interpreter runs its DMAs, its
    semaphores and its SMEM carry) against the gathered XLA op: every query
    group; contexts ragged across slots, 0, either side of a tile's end and
    the table's; garbage table entries past a sequence's end, which a walk
    that read them - even under its mask - would turn into NaN; static and
    traced windows and a walk that begins past page 0; a layer of a 5-D
    pool; the latent form; the lane-packed geometry; two head blocks; int8
    pools and heads of 64 in plain pools, which keep the grid of
    ``BlockSpec`` pages. The counter says what the walk takes: each slot's
    own tiles, and on that grid the longest's for every slot."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_DECODE_KV_TOKENS", 64)
    c, bad, kw_bad, good, kw = _decode_walk_case(case)
    if c["vmem"]:
        monkeypatch.setattr(pa, "_TILE_VMEM", c["vmem"])
    nh, quant = c["nkv"] * c["g"], bool(c["ngroups"])
    own = pa._fetches_pages(c["hd"], quant)
    assert own == (not quant and c["hd"] % 128 == 0)
    pages, heads, n_kv = pa._decode_tiles(
        c["nkv"], c["g"], c["hd"], 8, 20, 1 if quant else 4, quant,
        1 if c["vd"] else 2)
    assert (pages, heads, n_kv) == (8, 2 if c["vmem"] else c["nkv"], 3)
    windows = [None] if c["window"] is None else [c["window"], 70]
    if c["traced"]:
        walk = jax.jit(lambda w: pa.paged_decode_attention(
            *bad, window=w, **kw_bad))
        outs = [walk(jnp.asarray(w, jnp.int32)) for w in windows]
        assert walk._cache_size() == 1
    else:
        outs = [pa.paged_decode_attention(*bad, window=w, **kw_bad)
                for w in windows[:1]]
    for w, out in zip(windows, outs):
        want = pa.paged_decode_attention_xla(*good, window=w, **kw)
        assert out.shape == (len(c["ctx"]), nh, c["vd"] or c["hd"])
        np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    tiles = [x // 64 + 1 for x in c["ctx"]]
    assert pa.decode_tile_counts(
        c["ctx"], nh, good[1].shape, 1 if quant else 4, 20, quant,
        1 if c["vd"] else 2) == (
        sum(tiles) * (c["nkv"] // heads),
        (sum(tiles) if own else max(tiles) * len(tiles))
        * (c["nkv"] // heads))


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("t", [1, 5])
def test_paged_attention_step_hands_its_scale_to_both_kernels(t, backend):
    """``paged_attention_step(scale=)`` reaches the decode kernel (t = 1),
    the prefill kernel (t > 1) and their XLA references: at a scale that is
    not ``hd ** -0.5`` (Granite's ``attention_multiplier`` is 1 / hd) the
    step agrees with plain softmax attention at THAT scale over the written
    context, and not at the default."""
    from deepspeed_tpu.models._paged import LayerPool, paged_attention_step
    from deepspeed_tpu.ops import registry

    rs = np.random.RandomState(5)
    kp, vp = _layered_pools(rs)
    L, nblocks, nkv, bs, hd = kp.shape
    B, nh, layer, scale = 2, 4, 1, 1.0 / hd
    bt = _tables(rs, B, 6, nblocks)
    ctx = jnp.asarray([9, 20], jnp.int32)
    q = jnp.asarray(rs.randn(B, t, nh, hd).astype(np.float32))
    k, v = (jnp.asarray(rs.randn(B, t, nkv, hd).astype(np.float32))
            for _ in range(2))
    for op in ("paged_kv_write", "paged_decode_attention",
               "paged_prefill_attention"):
        registry.set_backend(op, backend)
    try:
        out, k_c, v_c = paged_attention_step(
            q, k, v, LayerPool(kp, None, jnp.int32(layer)),
            LayerPool(vp, None, jnp.int32(layer)), bt, ctx,
            ctx[:, None] + jnp.arange(t)[None], jnp.ones((B, t), bool),
            scale=scale)
    finally:
        for op in ("paged_kv_write", "paged_decode_attention",
                   "paged_prefill_attention"):
            registry.set_backend(op, None)

    def dense(pool, b):       # the sequence's context, in order
        return np.asarray(pool[layer, bt[b]]).swapaxes(1, 2).reshape(
            -1, nkv, hd)

    for b in range(B):
        n = int(ctx[b]) + t
        keys, values = dense(k_c.pool, b)[:n], dense(v_c.pool, b)[:n]
        for ti in range(t):
            for h in range(nh):
                upto = int(ctx[b]) + ti + 1
                kh = keys[:upto, h // (nh // nkv)]
                logits = kh @ np.asarray(q[b, ti, h])
                for s_, agrees in ((scale, True), (hd ** -0.5, False)):
                    p = np.exp(logits * s_ - (logits * s_).max())
                    want = (p / p.sum()) @ values[:upto, h // (nh // nkv)]
                    close = np.allclose(np.asarray(out[b, ti, h]), want,
                                        rtol=2e-4, atol=2e-4)
                    assert close == agrees


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("t", [1, 5])
def test_lane_packed_pools_give_what_plain_pools_give(t, backend):
    """Heads of 64 lanes, two a 128-lane pool row
    (``init_paged_pools(lane_pack=True)``): ``paged_attention_step`` reads
    the packing off the pool's shape, and the step's output and the written
    context are those of plain ``[.., nkv, bs, 64]`` pools - at the default
    scale, the head's and not the row's, and at another."""
    from deepspeed_tpu.models._paged import (LayerPool, init_paged_pools,
                                             lane_pack_of,
                                             paged_attention_step)
    from deepspeed_tpu.ops import registry

    rs = np.random.RandomState(7)
    L, nblocks, nkv, bs, hd, B, nh, layer = 2, 24, 4, 8, 64, 2, 16, 1
    assert (lane_pack_of(nkv, hd), lane_pack_of(3, hd),
            lane_pack_of(nkv, 128)) == (2, 1, 1)
    bt = _tables(rs, B, 6, nblocks)
    ctx = jnp.asarray([0, 11], jnp.int32)
    ops = ("paged_kv_write", "paged_decode_attention",
           "paged_prefill_attention")

    def steps(packed, scale):
        """A 9-token fill of every sequence, then the call under test."""
        pools = init_paged_pools(L, nblocks, nkv, bs, hd, jnp.float32,
                                 lane_pack=packed)
        assert pools["k"].shape == ((L, nblocks, 2, bs, 128) if packed
                                    else (L, nblocks, 4, bs, 64))
        rs_ = np.random.RandomState(8)
        entries = [LayerPool(pools[n], None, jnp.int32(layer))
                   for n in ("k", "v")]
        for n, at in ((9, ctx), (t, ctx + 9)):
            q = jnp.asarray(rs_.randn(B, n, nh, hd).astype(np.float32))
            k, v = (jnp.asarray(rs_.randn(B, n, nkv, hd).astype(np.float32))
                    for _ in range(2))
            out, *entries = paged_attention_step(
                q, k, v, *entries, bt, at, at[:, None] + jnp.arange(n)[None],
                jnp.ones((B, n), bool), scale=scale)
        return np.asarray(out), [np.asarray(e.pool) for e in entries]

    for op in ops:
        registry.set_backend(op, backend)
    try:
        for scale in (None, 1.0 / hd):
            plain, plain_pools = steps(False, scale)
            got, got_pools = steps(True, scale)
            np.testing.assert_allclose(got, plain, rtol=2e-5, atol=2e-5)
            for a, b in zip(got_pools, plain_pools):
                # a packed row is two heads' rows side by side
                np.testing.assert_array_equal(
                    a, b.reshape(L, nblocks, 2, 2, bs, hd)
                    .transpose(0, 1, 2, 4, 3, 5).reshape(a.shape))
    finally:
        for op in ops:
            registry.set_backend(op, None)
    with pytest.raises(ValueError, match="no quantized mode"):
        init_paged_pools(L, nblocks, nkv, bs, hd, kv_quant_group=64,
                         lane_pack=True)


# name -> (t, context_lens, lengths): where a step's rows fall on the pages
KV_WRITES = {
    "page_aligned_chunk": (16, [16], [16]),
    "chunk_from_mid_page_to_mid_page": (16, [13], [14]),
    "decode_batch": (1, [0, 7, 8, 31], [1, 1, 1, 1]),
    "rows_that_are_not_valid": (6, [5, 20], [4, 0]),
    "zero_length_dummy_sequence": (8, [0, 11], [0, 8]),
    "two_sequences_in_one_call": (11, [3, 22], [11, 9]),
}


@pytest.mark.parametrize("case", sorted(KV_WRITES))
def test_paged_kv_write_matches_the_scatter(case):
    """``paged_kv_write`` (the Mosaic call, interpreted) against the
    ``.at[].set`` reference on layer 1 of three: the same rows at the same
    positions, padded rows and dummy sequences write nothing, and every
    block, layer and slot the step does not touch is bit-identical."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    t, ctx, lens = KV_WRITES[case]
    rs = np.random.RandomState(5)
    kp, vp = _layered_pools(rs)
    b, nkv, bs, hd = len(ctx), 2, 8, 32
    k = jnp.asarray(rs.randn(b, t, nkv, hd).astype(np.float32))
    v = jnp.asarray(rs.randn(b, t, nkv, hd).astype(np.float32))
    bt = _tables(rs, b, 5, 24)
    ctx, lens = jnp.asarray(ctx, jnp.int32), jnp.asarray(lens, jnp.int32)
    write = jax.jit(lambda layer: pa.paged_kv_write(
        k, v, kp, vp, bt, ctx, lens, layer=layer))
    got = write(jnp.asarray(1, jnp.int32))
    want = pa.paged_kv_write_xla(k, v, kp, vp, bt, ctx, lens, layer=1)
    assert got[2] is None and got[3] is None
    # the rows, read back from where the tables put them
    for pool, rows in ((got[0], k), (got[1], v)):
        np.testing.assert_array_equal(np.asarray(pool), np.asarray(
            want[0] if pool is got[0] else want[1]))
        for i in range(b):
            for ti in range(int(lens[i])):
                pos = int(ctx[i]) + ti
                np.testing.assert_array_equal(
                    np.asarray(pool[1, bt[i, pos // bs], :, pos % bs]),
                    np.asarray(rows[i, ti]))
    # nothing else moved: as many slots differ as rows were written
    changed = np.any(np.asarray(got[0]) != np.asarray(kp), axis=(2, 4))
    assert changed.sum() == int(lens.sum())
    assert not changed[0].any() and not changed[2].any()
    assert not changed[:, 0].any()          # the trash block stays as it was


def test_paged_kv_write_on_one_layers_pool():
    """A 4-D pool is one layer's: no layer to give, the same overlay."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    rs = np.random.RandomState(6)
    kp, vp = (p[0] for p in _layered_pools(rs, L=1))
    k = jnp.asarray(rs.randn(2, 3, 2, 32).astype(np.float32))
    bt = _tables(rs, 2, 4, 24)
    ctx, lens = jnp.asarray([6, 17], jnp.int32), jnp.asarray([3, 2], jnp.int32)
    got = pa.paged_kv_write(k, -k, kp, vp, bt, ctx, lens)
    want = pa.paged_kv_write_xla(k, -k, kp, vp, bt, ctx, lens)
    for g, w in zip(got[:2], want[:2]):
        assert g.shape == kp.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    with pytest.raises(AssertionError, match="needs the layer"):
        pa.paged_kv_write(k, k, kp[None], vp[None], bt, ctx, lens)


def test_flash_causal_kv_longer_than_q():
    """kv_len > sq with causal=True is API-legal (trailing keys fully
    masked); the dead-step DMA fold must clamp the dkv kernel's q-side
    index to the last real q block (round-5 OOB regression)."""
    from deepspeed_tpu.ops.attention import attention_xla
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    rs = np.random.RandomState(5)
    b, sq, skv, h, d = 1, 64, 192, 2, 32
    q = jnp.asarray(rs.randn(b, sq, h, d).astype(np.float32))
    k = jnp.asarray(rs.randn(b, skv, h, d).astype(np.float32))
    v = jnp.asarray(rs.randn(b, skv, h, d).astype(np.float32))

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32) ** 2)

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True)),
        np.asarray(attention_xla(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)
    gk = jax.grad(lambda k_: loss(flash_attention, q, k_, v))(k)
    gx = jax.grad(lambda k_: loss(attention_xla, q, k_, v))(k)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gx),
                               rtol=2e-4, atol=2e-4)
    # trailing (fully-masked) keys must receive exactly zero gradient
    assert (np.asarray(gk)[:, sq:] == 0).all()


# --------------------------------------------------------------------------- #
# the registry's one per-device wrapper (ops/registry._per_device): a Pallas
# kernel in a program that spans a mesh runs under a shard_map with its rows
# over the data-parallel axes, or raises — it is never swapped for XLA
# --------------------------------------------------------------------------- #
def _attention_case():
    b, s, h, kvh, d = 4, 64, 4, 2, 32
    q, k, v = (rand(i, (b, s, n, d)) for i, n in enumerate((h, kvh, kvh)))
    return (q, k, v), lambda f, q, k, v: jnp.sum(f(q, k, v, causal=True) ** 2)


def _rms_case():
    args = (rand(0, (4, 16, 256)), rand(1, (256,)))
    return args, lambda f, x, w: jnp.sum(f(x, w, 1e-6) ** 2)


def _layer_norm_case():
    args = (rand(0, (8, 256)), rand(1, (256,)), rand(2, (256,)))
    return args, lambda f, x, w, b: jnp.sum(f(x, w, b, 1e-5) ** 2)


def _quantize_roundtrip_case():
    def loss(f, x):  # f is quantize; dequantize resolves the same way
        from deepspeed_tpu.ops import registry

        q, scales = f(x, 128)
        assert q.dtype == jnp.int8 and scales.shape == (x.size // 128,)
        return jnp.sum(registry.get_op("dequantize_int8")(q, scales, 128))

    return (rand(0, (8, 512)),), loss


PER_DEVICE_CASES = {
    "attention": _attention_case,
    "rms_norm": _rms_case,
    "layer_norm": _layer_norm_case,
    "quantize_int8": _quantize_roundtrip_case,
}


@pytest.fixture
def pallas_everywhere(monkeypatch):
    """The registry resolves as on the chip (kernels stay in interpret mode)."""
    from deepspeed_tpu.ops import registry

    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    return registry


@pytest.mark.parametrize("mesh_axes", [{"data": 4}, {"data": 2, "expert": 2}],
                         ids=["data4", "data2_expert2"])
@pytest.mark.parametrize("op", sorted(PER_DEVICE_CASES))
def test_pallas_op_over_a_data_parallel_mesh(devices8, pallas_everywhere,
                                             op, mesh_axes):
    """Value and every gradient (a replicated weight's is summed over the
    mesh) equal the XLA reference, and the program has the shard_map."""
    from deepspeed_tpu.comm import mesh as mesh_lib

    registry = pallas_everywhere
    mm = mesh_lib.MeshManager.create(mesh_axes, devices=devices8[:4])
    args, loss = PER_DEVICE_CASES[op]()
    differentiable = op != "quantize_int8"

    def run(f):
        fn = functools.partial(loss, f)
        if differentiable:
            fn = jax.value_and_grad(fn, argnums=tuple(range(len(args))))
        return jax.jit(fn), fn

    assert registry.resolved()[op] == "pallas"
    with mm.activate():
        jitted, fn = run(registry.get_op(op))
        assert "shard_map" in str(jax.make_jaxpr(fn)(*args))
        got = jitted(*args)
    want = run(registry.available_backends(op)["xla"])[0](*args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)


def _paged_call(registry):
    pool = jnp.zeros((8, 2, 16, 32))
    return lambda: registry.get_op("paged_decode_attention")(
        jnp.zeros((4, 4, 32)), pool, pool, jnp.zeros((4, 4), jnp.int32),
        jnp.ones((4,), jnp.int32))


def _attention_call(registry, b=4, **kw):
    q = jnp.zeros((b, 64, 4, 32))
    return lambda: registry.get_op("attention")(q, q, q, **kw)


NO_LAYOUT_CASES = {
    # (mesh axes, call): why the kernel cannot run there
    "paged_decode_has_no_layout": ({"data": 4}, _paged_call),
    "tensor_axis_is_not_covered": ({"data": 2, "tensor": 2}, _attention_call),
    "bias_is_a_keyword_array": (
        {"data": 4}, lambda r: _attention_call(
            r, bias=jnp.zeros((4, 4, 64, 64)), causal=False)),
    "rows_do_not_divide": ({"data": 4},
                           lambda r: _attention_call(r, b=2)),
    # Mosaic refuses a kernel under a shard_map over only some of the axes
    "inside_a_partly_manual_region": (
        {"data": 2, "pipe": 2}, lambda r: jax.shard_map(
            _attention_call(r), in_specs=(), out_specs=P(),
            axis_names={"pipe"}, check_vma=False)),
}


@pytest.mark.parametrize("case", sorted(NO_LAYOUT_CASES))
def test_pallas_op_without_a_layout_raises(devices8, pallas_everywhere, case):
    from deepspeed_tpu.comm import mesh as mesh_lib

    mesh_axes, make = NO_LAYOUT_CASES[case]
    mm = mesh_lib.MeshManager.create(mesh_axes, devices=devices8[:4])
    call = make(pallas_everywhere)
    with mm.activate(), pytest.raises(NotImplementedError,
                                      match="per-device program"):
        jax.jit(call)()


def test_mesh_of_the_trace_decides_not_the_global_mesh(devices8,
                                                       pallas_everywhere):
    """A one-device program under a four-device trainer's global mesh calls
    the kernel as is; the trainer's own trace wraps it whatever the global
    mesh says; inside the caller's own manual region it is called as is."""
    from deepspeed_tpu.comm import mesh as mesh_lib

    registry = pallas_everywhere
    x, w = rand(0, (8, 256)), rand(1, (256,))
    norm = lambda x, w: registry.get_op("rms_norm")(x, w, 1e-6)  # noqa: E731

    four = mesh_lib.init_mesh({"data": 4}, devices=devices8[:4])
    assert "shard_map" not in str(jax.make_jaxpr(norm)(x, w))
    one = mesh_lib.init_mesh({"data": 1}, devices=devices8[:1])
    with one.activate():
        assert "shard_map" not in str(jax.make_jaxpr(norm)(x, w))
    with four.activate():
        assert "shard_map" in str(jax.make_jaxpr(norm)(x, w))
        manual = jax.shard_map(norm, in_specs=(P("data"), P()),
                               out_specs=P("data"), check_vma=False)
        assert str(jax.make_jaxpr(manual)(x, w)).count("shard_map") == 1


# --------------------------------------------------------------------------- #
# a multi-token segment's state-space scan on the state pool
# --------------------------------------------------------------------------- #
SSM_SCAN_CASES = {
    # (b, t, heads, P, groups, rows (5: the trash row), fresh): what it holds
    "one_group_whole_tiles": (1, 256, 4, 32, 1, (2,), (False,)),
    "eight_groups": (1, 128, 32, 32, 8, (3,), (False,)),
    "tokens_off_the_tile": (1, 200, 4, 64, 2, (0,), (False,)),
    "under_one_tile": (2, 24, 4, 32, 1, (4, 1), (False, False)),
    "fresh_beside_carried": (2, 128, 4, 64, 1, (1, 3), (True, False)),
    "a_row_on_the_trash_row": (3, 128, 2, 128, 1, (2, 5, 0),
                               (False, False, True)),
    "two_trash_rows_last": (3, 130, 8, 32, 2, (4, 5, 5),
                            (False, True, False)),
}


def _ssm_scan_case(case, N=128, tail=8, dtype=jnp.float32):
    b, t, H, P, G, rows, fresh = SSM_SCAN_CASES[case]
    k = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    pool = jax.random.normal(k[0], (3, 6, N + tail, H * P), jnp.float32)
    x = jax.random.normal(k[1], (b, t, H, P), dtype)
    # a row's last tokens are padding (dt = 0), a different count a row
    real = t - jnp.arange(b) * 5 - 3
    dt = jnp.where(jnp.arange(t)[None, :, None] < real[:, None, None],
                   jax.nn.softplus(jax.random.normal(k[2], (b, t, H)) - 1),
                   0.0)
    A = -jax.random.uniform(k[3], (H,), jnp.float32, 1.0, 16.0)
    shape = (b, t, N) if G == 1 else (b, t, G, N)
    B, C = (jax.random.normal(k[i], shape, dtype) for i in (4, 5))
    return pool, jnp.asarray(rows), jnp.asarray(fresh), x, dt, A, B, C


@pytest.mark.parametrize("case", sorted(SSM_SCAN_CASES))
def test_ssm_chunk_scan_is_the_recurrence_on_the_pool(case):
    """``ssm_chunk_scan`` (Mosaic, interpreted) and the write of its rows
    against the token-by-token recurrence in float32: ``y`` of every live
    row, the rows' new state on the state part of their layer, and NOTHING
    else of the pool touched - the tail part under the state, the other
    rows, the other layers - the trash row apart, whose call rows read
    zeros."""
    from deepspeed_tpu.ops import ssm
    from deepspeed_tpu.ops.pallas import ssm as kernels
    from deepspeed_tpu.ops.pallas import ssm_scan

    pool, rows, fresh, x, dt, A, B, C = _ssm_scan_case(case)
    b, t, H, P = x.shape
    N, trash, layer = B.shape[-1], pool.shape[1] - 1, 1
    assert ssm_scan.takes(N, H, P, 1 if B.ndim == 3 else B.shape[2],
                          pool.dtype)
    y, new = jax.jit(ssm_scan.ssm_chunk_scan, static_argnums=9)(
        pool, layer, rows, fresh, x, dt, A, B, C, 16)
    got = kernels.state_rows_write(pool, layer, rows, new, (0, N, H * P))
    h0 = jnp.where(fresh[:, None, None, None], 0.0,
                   ssm.state_to_heads(pool[layer, rows, :N], H))
    want_y, want_h = ssm.ssm_recurrence(x, dt, A, B, C, h0)
    live = np.asarray(rows) != trash
    np.testing.assert_allclose(
        np.asarray(y)[live], np.asarray(want_y.reshape(b, t, -1))[live],
        rtol=2e-5, atol=2e-4)
    assert not np.asarray(y)[~live].any()
    want = np.array(pool)
    want[layer, np.asarray(rows)[live], :N] = \
        np.asarray(ssm.state_from_heads(want_h))[live]
    got = np.asarray(got)
    np.testing.assert_allclose(got[layer, :trash, :N],
                               want[layer, :trash, :N], rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(got[layer, :trash, N:],
                                  want[layer, :trash, N:])
    np.testing.assert_array_equal(got[[0, 2]], want[[0, 2]])


@pytest.mark.parametrize("why, sizes", [
    ("lanes_off_the_tile", (16, 8, 8, 1, "float32")),
    ("a_group_off_the_tile", (128, 4, 64, 4, "float32")),
    ("state_off_the_tile", (64, 4, 64, 1, "float32")),
    ("a_narrow_head", (128, 16, 16, 1, "float32")),
    ("a_bfloat16_state", (128, 4, 64, 1, "bfloat16")),
])
def test_ssm_chunk_scan_leaves_what_it_cannot_tile_to_xla(why, sizes):
    """The shape predicate: sizes the kernel does not tile take the XLA
    form inside the Pallas wrapper - the same program as the XLA backend's,
    no ``pallas_call`` of the scan's in it."""
    from deepspeed_tpu.ops import ssm
    from deepspeed_tpu.ops.pallas import ssm_scan

    N, H, P, G, dtype = sizes
    assert not ssm_scan.takes(N, H, P, G, dtype)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    b, t = 2, 24
    pool = jax.random.normal(k[0], (2, 4, N + 8, H * P), jnp.dtype(dtype))
    x = jax.random.normal(k[1], (b, t, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[2], (b, t, H)))
    shape = (b, t, N) if G == 1 else (b, t, G, N)
    B, C = (jax.random.normal(k[i], shape) for i in (3, 4))
    args = (pool, 1, jnp.asarray([2, 0]), jnp.asarray([True, False]), x, dt,
            -jnp.ones((H,)), B, C)
    text = str(jax.make_jaxpr(
        lambda *a: ssm_scan.ssm_chunk_scan(*a, 8))(*args))
    assert "ssm_chunk_scan" not in text
    got = ssm_scan.ssm_chunk_scan(*args, 8)
    want = ssm.ssm_chunk_scan_xla(*args, 8)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
