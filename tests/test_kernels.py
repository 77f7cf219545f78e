"""Pallas kernel sweeps (interpret mode) against the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash import flash_attention_lse
from repro.kernels.quant import dequantize_rows, quantize_rows
from repro.kernels.tree_block import tree_block_attention


def rand(rng, shape, dtype):
    x = rng.normal(size=shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=4e-2, atol=4e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,n,hd,lmax,t", [
    (1, 4, 2, 8, 64, 96, 16),
    (2, 2, 1, 4, 128, 64, 8),
    (1, 8, 8, 16, 32, 256, 32),
])
def test_tree_attention_sweep(b, h, kv, n, hd, lmax, t, dtype):
    rng = np.random.default_rng(hash((b, h, n)) % 2**31)
    q = rand(rng, (b, h, n, hd), dtype)
    kp = rand(rng, (b, kv, lmax, hd), dtype)
    vp = rand(rng, (b, kv, lmax, hd), dtype)
    kt = rand(rng, (b, kv, t, hd), dtype)
    vt = rand(rng, (b, kv, t, hd), dtype)
    mask = jnp.asarray(rng.random((n, t)) > 0.4).at[:, 0].set(True)
    plen = lmax // 2
    out = ops.tree_attention(q, kp, vp, kt, vt, mask, plen, block_k=32)
    want = ref.tree_attention_ref(q.astype(jnp.float32),
                                  kp.astype(jnp.float32),
                                  vp.astype(jnp.float32),
                                  kt.astype(jnp.float32),
                                  vt.astype(jnp.float32), mask, plen)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 16])
@pytest.mark.parametrize("b,h,kv,hd,lmax", [
    (1, 4, 2, 64, 128),
    (2, 8, 1, 128, 64),
])
def test_decode_attention_sweep(b, h, kv, hd, lmax, window, dtype):
    rng = np.random.default_rng(hash((b, h, hd, window)) % 2**31)
    q = rand(rng, (b, h, 1, hd), dtype)
    k = rand(rng, (b, kv, lmax, hd), dtype)
    v = rand(rng, (b, kv, lmax, hd), dtype)
    klen = lmax - 7
    out = ops.decode_attention(q, k, v, klen, window=window, block_k=32)
    want = ref.decode_attention_ref(q.astype(jnp.float32),
                                    k.astype(jnp.float32),
                                    v.astype(jnp.float32), klen,
                                    window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), **TOL[dtype])


def test_combine_lse_equals_joint_softmax():
    """Flash-decoding combination over two KV sources == joint softmax."""
    rng = np.random.default_rng(0)
    b, h, kv, n, hd = 1, 2, 2, 4, 32
    q = rand(rng, (b, h, n, hd), jnp.float32)
    k1 = rand(rng, (b, kv, 64, hd), jnp.float32)
    v1 = rand(rng, (b, kv, 64, hd), jnp.float32)
    k2 = rand(rng, (b, kv, 32, hd), jnp.float32)
    v2 = rand(rng, (b, kv, 32, hd), jnp.float32)
    p1 = flash_attention_lse(q, k1, v1, 64, block_k=32)
    mask = jnp.ones((n, 32), bool)
    p2 = tree_block_attention(q, k2, v2, mask)
    got = ops.combine_lse([p1, p2])
    want = ref.tree_attention_ref(q, k1, v1, k2, v2, mask, 64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_flash_zero_length_prefix_safe():
    """past_len=0 must not produce NaNs (fresh-context tree attention)."""
    rng = np.random.default_rng(1)
    q = rand(rng, (1, 2, 4, 32), jnp.float32)
    k = rand(rng, (1, 2, 64, 32), jnp.float32)
    v = rand(rng, (1, 2, 64, 32), jnp.float32)
    o, m, l = flash_attention_lse(q, k, v, 0, block_k=32)
    assert np.isfinite(np.asarray(o)).all()
    assert (np.asarray(l[..., 0]) == 0).all()
    kt = rand(rng, (1, 2, 8, 32), jnp.float32)
    vt = rand(rng, (1, 2, 8, 32), jnp.float32)
    mask = jnp.ones((4, 8), bool)
    out = ops.tree_attention(q, k, v, kt, vt, mask, 0, block_k=32)
    want = ref.tree_attention_ref(q, k, v, kt, vt, mask, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# int8 quantization (KV rows + weights)
# ---------------------------------------------------------------------------

def test_quantize_rows_roundtrip_bound():
    """Round-trip error is bounded by scale/2 = amax/254 per element."""
    rng = np.random.default_rng(11)
    x = rand(rng, (2, 3, 17, 32), jnp.float32) * 3.0
    q, s = quantize_rows(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 3, 17)
    back = dequantize_rows(q, s)
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    assert (np.abs(np.asarray(back) - np.asarray(x))
            <= amax / 254 + 1e-7).all()
    # saturation: the per-row extrema map to exactly +/-127
    assert (np.max(np.abs(np.asarray(q)), axis=-1) == 127).all()


def test_quantize_rows_zero_rows_exact():
    """All-zero rows (padded/unwritten cache slots) round-trip bit-exactly
    with scale 1 — no NaN/inf from a zero amax."""
    x = jnp.zeros((1, 2, 4, 8), jnp.float32).at[0, 0, 0].set(1.0)
    q, s = quantize_rows(x)
    assert np.asarray(s)[0, 0, 1:].tolist() == [1.0, 1.0, 1.0]
    back = np.asarray(dequantize_rows(q, s))
    assert (back[0, 0, 1:] == 0).all() and (back[0, 1] == 0).all()
    np.testing.assert_allclose(back[0, 0, 0], np.asarray(x)[0, 0, 0],
                               atol=1 / 254)


@pytest.mark.parametrize("b,h,kv,n,hd,lmax,t", [
    (1, 4, 2, 8, 64, 96, 16),
    (2, 2, 1, 4, 32, 64, 8),
])
def test_tree_attention_quant_kernel_vs_ref(b, h, kv, n, hd, lmax, t):
    """int8 K/V with per-row scales, fused in-kernel dequant: the kernel
    path must match the quant oracle under per-row [B] past_len and
    per-row [B,n,T] tree masks (the fused SpecPipe-DB dispatch shape)."""
    rng = np.random.default_rng(hash((b, h, n, t)) % 2**31)
    q = rand(rng, (b, h, n, hd), jnp.float32)
    kp = rand(rng, (b, kv, lmax, hd), jnp.float32)
    vp = rand(rng, (b, kv, lmax, hd), jnp.float32)
    kt = rand(rng, (b, kv, t, hd), jnp.float32)
    vt = rand(rng, (b, kv, t, hd), jnp.float32)
    kpq, kps = quantize_rows(kp)
    vpq, vps = quantize_rows(vp)
    ktq, kts = quantize_rows(kt)
    vtq, vts = quantize_rows(vt)
    mask = jnp.asarray(
        rng.random((b, n, t)) > 0.4).at[:, :, 0].set(True)
    plen = jnp.asarray(rng.integers(1, lmax, size=b), jnp.int32)
    quant_kw = dict(k_scale=kps, v_scale=vps, kt_scale=kts, vt_scale=vts)
    out = ops.tree_attention(q, kpq, vpq, ktq, vtq, mask, plen,
                             block_k=32, **quant_kw)
    want = ref.tree_attention_quant_ref(q, kpq, vpq, ktq, vtq, mask, plen,
                                        **quant_kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # and the fused-dequant math matches fp32 attention over the
    # dequantized tensors (no separate approximation inside the kernel)
    full = ref.tree_attention_ref(q, dequantize_rows(kpq, kps),
                                  dequantize_rows(vpq, vps),
                                  dequantize_rows(ktq, kts),
                                  dequantize_rows(vtq, vts), mask, plen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [0, 16])
def test_decode_attention_quant_kernel_vs_ref(window):
    rng = np.random.default_rng(13 + window)
    b, h, kv, hd, lmax = 2, 4, 2, 32, 64
    q = rand(rng, (b, h, 1, hd), jnp.float32)
    k = rand(rng, (b, kv, lmax, hd), jnp.float32)
    v = rand(rng, (b, kv, lmax, hd), jnp.float32)
    kq, ks = quantize_rows(k)
    vq, vs = quantize_rows(v)
    klen = lmax - 7
    out = ops.decode_attention(q, kq, vq, klen, window=window, block_k=32,
                               k_scale=ks, v_scale=vs)
    want = ref.decode_attention_quant_ref(q, kq, vq, klen, k_scale=ks,
                                          v_scale=vs, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("m,k,n", [(7, 33, 19), (128, 128, 128),
                                   (130, 96, 200)])
def test_dequant_matmul_kernel_vs_ref(m, k, n):
    """Fused Pallas dequant-matmul (incl. ragged shapes that pad to the
    block grid) against the jnp oracle."""
    from repro.kernels.quant import quantize_weight
    rng = np.random.default_rng(hash((m, k, n)) % 2**31)
    x = rand(rng, (m, k), jnp.float32)
    w = rand(rng, (k, n), jnp.float32)
    wq = quantize_weight(w, 1)
    out = ops.dequant_matmul(x, wq["q8"], wq["scale"], use_kernel=True,
                             block_m=64, block_n=64, block_k=32)
    want = ref.dequant_matmul_ref(x, wq["q8"], wq["scale"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dequant_matmul_zero_channel_scale():
    """An all-zero output channel quantizes to scale 1 / q8 0 and the
    kernel must produce exact zeros for it (no NaN from a 0 scale)."""
    from repro.kernels.quant import quantize_weight
    rng = np.random.default_rng(17)
    x = rand(rng, (5, 16), jnp.float32)
    w = rng.normal(size=(16, 8)).astype(np.float32)
    w[:, 3] = 0.0
    wq = quantize_weight(jnp.asarray(w), 1)
    assert float(wq["scale"][3]) == 1.0
    out = np.asarray(ops.dequant_matmul(x, wq["q8"], wq["scale"],
                                        use_kernel=True, block_m=8,
                                        block_n=8, block_k=8))
    assert (out[:, 3] == 0).all()
    want = np.asarray(ref.dequant_matmul_ref(x, wq["q8"], wq["scale"]))
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 11])
@pytest.mark.parametrize("b,h,kv,s,hd", [(1, 4, 2, 96, 32), (2, 2, 1, 64, 64)])
def test_prefill_causal_flash_sweep(b, h, kv, s, hd, window, dtype):
    rng = np.random.default_rng(hash((b, s, window)) % 2**31)
    q = rand(rng, (b, h, s, hd), dtype)
    k = rand(rng, (b, kv, s, hd), dtype)
    v = rand(rng, (b, kv, s, hd), dtype)
    pos = jnp.arange(s)
    got = ops.prefill_attention(q, k, v, pos, window=window, block_k=32,
                                block_q=16)
    rep = h // kv
    kr = jnp.repeat(k.astype(jnp.float32), rep, 1)
    vr = jnp.repeat(v.astype(jnp.float32), rep, 1)
    lg = jnp.einsum("bhqd,bhsd->bhqs", q.astype(jnp.float32), kr) / np.sqrt(hd)
    m = pos[None, :] <= pos[:, None]
    if window:
        m &= pos[None, :] > pos[:, None] - window
    lg = jnp.where(m[None, None], lg, -jnp.inf)
    want = jnp.einsum("bhqs,bhsd->bhqd", jax.nn.softmax(lg, -1), vr)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), **TOL[dtype])
