"""REPRO_USE_PALLAS_ATTN=1 path: kernel-backed decode / tree-verify must
match the jnp path exactly (the kernels run in interpret mode on CPU).
Plus the dispatch-policy seams: interpret mode chosen by platform at call
time, and the ``USE_PALLAS_QUANT`` kernel-vs-oracle policy for the fused
dequant-matmul."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.models import attention as A
from repro.models import transformer as tf


def test_kernel_decode_matches_jnp(tiny_dense):
    cfg = tiny_dense
    params = tf.init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 128)
    cache = tf.init_cache(cfg, 2, 16)
    logits, cache = tf.prefill(params, cfg, toks, cache)
    tok = jnp.argmax(logits, -1)

    ref, _ = tf.decode_step(params, cfg, tok,
                            jax.tree.map(lambda x: x, cache), 8)
    old = A.USE_PALLAS_ATTN
    try:
        A.USE_PALLAS_ATTN = True
        got, _ = tf.decode_step(params, cfg, tok,
                                jax.tree.map(lambda x: x, cache), 8)
    finally:
        A.USE_PALLAS_ATTN = old
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_kernel_tree_verify_matches_jnp(tiny_dense):
    cfg = tiny_dense
    params = tf.init_model(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, 128)
    cache = tf.init_cache(cfg, 1, 16)
    logits, cache = tf.prefill(params, cfg, toks, cache)
    root = jnp.argmax(logits, -1)

    tcap = 8
    node_tokens = jnp.zeros((1, 4), jnp.int32).at[0, 0].set(root[0])
    positions = jnp.asarray([[6, 0, 0, 0]], jnp.int32)
    mask = np.zeros((4, tcap), bool)
    mask[0, 0] = True

    def go():
        tcaches = tf.init_tree_caches(cfg, 1, tcap)
        lg, _ = tf.tree_verify_step(params, cfg, node_tokens, positions,
                                    jnp.asarray(mask), cache, 6, tcaches, 0)
        return np.asarray(lg)

    ref = go()
    old = A.USE_PALLAS_ATTN
    try:
        A.USE_PALLAS_ATTN = True
        got = go()
    finally:
        A.USE_PALLAS_ATTN = old
    np.testing.assert_allclose(got[:, 0], ref[:, 0], rtol=2e-4, atol=2e-4)


def test_interpret_resolved_per_call_not_at_import(monkeypatch):
    """Interpret mode follows the platform at call time, never an import-
    time default: off the TPU the dispatchers interpret (and match the
    oracle), ``interpret=False`` compiles for a described chip, and on a
    TPU the interpreter is refused outright."""
    from repro.kernels import interpret_mode

    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(1, 2, 1, 32)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 2, 16, 32)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 2, 16, 32)).astype(np.float32))
    want = ref.decode_attention_ref(q, k, v, 12)
    out = ops.decode_attention(q, k, v, 12, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    assert interpret_mode() is True
    assert interpret_mode(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    assert interpret_mode(False) is False
    with pytest.raises(ValueError, match="never used on a TPU"):
        interpret_mode(True)


def test_quant_matmul_policy_kernel_vs_oracle():
    """use_kernel=None follows USE_PALLAS_QUANT; both backends agree and
    flipping the module flag needs no reimport."""
    from repro.kernels.quant import quantize_weight
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(3, 5, 24)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(24, 10)).astype(np.float32))
    wq = quantize_weight(w, 1)
    want = ref.dequant_matmul_ref(x.reshape(-1, 24), wq["q8"],
                                  wq["scale"]).reshape(3, 5, 10)

    oracle = ops.quant_matmul(x, wq, use_kernel=False)
    kernel = ops.quant_matmul(x, wq, use_kernel=True)
    np.testing.assert_allclose(np.asarray(oracle), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    old = ops.USE_PALLAS_QUANT
    try:
        ops.USE_PALLAS_QUANT = True
        flagged = ops.quant_matmul(x, wq)      # default follows the flag
        np.testing.assert_allclose(np.asarray(flagged), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    finally:
        ops.USE_PALLAS_QUANT = old


def test_quant_matmul_higher_rank_contraction():
    """Attention projections contract >1 axis (e.g. w_o [H, hd, D]): the
    dict convention (first q8.ndim - scale.ndim axes contract) must
    reproduce the einsum on the dequantized weight."""
    from repro.kernels.quant import dequantize_weight, quantize_weight
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(2, 3, 4, 8)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 8, 16)).astype(np.float32))
    wq = quantize_weight(w, 2)
    assert wq["scale"].shape == (16,)
    got = ops.quant_matmul(x, wq, use_kernel=False)
    want = jnp.einsum("bshd,hdo->bso", x, dequantize_weight(wq))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("use_pallas_attn", [False, True])
def test_kernel_paths_match_on_quantized_model(tiny_dense, use_pallas_attn):
    """Quantized tiny model: the Pallas-attention path (fused in-kernel KV
    dequant) must match the jnp path (dense dequant) on decode."""
    import dataclasses
    cfg = dataclasses.replace(tiny_dense, quant="int8")
    params = tf.init_model(jax.random.PRNGKey(0), tiny_dense)
    from repro.core.speculative import ModelBundle
    qb = ModelBundle(params, tiny_dense).quantize()
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 128)

    def go(flag):
        old = A.USE_PALLAS_ATTN
        try:
            A.USE_PALLAS_ATTN = flag
            cache = tf.init_cache(cfg, 2, 16)
            logits, cache = tf.prefill(qb.params, cfg, toks, cache)
            assert cache["stack"][0]["k"].dtype == jnp.int8
            tok = jnp.argmax(logits, -1)
            out, _ = tf.decode_step(qb.params, cfg, tok, cache, 8)
            return np.asarray(out)
        finally:
            A.USE_PALLAS_ATTN = old

    ref_out = go(False)
    if use_pallas_attn:
        got = go(True)
        np.testing.assert_allclose(got, ref_out, rtol=2e-4, atol=2e-4)
    else:
        assert np.isfinite(ref_out).all()
