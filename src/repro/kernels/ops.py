"""Jit'd wrappers dispatching to the Pallas kernels or the pure-jnp
references.

``combine_lse`` merges partial attention results computed over disjoint KV
sources using their log-sum-exp stats — mathematically identical to a joint
softmax over the concatenation (flash-decoding combination), which is how
paper Algorithm 1's  softmax(concat(S_past, S_predict))  is realised
without materialising the concat.

Interpret mode is not a dispatcher option: each kernel decides it from
the platform when it is traced (``repro.kernels.interpret_mode``), so the
kernels interpret off the TPU and never on it.

Quantized paths: passing per-row ``k_scale``/``v_scale`` side tensors
marks K/V as symmetric int8 and fuses the dequant into the kernels;
``dequant_matmul``/``quant_matmul`` dispatch the fused int8-weight matmul
(kernel vs jnp oracle under the same policy, defaulting to the jnp path
unless ``REPRO_USE_PALLAS_QUANT=1`` — mirroring ``USE_PALLAS_ATTN``).
"""
from __future__ import annotations

import math
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import quant as qz
from repro.kernels import ref
from repro.kernels.flash import flash_attention_lse
from repro.kernels.tree_block import tree_block_attention

# Kernel-vs-jnp policy for the fused dequant-matmul at weight-projection
# call sites (interpret-mode Pallas is slow on CPU CI, so the jnp oracle
# is the host default, like USE_PALLAS_ATTN for the attention paths).
USE_PALLAS_QUANT = os.environ.get("REPRO_USE_PALLAS_QUANT", "0") == "1"


def combine_lse(parts):
    """parts: list of (o [B,H,n,hd], m [B,H,n,1+], l [B,H,n,1+]).

    o are normalised within their source; m/l are that source's softmax
    stats.  Returns the exact joint-softmax combination.
    """
    ms = jnp.stack([m[..., :1] for _, m, _ in parts])      # [P,B,H,n,1]
    m_all = jnp.max(ms, axis=0)
    num = 0.0
    den = 0.0
    for o, m, l in parts:
        w = l[..., :1] * jnp.exp(m[..., :1] - m_all)       # [B,H,n,1]
        num = num + w * o.astype(jnp.float32)
        den = den + w
    return (num / jnp.maximum(den, 1e-30))


def tree_attention(q, k_past, v_past, k_tree, v_tree, tree_mask, past_len,
                   *, scale=None, window: int = 0, qpos=None,
                   use_kernel: bool = True, block_k: int = 512,
                   k_scale=None, v_scale=None, kt_scale=None,
                   vt_scale=None):
    """Two-level tree attention — see kernels/ref.py for the oracle.

    ``past_len`` may be a scalar or per-row [B], ``tree_mask`` [n,T] or
    per-row [B,n,T] (the SpecPipe-DB fused dispatch stacks one request per
    batch row, each with its own committed prefix and ancestor mask).

    Quantized caches pass int8 k/v plus per-row f32 scales
    (``k_scale``/``v_scale`` [B,KV,Lmax] for the past half,
    ``kt_scale``/``vt_scale`` [B,KV,T] for the tree half); the dequant
    fuses into both kernels, and the jnp fallback uses the quant oracle.
    """
    quant = k_scale is not None
    if not use_kernel:
        if quant:
            return ref.tree_attention_quant_ref(
                q, k_past, v_past, k_tree, v_tree, tree_mask, past_len,
                k_scale=k_scale, v_scale=v_scale, kt_scale=kt_scale,
                vt_scale=vt_scale, scale=scale)
        return ref.tree_attention_ref(q, k_past, v_past, k_tree, v_tree,
                                      tree_mask, past_len, scale=scale)
    op, mp, lp = flash_attention_lse(q, k_past, v_past, past_len, qpos,
                                     k_scale=k_scale, v_scale=v_scale,
                                     scale=scale, window=window,
                                     block_k=block_k)
    ot, mt, lt = tree_block_attention(q, k_tree, v_tree, tree_mask,
                                      k_scale=kt_scale, v_scale=vt_scale,
                                      scale=scale)
    out = combine_lse([(op, mp, lp), (ot, mt, lt)])
    return out.astype(q.dtype)


def paged_tree_attention(q, k_pool, v_pool, table, kt_pool, vt_pool,
                         t_table, tree_mask, past_len, *, scale=None,
                         use_kernel: bool = True,
                         k_scale=None, v_scale=None, kt_scale=None,
                         vt_scale=None):
    """Two-level tree attention over *paged* caches: K/V live in blocked
    pools [Nb,KV,page,hd] indexed through per-slot block tables [B,mb]
    (``models.paging``), gathered tile-by-tile inside the kernels via
    scalar-prefetch table refs.  Same LSE combination as
    ``tree_attention``; int8 pools pass blocked per-row scale pools
    [Nb,KV,page]."""
    if not use_kernel:
        return ref.paged_tree_attention_ref(
            q, k_pool, v_pool, table, kt_pool, vt_pool, t_table, tree_mask,
            past_len, k_scale=k_scale, v_scale=v_scale, kt_scale=kt_scale,
            vt_scale=vt_scale, scale=scale)
    from repro.kernels.paged import (paged_flash_attention_lse,
                                     paged_tree_block_attention)
    op, mp, lp = paged_flash_attention_lse(q, k_pool, v_pool, table,
                                           past_len, k_scale=k_scale,
                                           v_scale=v_scale, scale=scale)
    ot, mt, lt = paged_tree_block_attention(q, kt_pool, vt_pool, t_table,
                                            tree_mask, k_scale=kt_scale,
                                            v_scale=vt_scale, scale=scale)
    out = combine_lse([(op, mp, lp), (ot, mt, lt)])
    return out.astype(q.dtype)


def paged_decode_attention(q, k_pool, v_pool, table, kv_len, *, scale=None,
                           window: int = 0, use_kernel: bool = True,
                           k_scale=None, v_scale=None):
    """Flash-decode over a paged KV cache: pools [Nb,KV,page,hd] +
    block table [B,mb]; ``kv_len`` scalar or per-row [B]."""
    if not use_kernel:
        return ref.paged_decode_attention_ref(
            q, k_pool, v_pool, table, kv_len, k_scale=k_scale,
            v_scale=v_scale, window=window, scale=scale)
    from repro.kernels.paged import paged_flash_attention_lse
    n = q.shape[2]
    kv_len = jnp.asarray(kv_len, jnp.int32)
    qpos = jnp.broadcast_to((kv_len - 1).reshape(-1, 1)
                            if kv_len.ndim else kv_len - 1,
                            (q.shape[0], n))
    o, _, _ = paged_flash_attention_lse(q, k_pool, v_pool, table, kv_len,
                                        qpos, k_scale=k_scale,
                                        v_scale=v_scale, scale=scale,
                                        window=window)
    return o.astype(q.dtype)


def prefill_attention(q, k, v, positions, *, scale=None, window: int = 0,
                      block_k: int = 512, block_q: int = 512):
    """Causal flash attention for prefill/training — q: [B,H,S,hd],
    k/v: [B,KV,S,hd], positions: [S]."""
    o, _, _ = flash_attention_lse(
        q, k, v, k.shape[2], positions, scale=scale, window=window,
        causal=True, block_k=block_k, block_q=min(block_q, q.shape[2]))
    return o.astype(q.dtype)


def decode_attention(q, k, v, kv_len, *, scale=None, window: int = 0,
                     use_kernel: bool = True, block_k: int = 512,
                     k_scale=None, v_scale=None):
    """Single-/few-token decode over a long KV cache (optionally int8
    with per-row ``k_scale``/``v_scale`` [B,KV,Lmax] dequantized
    in-kernel)."""
    if not use_kernel:
        if k_scale is not None:
            return ref.decode_attention_quant_ref(
                q, k, v, kv_len, k_scale=k_scale, v_scale=v_scale,
                window=window, scale=scale)
        return ref.decode_attention_ref(q, k, v, kv_len, window=window,
                                        scale=scale)
    n = q.shape[2]
    qpos = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32) - 1, (n,))
    o, _, _ = flash_attention_lse(q, k, v, kv_len, qpos, k_scale=k_scale,
                                  v_scale=v_scale, scale=scale,
                                  window=window, block_k=block_k)
    return o.astype(q.dtype)


def dequant_matmul(x, w_q, w_scale, *, use_kernel: Optional[bool] = None,
                   block_m: int = 128, block_n: int = 128,
                   block_k: int = 128):
    """Fused dequant-matmul: x [M,K] f32 @ int8 w_q [K,N] with
    per-out-channel f32 scales [N] -> [M,N] f32.  ``use_kernel=None``
    follows the ``USE_PALLAS_QUANT`` module policy."""
    if use_kernel is None:
        use_kernel = USE_PALLAS_QUANT
    if not use_kernel:
        return ref.dequant_matmul_ref(x, w_q, w_scale)
    return qz.dequant_matmul_kernel(x, w_q, w_scale, block_m=block_m,
                                    block_n=block_n, block_k=block_k)


def quant_matmul(x, w, *, use_kernel: Optional[bool] = None):
    """Apply a quantized weight dict ``{"q8", "scale"}`` to ``x``,
    contracting x's trailing axes with w's leading (first
    ``q8.ndim - scale.ndim``) axes — the generalised einsum every
    quantized projection call site routes through.  Shapes collapse to
    one 2-D ``dequant_matmul`` and reshape back."""
    q8, scale = w["q8"], w["scale"]
    nin = q8.ndim - scale.ndim
    kdim = math.prod(q8.shape[:nin])
    out_shape = q8.shape[nin:]
    batch = x.shape[:x.ndim - nin]
    y = dequant_matmul(x.reshape(-1, kdim).astype(jnp.float32),
                       q8.reshape(kdim, -1), scale.reshape(-1),
                       use_kernel=use_kernel)
    return y.reshape(*batch, *out_shape)
