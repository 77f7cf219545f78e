"""Tree-suffix attention block (Pallas) — the speculative half of the
paper's dynamic tree attention.

The tree buffer is small (w·d ≤ a few hundred nodes), so it is one VMEM
tile: a single grid step per (batch, head) computes the masked softmax
against the ancestor mask and emits (o, m, l) stats for exact combination
with the past half (``kernels.flash``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _tree_kernel(q_ref, k_ref, v_ref, mask_ref, *rest, scale, quant):
    # quantized K/V carry per-row scale side refs ([t] each, same head
    # index map) dequantized in-kernel before the fp32 masked softmax
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref = rest
    else:
        o_ref, m_ref, l_ref = rest
    q = q_ref[0, 0].astype(jnp.float32) * scale          # [n, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [t, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    if quant:
        k = k * ks_ref[0, 0][:, None]
        v = v * vs_ref[0, 0][:, None]
    mask = mask_ref[0] != 0                              # [n, t] (this row's)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)               # [n, 1]
    p = jnp.where(mask, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o = o / jnp.maximum(l, 1e-30)
    o_ref[0, 0] = o.astype(o_ref.dtype)
    m_ref[0, 0] = jnp.broadcast_to(m, m_ref.shape[2:]).astype(jnp.float32)
    l_ref[0, 0] = jnp.broadcast_to(l, l_ref.shape[2:]).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def tree_block_attention(q, k_tree, v_tree, tree_mask, *, k_scale=None,
                         v_scale=None, scale=None,
                         interpret: Optional[bool] = None):
    """q: [B,H,n,hd]; k/v_tree: [B,KV,T,hd]; tree_mask: [n,T] bool, or
    per-row [B,n,T] (SpecPipe-DB fused dispatch: each batch row is a
    different request's tree, so each row carries its own ancestor mask).
    k_scale/v_scale [B,KV,T] f32 mark k/v_tree as per-row symmetric int8;
    the dequant fuses into the kernel.

    Returns (o [B,H,n,hd], m [B,H,n,128], l [B,H,n,128]).
    """
    quant = k_scale is not None
    b, h, n, hd = q.shape
    kvh, t = k_tree.shape[1], k_tree.shape[2]
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    if tree_mask.ndim == 2:
        tree_mask = tree_mask[None]
    mask_i8 = jnp.broadcast_to(tree_mask, (b, n, t)).astype(jnp.int8)

    scale_specs, scale_args = [], []
    if quant:
        scale_specs = [pl.BlockSpec((1, 1, t),
                                    lambda i, j: (i, j // rep, 0))] * 2
        scale_args = [k_scale.astype(jnp.float32),
                      v_scale.astype(jnp.float32)]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, n, hd), q.dtype),
        jax.ShapeDtypeStruct((b, h, n, 128), jnp.float32),
        jax.ShapeDtypeStruct((b, h, n, 128), jnp.float32),
    ]
    o, m, l = pl.pallas_call(
        functools.partial(_tree_kernel, scale=scale, quant=quant),
        grid=(b, h),
        in_specs=[
            pl.BlockSpec((1, 1, n, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, t, hd), lambda i, j: (i, j // rep, 0, 0)),
            pl.BlockSpec((1, 1, t, hd), lambda i, j: (i, j // rep, 0, 0)),
            pl.BlockSpec((1, n, t), lambda i, j: (i, 0, 0)),
            *scale_specs,
        ],
        out_specs=[
            pl.BlockSpec((1, 1, n, hd), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, n, 128), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, 1, n, 128), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret_mode(interpret),
    )(q, k_tree, v_tree, mask_i8, *scale_args)
    return o, m, l
