"""Flash attention with running-softmax statistics (Pallas, TPU target).

One kernel serves three call sites:
  * the *past* half of dynamic tree attention (validity = ``kv_len`` prefix,
    per-query sliding window optional),
  * single-token flash-decode over a long KV cache,
  * prefill/causal use via the per-query window/position masking.

The kernel streams K/V in ``block_k``-row VMEM tiles along the last grid
axis and keeps (acc, m, l) in VMEM scratch; outputs are the normalised
attention plus the (m, l) log-sum-exp stats so partial results over
different KV sources can be combined exactly (flash-decoding style) — this
is how the two-level (model + tree) cache attention is assembled without
concatenating caches.

VMEM budget per step ≈ q (n·hd) + 2·(block_k·hd) + acc (n·hd) floats; with
n ≤ 128, hd ≤ 256, block_k = 512 that is ≈ 1.3 MB — well inside the ~16 MB
VMEM of a TPU core, with MXU-aligned (128-multiple) tiles.
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


def _flash_kernel(plen_ref, q_ref, k_ref, v_ref, *rest, scale, block_k,
                  window, causal, quant):
    # quantized K/V ride with per-row scale side refs ([bk] per tile,
    # same index map as k/v) that dequantize in-kernel before the fp32
    # QK^T / PV accumulation
    if quant:
        (ks_ref, vs_ref, qpos_ref, o_ref, m_ref, l_ref,
         acc_ref, ms_ref, ls_ref) = rest
    else:
        qpos_ref, o_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref = rest
    kb = pl.program_id(3)
    nb = pl.num_programs(3)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [n, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
    if quant:
        k = k * ks_ref[0, 0][:, None]
        v = v * vs_ref[0, 0][:, None]
    n = q.shape[0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [n, bk]

    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (n, block_k), 1)
    plen = plen_ref[pl.program_id(0)]     # per-batch-row valid prefix
    valid = kpos < plen
    if causal or window > 0:
        qp = qpos_ref[0, 0][:, :1]                       # [n, 1] int32
        if causal:
            valid &= kpos <= qp
        if window > 0:
            valid &= kpos > qp - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = ms_ref[:, :1]                               # [n, 1]
    l_prev = ls_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)           # [n, 1]
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                               # [n, bk]
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)                      # [n, 1]
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    ms_ref[...] = jnp.broadcast_to(m_new, ms_ref.shape)
    ls_ref[...] = jnp.broadcast_to(l_new, ls_ref.shape)

    @pl.when(kb == nb - 1)
    def _finalize():
        l = ls_ref[:, :1]
        safe = jnp.maximum(l, 1e-30)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)
        m_ref[0, 0] = ms_ref[...].astype(m_ref.dtype)
        l_ref[0, 0] = ls_ref[...].astype(l_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "block_q", "window",
                                             "interpret", "scale", "causal"))
def flash_attention_lse(q, k, v, kv_len, qpos=None, *, k_scale=None,
                        v_scale=None, scale=None,
                        block_k: int = 512, block_q: int = 0,
                        window: int = 0, causal: bool = False,
                        interpret: Optional[bool] = None):
    """q: [B,H,n,hd]; k/v: [B,KV,L,hd]; kv_len: () or per-row [B] int32
    valid prefix (a scalar broadcasts over the batch).

    qpos: [n] or per-row [B,n] int32 absolute query positions (required
    when window > 0 or causal).  block_q tiles the query dim (0 => one tile
    — decode/tree widths; prefill passes e.g. 512).  k_scale/v_scale
    [B,KV,L] f32 mark k/v as per-row symmetric int8: each block_k tile of
    scales rides beside its K/V tile and the dequant fuses into the
    kernel (fp32 accumulate unchanged).  Returns
    (o [B,H,n,hd], m [B,H,n,128], l [B,H,n,128]) — lane-replicated LSE
    stats for flash-decoding combination.
    """
    quant = k_scale is not None
    b, h, n0, hd = q.shape
    kvh, lmax = k.shape[1], k.shape[2]
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    if lmax % block_k:
        pad = block_k - lmax % block_k
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if quant:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, 0), (0, pad)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, 0), (0, pad)))
        lmax += pad
    nb = lmax // block_k
    if qpos is None:
        qpos = jnp.zeros((n0,), jnp.int32)
    qpos = jnp.asarray(qpos, jnp.int32)
    if qpos.ndim == 1:
        qpos = jnp.broadcast_to(qpos[None], (b, n0))
    bq = block_q or n0
    qpad = (-n0) % bq
    n = n0 + qpad
    if qpad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, qpad), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, qpad)))
    nq = n // bq
    qpos2 = jnp.broadcast_to(qpos[:, None, :, None],
                             (b, 1, n, 128)).astype(jnp.int32)
    plen = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))

    grid = (b, h, nq, nb)
    kernel = functools.partial(_flash_kernel, scale=scale, block_k=block_k,
                               window=window, causal=causal, quant=quant)
    out_shape = [
        jax.ShapeDtypeStruct((b, h, n, hd), q.dtype),
        jax.ShapeDtypeStruct((b, h, n, 128), jnp.float32),
        jax.ShapeDtypeStruct((b, h, n, 128), jnp.float32),
    ]
    kv_spec = pl.BlockSpec((1, 1, block_k, hd),
                           lambda i, j, qi, kb, *_: (i, j // rep, kb, 0))
    scale_specs, scale_args = [], []
    if quant:
        scale_specs = [pl.BlockSpec((1, 1, block_k),
                                    lambda i, j, qi, kb, *_:
                                    (i, j // rep, kb))] * 2
        scale_args = [k_scale.astype(jnp.float32),
                      v_scale.astype(jnp.float32)]
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, bq, hd),
                             lambda i, j, qi, kb, *_: (i, j, qi, 0)),
                kv_spec,
                kv_spec,
                *scale_specs,
                pl.BlockSpec((1, 1, bq, 128),
                             lambda i, j, qi, kb, *_: (i, 0, qi, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, bq, hd),
                             lambda i, j, qi, kb, *_: (i, j, qi, 0)),
                pl.BlockSpec((1, 1, bq, 128),
                             lambda i, j, qi, kb, *_: (i, j, qi, 0)),
                pl.BlockSpec((1, 1, bq, 128),
                             lambda i, j, qi, kb, *_: (i, j, qi, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, hd), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret_mode(interpret),
    )(plen, q, k, v, *scale_args, qpos2)
    if qpad:
        o, m, l = o[:, :, :n0], m[:, :, :n0], l[:, :, :n0]
    return o, m, l
