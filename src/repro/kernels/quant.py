"""Per-block symmetric int8 quantization + the fused dequant-matmul kernel.

Two quantization granularities serve the serving path:

  * **KV rows** (``quantize_rows``): one f32 scale per cache row, i.e. per
    (batch, position, kv-head) slice, reducing over ``head_dim``.  This is
    tile-granular with respect to the attention kernels' ``block_k`` K/V
    tiling — every ``block_k``-row tile of int8 K/V pairs with the same
    ``block_k``-row tile of scales, so the scales ride the Pallas kernels
    as side refs with identical index maps and the dequant fuses into the
    QK^T / PV loads (fp32 accumulate, as before).
  * **weights** (``quantize_weight``): one f32 scale per *output channel*
    (the trailing axes of the projection), reducing over the contraction
    axes.  A quantized weight is the dict ``{"q8": int8, "scale": f32}``
    where the contraction axes are the first ``q8.ndim - scale.ndim`` axes
    — the convention ``ops.quant_matmul`` applies at every projection call
    site.

Symmetric scheme: ``scale = amax / 127`` (zero slices get scale 1 so the
round-trip is exact zeros, never NaN), ``q = clip(round(x / scale))``,
``dequant = q * scale``.  Round-trip error is bounded by ``scale / 2 =
amax / 254`` per element.

The fused dequant-matmul kernel streams int8 weight tiles through VMEM,
accumulates x @ w in fp32 over ``block_k`` contraction tiles, and applies
the per-out-channel scales once at the final tile — int8 bytes on the
memory bus, fp32 math on the MXU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

Q_MAX = 127.0


def quantize_rows(x, axis: int = -1):
    """Symmetric int8 with one scale per slice along ``axis``.

    Returns ``(q int8, scale f32)`` where ``q`` keeps ``x``'s shape and
    ``scale`` drops ``axis``.  All-zero slices quantize to exact zeros
    (scale 1), so padded/unwritten cache rows round-trip bit-exactly.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / Q_MAX, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -Q_MAX, Q_MAX)
    return q.astype(jnp.int8), jnp.squeeze(s, axis=axis)


def dequantize_rows(q, scale, axis: int = -1):
    """Inverse of ``quantize_rows``: broadcast ``scale`` back over
    ``axis`` (f32 result)."""
    return q.astype(jnp.float32) * jnp.expand_dims(scale, axis)


def quantize_weight(w, n_in: int):
    """Per-out-channel symmetric int8: the first ``n_in`` axes of ``w``
    are the contraction axes (reduced for the amax), the rest are output
    channels.  Returns ``{"q8": int8 [*w.shape], "scale": f32
    [*w.shape[n_in:]]}`` — the dict convention every quantized projection
    call site dispatches on.
    """
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=tuple(range(n_in)))
    s = jnp.where(amax > 0, amax / Q_MAX, 1.0)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -Q_MAX, Q_MAX)
    return {"q8": q.astype(jnp.int8), "scale": s}


def is_quantized(w) -> bool:
    """True for the ``{"q8", "scale"}`` quantized-weight dict."""
    return isinstance(w, dict) and "q8" in w


def dequantize_weight(w):
    """f32 view of a quantized weight dict (scale broadcasts over the
    trailing output-channel axes)."""
    return w["q8"].astype(jnp.float32) * w["scale"]


# ---------------------------------------------------------------------------
# fused dequant-matmul Pallas kernel
# ---------------------------------------------------------------------------

def _dq_matmul_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref):
    kb = pl.program_id(2)
    nkb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)                   # [bm, bk]
    w = w_ref[...].astype(jnp.float32)                   # [bk, bn] (int8 in)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kb == nkb - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] * s_ref[0]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def dequant_matmul_kernel(x, w_q, w_scale, *, block_m: int = 128,
                          block_n: int = 128, block_k: int = 128,
                          interpret: Optional[bool] = None):
    """x [M, K] f32 @ int8 w_q [K, N] with per-out-channel f32 scales [N]
    -> [M, N] f32.  The weight stays int8 on the bus; the scale applies
    once per output tile after the fp32 accumulation (same association as
    ``ref.dequant_matmul_ref``)."""
    m0, kdim0 = x.shape
    _, n0 = w_q.shape
    bm, bn, bk = (min(block_m, m0), min(block_n, n0), min(block_k, kdim0))
    mp, np_, kp = ((-m0) % bm, (-n0) % bn, (-kdim0) % bk)
    if mp or kp:
        x = jnp.pad(x, ((0, mp), (0, kp)))
    if kp or np_:
        w_q = jnp.pad(w_q, ((0, kp), (0, np_)))
    if np_:
        w_scale = jnp.pad(w_scale, ((0, np_),))
    m, n, kdim = m0 + mp, n0 + np_, kdim0 + kp

    out = pl.pallas_call(
        _dq_matmul_kernel,
        grid=(m // bm, n // bn, kdim // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kb: (i, kb)),
            pl.BlockSpec((bk, bn), lambda i, j, kb: (kb, j)),
            pl.BlockSpec((1, bn), lambda i, j, kb: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kb: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(x.astype(jnp.float32), w_q, w_scale.reshape(1, -1))
    return out[:m0, :n0]
