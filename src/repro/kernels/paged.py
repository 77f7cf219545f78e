"""Paged variants of the attention kernels (Pallas, TPU target).

The dense kernels in ``kernels.flash`` / ``kernels.tree_block`` read K/V
from contiguous ``[B, KV, L, hd]`` caches.  Here the cache is a *paged*
arena (``models.paging``): a flat pool of physical blocks

    k_pool / v_pool : [Nb, KV, page, hd]
    table           : [B, mb] int32     logical block -> physical block

and each grid step's K/V tile is gathered *through the block table* — the
table rides as a scalar-prefetch ref (the same side-ref idiom as the
PR-6 ``k_scale``/``v_scale`` plumbing) and the BlockSpec index map picks
``tab_ref[b, kb]`` as the pool row for logical block ``kb``.  Masking
stays logical: position ``kb * page + r`` is compared against the valid
prefix / ancestor mask exactly as in the dense kernels, so physical
block 0 (the null block every unallocated logical block aliases) is
read but always masked out.

Composes with the int8 path: per-row scales live in blocked pools
``[Nb, KV, page]`` and ride the same table-indexed maps.

``paged_flash_attention_lse`` reuses the dense ``_flash_kernel`` body
unchanged — grid axis 3 already iterates K/V tiles in logical order, so
``block_k = page`` makes its position arithmetic the logical positions;
only the index maps change.  The tree half needs a restructure: the
dense tree kernel is single-tile, but a paged tree is one tile *per
block*, so ``_paged_tree_kernel`` is the running-accumulation
(init / accumulate / finalize) form of the same masked softmax.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels.flash import NEG_INF, _flash_kernel


def _paged_flash_kernel(plen_ref, tab_ref, *args, **kw):
    # tab_ref is consumed by the BlockSpec index maps only
    del tab_ref
    _flash_kernel(plen_ref, *args, **kw)


@functools.partial(jax.jit, static_argnames=("window", "interpret", "scale",
                                             "causal"))
def paged_flash_attention_lse(q, k_pool, v_pool, table, kv_len, qpos=None, *,
                              k_scale=None, v_scale=None, scale=None,
                              window: int = 0, causal: bool = False,
                              interpret: Optional[bool] = None):
    """q: [B,H,n,hd]; k_pool/v_pool: [Nb,KV,page,hd]; table: [B,mb] int32;
    kv_len: () or per-row [B] int32 valid prefix.  k_scale/v_scale
    [Nb,KV,page] mark the pools as per-row symmetric int8.  Returns
    (o [B,H,n,hd], m [B,H,n,128], l [B,H,n,128]) like the dense kernel.
    """
    quant = k_scale is not None
    b, h, n, hd = q.shape
    kvh, page = k_pool.shape[1], k_pool.shape[2]
    mb = table.shape[1]
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    if qpos is None:
        qpos = jnp.zeros((n,), jnp.int32)
    qpos = jnp.asarray(qpos, jnp.int32)
    if qpos.ndim == 1:
        qpos = jnp.broadcast_to(qpos[None], (b, n))
    qpos2 = jnp.broadcast_to(qpos[:, None, :, None],
                             (b, 1, n, 128)).astype(jnp.int32)
    plen = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    table = jnp.asarray(table, jnp.int32)

    grid = (b, h, 1, mb)
    kernel = functools.partial(_paged_flash_kernel, scale=scale,
                               block_k=page, window=window, causal=causal,
                               quant=quant)
    out_shape = [
        jax.ShapeDtypeStruct((b, h, n, hd), q.dtype),
        jax.ShapeDtypeStruct((b, h, n, 128), jnp.float32),
        jax.ShapeDtypeStruct((b, h, n, 128), jnp.float32),
    ]
    # the paged gather: pool row = table[batch, logical block]
    kv_spec = pl.BlockSpec(
        (1, 1, page, hd),
        lambda i, j, qi, kb, plen_ref, tab_ref: (tab_ref[i, kb], j // rep,
                                                 0, 0))
    scale_specs, scale_args = [], []
    if quant:
        scale_specs = [pl.BlockSpec(
            (1, 1, page),
            lambda i, j, qi, kb, plen_ref, tab_ref: (tab_ref[i, kb],
                                                     j // rep, 0))] * 2
        scale_args = [k_scale.astype(jnp.float32),
                      v_scale.astype(jnp.float32)]
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, n, hd),
                             lambda i, j, qi, kb, *_: (i, j, 0, 0)),
                kv_spec,
                kv_spec,
                *scale_specs,
                pl.BlockSpec((1, 1, n, 128),
                             lambda i, j, qi, kb, *_: (i, 0, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, n, hd),
                             lambda i, j, qi, kb, *_: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, n, 128),
                             lambda i, j, qi, kb, *_: (i, j, 0, 0)),
                pl.BlockSpec((1, 1, n, 128),
                             lambda i, j, qi, kb, *_: (i, j, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((n, hd), jnp.float32),
                pltpu.VMEM((n, 128), jnp.float32),
                pltpu.VMEM((n, 128), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret_mode(interpret),
    )(plen, table, q, k_pool, v_pool, *scale_args, qpos2)
    return o, m, l


def _paged_tree_kernel(tab_ref, q_ref, k_ref, v_ref, mask_ref, *rest,
                       scale, quant):
    # running-accumulation form of the dense tree kernel: one grid step
    # per logical tree block, (acc, m, l) carried in VMEM scratch
    del tab_ref
    if quant:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref, ms_ref, ls_ref = rest
    kb = pl.program_id(2)
    nb = pl.num_programs(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        ms_ref[...] = jnp.full_like(ms_ref, NEG_INF)
        ls_ref[...] = jnp.zeros_like(ls_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # [n, hd]
    k = k_ref[0, 0].astype(jnp.float32)                  # [page, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    if quant:
        k = k * ks_ref[0, 0][:, None]
        v = v * vs_ref[0, 0][:, None]
    mask = mask_ref[0, 0] != 0                           # [n, page]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = ms_ref[:, :1]
    l_prev = ls_ref[:, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
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


@functools.partial(jax.jit, static_argnames=("interpret", "scale"))
def paged_tree_block_attention(q, k_pool, v_pool, table, tree_mask, *,
                               k_scale=None, v_scale=None, scale=None,
                               interpret: Optional[bool] = None):
    """Paged tree-suffix attention: q [B,H,n,hd]; k/v pools
    [Nb,KV,page,hd] indexed by ``table`` [B,mb]; tree_mask [n,T] or
    per-row [B,n,T] bool over the *logical* tree positions
    (T <= mb * page; the tail of the last block is force-masked).
    Returns (o, m[.,128], l[.,128]) stats for LSE combination."""
    quant = k_scale is not None
    b, h, n, hd = q.shape
    kvh, page = k_pool.shape[1], k_pool.shape[2]
    mb = table.shape[1]
    rep = h // kvh
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    if tree_mask.ndim == 2:
        tree_mask = tree_mask[None]
    t = tree_mask.shape[-1]
    mask_i8 = jnp.broadcast_to(tree_mask, (b, n, t)).astype(jnp.int8)
    pad = mb * page - t
    if pad:
        mask_i8 = jnp.pad(mask_i8, ((0, 0), (0, 0), (0, pad)))
    # one [n, page] mask tile per logical block, block-major, so each
    # tile's last two dims are whole array dims (the TPU tiling rule)
    mask_i8 = mask_i8.reshape(b, n, mb, page).transpose(0, 2, 1, 3)
    table = jnp.asarray(table, jnp.int32)

    kv_spec = pl.BlockSpec(
        (1, 1, page, hd),
        lambda i, j, kb, tab_ref: (tab_ref[i, kb], j // rep, 0, 0))
    scale_specs, scale_args = [], []
    if quant:
        scale_specs = [pl.BlockSpec(
            (1, 1, page),
            lambda i, j, kb, tab_ref: (tab_ref[i, kb], j // rep, 0))] * 2
        scale_args = [k_scale.astype(jnp.float32),
                      v_scale.astype(jnp.float32)]
    out_shape = [
        jax.ShapeDtypeStruct((b, h, n, hd), q.dtype),
        jax.ShapeDtypeStruct((b, h, n, 128), jnp.float32),
        jax.ShapeDtypeStruct((b, h, n, 128), jnp.float32),
    ]
    o, m, l = pl.pallas_call(
        functools.partial(_paged_tree_kernel, scale=scale, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, mb),
            in_specs=[
                pl.BlockSpec((1, 1, n, hd), lambda i, j, kb, *_: (i, j, 0,
                                                                  0)),
                kv_spec,
                kv_spec,
                # the mask indexes LOGICAL blocks (not through the table)
                pl.BlockSpec((1, 1, n, page),
                             lambda i, j, kb, *_: (i, kb, 0, 0)),
                *scale_specs,
            ],
            out_specs=[
                pl.BlockSpec((1, 1, n, hd), lambda i, j, kb, *_: (i, j, 0,
                                                                  0)),
                pl.BlockSpec((1, 1, n, 128), lambda i, j, kb, *_: (i, j, 0,
                                                                   0)),
                pl.BlockSpec((1, 1, n, 128), lambda i, j, kb, *_: (i, j, 0,
                                                                   0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((n, hd), jnp.float32),
                pltpu.VMEM((n, 128), jnp.float32),
                pltpu.VMEM((n, 128), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(table, q, k_pool, v_pool, mask_i8, *scale_args)
    return o, m, l
