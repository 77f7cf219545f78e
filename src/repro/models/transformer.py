"""Decoder model covering all assigned architecture families.

Layer organisation
------------------
Every architecture is a repetition of a *unit* (a short tuple of sub-layer
kinds), e.g. dense = ``("attn",)``, Mamba-2 = ``("ssm",)``, RecurrentGemma =
``("rglru","rglru","local")``.  The repeated region is executed with
``lax.scan`` over stacked unit params (MaxText-style) so that 64-layer
configs lower to compact HLO; non-uniform prefix layers (MoE ``first_dense``)
and the pattern remainder are unrolled.

Execution modes
---------------
  * full   — training / prefill over a whole sequence (optionally filling the
             model KV cache / recurrent states).
  * decode — one token per step against the model cache (``serve_step``).
  * tree   — PipeDec: verify one prediction-tree layer against the two-level
             cache (model cache + tree cache) with the ancestor mask.

All functions are pure; caches/states are explicit pytrees.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import config as config_mod
from repro.models import paging
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig
from repro.models.layers import (embed, init_embedding, init_mlp,
                                 init_rmsnorm, mlp, rmsnorm, unembed)


# --------------------------------------------------------------------------
# unit layout
# --------------------------------------------------------------------------
def unit_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    if cfg.family == "ssm":
        return ("ssm",)
    if cfg.rglru is not None:
        return tuple(config_mod.PATTERN_KINDS.get(c, "local")
                     for c in cfg.rglru.pattern)
    return ("attn",)


def layout(cfg: ModelConfig) -> Tuple[int, int, Tuple[str, ...]]:
    """(n_prefix_dense, n_repeats, tail_kinds)."""
    kinds = unit_kinds(cfg)
    n_prefix = cfg.moe.first_dense if cfg.moe is not None else 0
    body = cfg.num_layers - n_prefix
    reps = body // len(kinds)
    tail = kinds[: body % len(kinds)]
    return n_prefix, reps, tail


def _sub_has_ffn(cfg: ModelConfig, kind: str) -> bool:
    if kind == "ssm":
        return False
    return cfg.d_ff > 0 or cfg.moe is not None


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def _init_sublayer(key, cfg: ModelConfig, kind: str, *, use_moe: bool, dtype):
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": init_rmsnorm(cfg.d_model, dtype)}
    if kind in ("attn", "local"):
        p["mixer"] = attn.init_attention(ks[0], cfg, dtype)
    elif kind == "ssm":
        p["mixer"] = ssm_mod.init_ssm(ks[0], cfg, dtype)
    elif kind == "rglru":
        p["mixer"] = rglru_mod.init_rglru(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if cfg.is_encdec and kind in ("attn", "local"):
        p["cross_norm"] = init_rmsnorm(cfg.d_model, dtype)
        p["cross"] = attn.init_attention(ks[1], cfg, dtype, cross=True)
    if _sub_has_ffn(cfg, kind):
        p["norm2"] = init_rmsnorm(cfg.d_model, dtype)
        if use_moe:
            p["ffn"] = moe_mod.init_moe(ks[2], cfg, dtype)
        else:
            p["ffn"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.mlp_variant,
                                dtype)
    return p


def _init_unit(key, cfg: ModelConfig, *, use_moe: bool, dtype,
               kinds: Optional[Tuple[str, ...]] = None):
    kinds = kinds or unit_kinds(cfg)
    ks = jax.random.split(key, len(kinds))
    return [
        _init_sublayer(ks[i], cfg, kind, use_moe=use_moe and kind != "ssm",
                       dtype=dtype)
        for i, kind in enumerate(kinds)
    ]


def init_stack(key, cfg: ModelConfig, lo: int, hi: int, dtype=jnp.float32):
    """Repeated units ``lo..hi-1`` of the layer stack, stacked on a leading
    dim.  ``key`` is the model's key, so these are the values
    ``init_model`` gives them and a deployment can create each pipeline
    stage's share on its own device."""
    rk = jax.random.split(jax.random.split(key, 8)[3], layout(cfg)[1])
    units = [_init_unit(rk[i], cfg, use_moe=cfg.moe is not None,
                        dtype=dtype) for i in range(lo, hi)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *units)


def init_model(key, cfg: ModelConfig, dtype=jnp.float32):
    n_prefix, reps, tail = layout(cfg)
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": init_embedding(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": init_rmsnorm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = init_embedding(ks[1], cfg.vocab_size, cfg.d_model,
                                           dtype)
    if n_prefix:
        pk = jax.random.split(ks[2], n_prefix)
        params["prefix"] = [
            _init_unit(pk[i], cfg, use_moe=False, dtype=dtype, kinds=("attn",))
            for i in range(n_prefix)
        ]
    if reps:
        params["stack"] = init_stack(key, cfg, 0, reps, dtype)
    if tail:
        params["tail"] = _init_unit(ks[4], cfg, use_moe=cfg.moe is not None,
                                    dtype=dtype, kinds=tail)
    if cfg.is_encdec:
        from repro.models.encdec import init_encoder
        params["encoder"] = init_encoder(ks[5], cfg, dtype)
    return params


# --------------------------------------------------------------------------
# caches
# --------------------------------------------------------------------------
def _init_sub_cache(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                    dtype):
    if kind in ("attn", "local"):
        return attn.init_kv_cache(cfg, batch, max_len, dtype)
    if kind == "ssm":
        return ssm_mod.init_ssm_state(cfg, batch, dtype)
    if kind == "rglru":
        return rglru_mod.init_rglru_state(cfg, batch, dtype)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.float32,
               *, stacked: bool = True):
    """Model KV/state cache.

    ``stacked=True`` stacks the repeated-unit caches with a leading reps dim
    (scan-over-layers; prefill/training).  ``stacked=False`` keeps one
    buffer per layer ("units" list) — the serving layout, which lets XLA
    alias each donated buffer through the decode step's in-place update
    instead of double-buffering the whole cache through a scan.
    """
    n_prefix, reps, tail = layout(cfg)
    kinds = unit_kinds(cfg)
    cache: Dict[str, Any] = {}
    if n_prefix:
        cache["prefix"] = [
            [_init_sub_cache(cfg, "attn", batch, max_len, dtype)]
            for _ in range(n_prefix)
        ]
    if reps:
        unit = [_init_sub_cache(cfg, k, batch, max_len, dtype) for k in kinds]
        if stacked:
            cache["stack"] = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (reps, *x.shape)).copy(),
                unit)
        else:
            cache["units"] = [
                [_init_sub_cache(cfg, k, batch, max_len, dtype)
                 for k in kinds]
                for _ in range(reps)
            ]
    if tail:
        cache["tail"] = [_init_sub_cache(cfg, k, batch, max_len, dtype)
                         for k in tail]
    return cache


def restack_cache(cfg: ModelConfig, cache):
    """Convert an unstacked ("units") cache to the stacked layout."""
    if "units" not in cache:
        return cache
    out = {k: v for k, v in cache.items() if k != "units"}
    out["stack"] = jax.tree.map(lambda *xs: jnp.stack(xs), *cache["units"])
    return out


def unstack_cache(cfg: ModelConfig, cache):
    """Convert a stacked cache to the serving ("units") layout."""
    if "stack" not in cache:
        return cache
    reps = layout(cfg)[1]
    out = {k: v for k, v in cache.items() if k != "stack"}
    out["units"] = [jax.tree.map(lambda t: t[i], cache["stack"])
                    for i in range(reps)]
    return out


def unstack_params(cfg: ModelConfig, params):
    """Serving layout for params: per-layer weight trees instead of one
    stacked tensor per weight.  Keeps each layer's weights a separate
    buffer so per-step streaming reads exactly one layer (XLA cannot hoist
    a whole-stack convert/copy in front of the layer loop)."""
    if "stack" not in params:
        return params
    reps = layout(cfg)[1]
    out = {k: v for k, v in params.items() if k != "stack"}
    out["units"] = [jax.tree.map(lambda t: t[i], params["stack"])
                    for i in range(reps)]
    return out


def init_tree_caches(cfg: ModelConfig, batch: int, capacity: int,
                     dtype=jnp.float32):
    """Tree (level-2) KV caches; attention sub-layers only."""
    assert cfg.family not in ("ssm",), "tree cache is attention-only"
    n_prefix, reps, tail = layout(cfg)
    kinds = unit_kinds(cfg)
    tc: Dict[str, Any] = {}

    def sub(kind):
        if kind in ("attn", "local"):
            return attn.init_tree_cache(cfg, batch, capacity, dtype)
        return None

    if n_prefix:
        tc["prefix"] = [[sub("attn")] for _ in range(n_prefix)]
    if reps:
        unit = [sub(k) for k in kinds]
        tc["stack"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (reps, *x.shape)).copy(), unit)
    if tail:
        tc["tail"] = [sub(k) for k in tail]
    return tc


# --------------------------------------------------------------------------
# activation sharding (Megatron-style sequence parallelism)
# --------------------------------------------------------------------------
# When set (by the launcher) to a NamedSharding over [B, S, d], the residual
# stream is constrained to it between layers — sharding the *sequence* dim
# over the "model" axis so per-device activation carries shrink by the model
# axis size.  XLA converts the surrounding all-reduces into
# reduce-scatter + all-gather pairs (same volume, less live memory).
_ACTIVATION_SHARDING = None
_SCAN_UNROLL = 1  # >1 unrolls the layer scan (exact cost_analysis accounting)


def set_activation_sharding(sharding) -> None:
    global _ACTIVATION_SHARDING
    _ACTIVATION_SHARDING = sharding


def set_scan_unroll(n: int) -> None:
    global _SCAN_UNROLL
    _SCAN_UNROLL = max(1, int(n))


def _constrain(x):
    if _ACTIVATION_SHARDING is not None and x.ndim == 3:
        return jax.lax.with_sharding_constraint(x, _ACTIVATION_SHARDING)
    return x


# --------------------------------------------------------------------------
# sub-layer application
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Ctx:
    """Static + traced context threaded through the layers."""
    mode: str                       # full | decode | tree | chunk
    positions: Any                  # [B,S] absolute positions
    cache_len: Any = None           # committed tokens: scalar (decode) or
                                    # per-row [B] (tree mode); in chunk
                                    # mode the per-row chunk start offsets
    tree_write_index: Any = None    # [B] per-row tree buffer write offsets
    tree_mask: Any = None           # [B, n, Tcap] per-row ancestor masks
    enc_kv: Any = None              # per-layer (k, v) list for cross-attn
    enc_kv_idx: int = 0
    window_override: int = -1       # -1: use config default per kind
    causal: bool = True
    remat: bool = False             # checkpoint the scan body (training)


def _window(cfg: ModelConfig, kind: str, ctx: Ctx) -> int:
    if ctx.window_override >= 0:
        return ctx.window_override
    if kind == "local":
        return cfg.rglru.window
    return cfg.sliding_window


def _apply_sublayer(p, cfg: ModelConfig, kind: str, x, cache, tree_cache,
                    ctx: Ctx, enc_kv=None):
    """Returns (x, new_cache, new_tree_cache, aux)."""
    aux = jnp.zeros((), jnp.float32)
    h = rmsnorm(p["norm1"], x, cfg.norm_eps)
    win = _window(cfg, kind, ctx)
    if kind in ("attn", "local"):
        if ctx.mode == "full":
            y, cache = attn.attn_forward(
                p["mixer"], cfg, h, ctx.positions, window=win, cache=cache,
                cache_index=0, causal=ctx.causal)
        elif ctx.mode == "decode":
            y, cache = attn.attn_decode(
                p["mixer"], cfg, h, ctx.positions[:, 0], cache, ctx.cache_len,
                window=win)
        elif ctx.mode == "chunk":
            y, cache = attn.attn_prefill_chunk(
                p["mixer"], cfg, h, ctx.positions, cache, ctx.cache_len,
                window=win)
        else:  # tree
            y, tree_cache = attn.attn_tree_verify(
                p["mixer"], cfg, h, ctx.positions, model_cache=cache,
                model_len=ctx.cache_len, tree_cache=tree_cache,
                tree_write_index=ctx.tree_write_index,
                tree_mask=ctx.tree_mask, window=win)
            cache = None  # model cache is read-only here; don't re-emit it
    elif kind == "ssm":
        if ctx.mode == "chunk":
            raise NotImplementedError(
                "chunked prefill through an ssm sub-layer is undefined "
                "(no mid-sequence recurrent re-entry); recurrent "
                "architectures keep the whole-prompt prefill path")
        if ctx.mode == "tree":
            # a width-w tree layer has no single recurrent successor state;
            # recurrent architectures speculate in chain-mode instead
            # (core/chain.py) — fail loudly rather than decode garbage
            raise NotImplementedError(
                "tree-verify through an ssm sub-layer is undefined; use "
                "chain-mode speculation (repro.core.chain) for recurrent "
                "architectures")
        if ctx.mode == "full":
            # full mode is always a from-scratch prefill (positions start at
            # 0), so the SSD scan must seed from the zero state — a recycled
            # KV-arena slot's ``cache["ssd"]`` holds the PREVIOUS occupant's
            # final recurrent state and must never leak into the new
            # request (tests/test_serving_db.py pins fresh == recycled).
            y, state = ssm_mod.ssm_forward(p["mixer"], cfg, h)
            cache = state if cache is not None else None
        else:  # decode
            y, cache = ssm_mod.ssm_decode(p["mixer"], cfg, h, cache)
    elif kind == "rglru":
        if ctx.mode == "chunk":
            raise NotImplementedError(
                "chunked prefill through an rglru sub-layer is undefined "
                "(no mid-sequence recurrent re-entry); recurrent "
                "architectures keep the whole-prompt prefill path")
        if ctx.mode == "tree":
            raise NotImplementedError(
                "tree-verify through an rglru sub-layer is undefined; use "
                "chain-mode speculation (repro.core.chain) for recurrent "
                "architectures")
        if ctx.mode == "full":
            # like the ssm branch: prefill starts the recurrence from the
            # zero state (no ``state=`` seed), so recycled slots are clean
            y, state = rglru_mod.rglru_forward(p["mixer"], cfg, h)
            cache = state if cache is not None else None
        else:
            y, cache = rglru_mod.rglru_decode(p["mixer"], cfg, h, cache)
    else:
        raise ValueError(kind)
    x = x + y

    if "cross" in p and enc_kv is not None:
        hc = rmsnorm(p["cross_norm"], x, cfg.norm_eps)
        x = x + attn.cross_attn_forward(p["cross"], cfg, hc, enc_kv)

    if "ffn" in p:
        h2 = rmsnorm(p["norm2"], x, cfg.norm_eps)
        if cfg.moe is not None and "router" in p["ffn"]:
            y2, aux = moe_mod.moe_forward(p["ffn"], cfg, h2)
        else:
            y2 = mlp(p["ffn"], h2, cfg.mlp_variant)
        x = x + y2
    return x, cache, tree_cache, aux


def _apply_unit(unit_p, cfg: ModelConfig, kinds, x, unit_cache, unit_tcache,
                ctx: Ctx, enc_kv_list=None):
    new_cache, new_tcache = [], []
    aux_total = jnp.zeros((), jnp.float32)
    for i, kind in enumerate(kinds):
        c = unit_cache[i] if unit_cache is not None else None
        tc = unit_tcache[i] if unit_tcache is not None else None
        ekv = None
        if enc_kv_list is not None and kind in ("attn", "local"):
            ekv = enc_kv_list[i]
        x, c, tc, aux = _apply_sublayer(unit_p[i], cfg, kind, x, c, tc, ctx,
                                        enc_kv=ekv)
        new_cache.append(c)
        new_tcache.append(tc)
        aux_total = aux_total + aux
    return x, new_cache, new_tcache, aux_total


# --------------------------------------------------------------------------
# whole-model application
# --------------------------------------------------------------------------
def _run_layers(params, cfg: ModelConfig, x, cache, tcache, ctx: Ctx,
                enc_out=None):
    n_prefix, reps, tail = layout(cfg)
    kinds = unit_kinds(cfg)
    aux_total = jnp.zeros((), jnp.float32)
    new_cache: Dict[str, Any] = {}
    new_tcache: Dict[str, Any] = {}

    enc_kv = None
    if enc_out is not None:
        # precompute per-unit cross KV lazily inside the scan is not possible
        # with stacked params; compute per sub-layer outside for prefix/tail
        # and inside the scan body for the stack (cheap einsums).
        enc_kv = enc_out

    def get(c, key):
        return None if c is None else c.get(key)

    if n_prefix:
        pc, ptc = [], []
        for i in range(n_prefix):
            x, c, tc, aux = _apply_unit(
                params["prefix"][i], cfg, ("attn",), x,
                get(cache, "prefix")[i] if cache else None,
                get(tcache, "prefix")[i] if tcache else None, ctx,
                enc_kv_list=None)
            pc.append(c)
            ptc.append(tc)
            aux_total = aux_total + aux
        new_cache["prefix"], new_tcache["prefix"] = pc, ptc

    if reps:
        stack_p = params.get("stack")
        units_p = params.get("units")
        stack_c = get(cache, "stack")
        stack_tc = get(tcache, "stack")

        def _unit_ekv(unit_p):
            if enc_kv is None:
                return None
            return [
                attn.encode_cross_kv(unit_p[i]["cross"], cfg, enc_kv)
                if kinds[i] in ("attn", "local") and "cross" in unit_p[i]
                else None
                for i in range(len(kinds))
            ]

        units_c = get(cache, "units")
        if units_c is not None:
            # Serving layout: one buffer per layer, unrolled loop — each
            # donated buffer is updated in place (no scan double-buffer).
            new_units = []
            for i in range(reps):
                unit_p = (units_p[i] if units_p is not None
                          else jax.tree.map(lambda t: t[i], stack_p))
                x, nc, _, aux = _apply_unit(unit_p, cfg, kinds, x,
                                            units_c[i], None, ctx,
                                            enc_kv_list=_unit_ekv(unit_p))
                aux_total = aux_total + aux
                new_units.append(nc)
            new_cache["units"], new_tcache["units"] = new_units, None
            cache_done = True
        else:
            cache_done = False
            assert stack_p is not None, \
                "unstacked params require the serving (units) cache layout"

        def body(carry, xs):
            xh, auxc = carry
            unit_p, unit_c, unit_tc = xs
            xh = _constrain(xh)
            xh, nc, ntc, aux = _apply_unit(unit_p, cfg, kinds, xh, unit_c,
                                           unit_tc, ctx,
                                           enc_kv_list=_unit_ekv(unit_p))
            xh = _constrain(xh)
            return (xh, auxc + aux), (nc, ntc)

        if not cache_done:
            scan_body = jax.checkpoint(body) if ctx.remat else body
            (x, aux_total), (sc, stc) = jax.lax.scan(
                scan_body, (x, aux_total),
                (stack_p, stack_c, stack_tc),
                unroll=min(_SCAN_UNROLL, reps))
            new_cache["stack"], new_tcache["stack"] = sc, stc

    if tail:
        x, tcch, ttc, aux = _apply_unit(
            params["tail"], cfg, tail, x,
            get(cache, "tail") if cache else None,
            get(tcache, "tail") if tcache else None, ctx, enc_kv_list=None)
        new_cache["tail"], new_tcache["tail"] = tcch, ttc
        aux_total = aux_total + aux

    return x, (new_cache if cache is not None else None), \
        (new_tcache if tcache is not None else None), aux_total


def _logits(params, cfg: ModelConfig, x):
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        return unembed(params["embed"], x)
    return unembed(params["lm_head"], x)


def _embed_inputs(params, cfg: ModelConfig, tokens, prefix_embeds=None):
    x = embed(params["embed"], tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    return x


# -- public API --------------------------------------------------------------
def forward(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
            enc_out=None, window_override: int = -1, remat: bool = False):
    """Training forward: logits [B, S(+P), V] and MoE aux loss."""
    x = _embed_inputs(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    ctx = Ctx(mode="full", positions=positions,
              window_override=window_override, remat=remat)
    x, _, _, aux = _run_layers(params, cfg, x, None, None, ctx,
                               enc_out=enc_out)
    return _logits(params, cfg, x), aux


def prefill(params, cfg: ModelConfig, tokens, cache, *, prefix_embeds=None,
            enc_out=None, window_override: int = -1):
    """Fill the model cache; returns (last-position logits [B,V], cache)."""
    x = _embed_inputs(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    ctx = Ctx(mode="full", positions=positions, cache_len=0,
              window_override=window_override)
    x, cache, _, _ = _run_layers(params, cfg, x, cache, None, ctx,
                                 enc_out=enc_out)
    return _logits(params, cfg, x[:, -1]), cache


def prefill_chunk(params, cfg: ModelConfig, tokens, cache, chunk_start, *,
                  window_override: int = -1):
    """Fill the model cache with ONE chunk of a longer prompt (chunked
    prefill-in-ring): row b's ``tokens[b]`` occupy absolute positions
    ``[chunk_start[b], chunk_start[b] + s)``.  Chunks must be fed in
    order; each chunk attends over the cache rows earlier chunks already
    wrote (bit-identical to a one-shot ``prefill`` — see
    ``attention.attn_prefill_chunk``).  Returns (logits [B, s, V], cache)
    — ALL chunk positions' logits, so the caller picks the last valid
    prompt position of the final chunk for the next-token prediction.
    """
    x = embed(params["embed"], tokens)
    b, s, _ = x.shape
    chunk_start = jnp.broadcast_to(
        jnp.asarray(chunk_start, jnp.int32).reshape(-1), (b,))
    positions = chunk_start[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    ctx = Ctx(mode="chunk", positions=positions, cache_len=chunk_start,
              window_override=window_override)
    x, cache, _, _ = _run_layers(params, cfg, x, cache, None, ctx)
    return _logits(params, cfg, x), cache


def decode_step(params, cfg: ModelConfig, token, cache, cache_len, *,
                enc_out=None, window_override: int = -1):
    """token [B] -> (logits [B,V], cache). Writes at position cache_len."""
    x = embed(params["embed"], token[:, None])
    b = x.shape[0]
    positions = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32)[None, None], (b, 1))
    ctx = Ctx(mode="decode", positions=positions, cache_len=cache_len,
              window_override=window_override)
    x, cache, _, _ = _run_layers(params, cfg, x, cache, None, ctx,
                                 enc_out=enc_out)
    return _logits(params, cfg, x[:, 0]), cache


def tree_verify_step(params, cfg: ModelConfig, node_tokens, node_positions,
                     tree_mask, cache, cache_len, tree_caches,
                     tree_write_index, *, enc_out=None,
                     window_override: int = -1):
    """Verify one tree layer (PipeDec §3.4.2).

    node_tokens: [B, n] token ids of the new layer (padded);
    node_positions: [B, n] absolute positions;
    tree_mask: [B, n, Tcap] per-row ancestor mask vs the whole tree buffer
               (a single [n, Tcap] mask broadcasts over the batch);
    cache_len: [B] per-row committed-prefix length (scalar broadcasts);
    tree_write_index: [B] per-row tree-buffer write offset (scalar
               broadcasts).
    Rows are fully independent — SpecPipe-DB stacks every in-flight
    request's deepest layer here for ONE fused dispatch per timestep; the
    single-request engine is the B=1 case of the same code.
    Returns (logits [B, n, V], tree_caches).
    """
    b = node_tokens.shape[0]
    cache_len = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    tree_write_index = jnp.broadcast_to(
        jnp.asarray(tree_write_index, jnp.int32).reshape(-1), (b,))
    if tree_mask.ndim == 2:
        tree_mask = tree_mask[None]
    tree_mask = jnp.broadcast_to(tree_mask, (b, *tree_mask.shape[1:]))
    x = embed(params["embed"], node_tokens)
    ctx = Ctx(mode="tree", positions=node_positions, cache_len=cache_len,
              tree_write_index=tree_write_index, tree_mask=tree_mask,
              window_override=window_override)
    x, _, tree_caches, _ = _run_layers(params, cfg, x, cache, tree_caches,
                                       ctx, enc_out=enc_out)
    return _logits(params, cfg, x), tree_caches


# distance of the cache "length" axis from the trailing axis, per buffer name
# (buffers may carry an extra leading `reps` dim when stacked for scan);
# k_scale/v_scale are the int8 layout's per-row scales [B, L, KV]
CACHE_LEN_AXIS_FROM_END = {"k": 3, "v": 3, "c_kv": 2, "k_rope": 2,
                           "k_scale": 2, "v_scale": 2}


def cache_len_axis(name: str, arr) -> int:
    return arr.ndim - CACHE_LEN_AXIS_FROM_END[name]


# --------------------------------------------------------------------------
# slot-stacked cache views (SpecPipe-DB KV arena)
# --------------------------------------------------------------------------
def _slot_axis(path) -> int:
    """Axis carrying the slot/batch dim of an arena buffer: stacked
    repeated-unit buffers ("stack") have a leading reps dim, so their slot
    axis is 1; prefix/tail/units buffers use axis 0.  Works for KV buffers
    and recurrent state dicts alike."""
    return 1 if path and getattr(path[0], "key", None) == "stack" else 0


def slice_cache_rows(cache, start: int, size: int):
    """Static slice of ``size`` slot rows starting at ``start`` from every
    buffer of a slot-stacked cache pytree (``None`` leaves pass through)."""

    def f(path, buf):
        if buf is None:
            return None
        if paging.is_paged(buf):
            # table slice, shared pool — O(1) view, no row gather
            return paging.slice_slots(buf, start, size)
        return jax.lax.slice_in_dim(buf, start, start + size,
                                    axis=_slot_axis(path))

    return jax.tree_util.tree_map_with_path(
        f, cache, is_leaf=lambda x: x is None or paging.is_paged(x))


def update_cache_rows(cache, rows, start: int = 0):
    """Write a row slice (as produced by ``slice_cache_rows``) back into the
    full slot-stacked cache pytree at slot ``start``."""

    def f(path, buf, upd):
        if buf is None:
            return None
        if paging.is_paged(buf):
            if paging.is_paged(upd):
                # a slice_slots view shares the full pool: its updated
                # pages ARE the updated arena — keep the full table
                return paging.adopt_pool(buf, upd)
            return paging.write_slot_rows(buf, upd, start)
        return jax.lax.dynamic_update_slice_in_dim(
            buf, upd.astype(buf.dtype), start, axis=_slot_axis(path))

    return jax.tree_util.tree_map_with_path(
        f, cache, rows, is_leaf=lambda x: x is None or paging.is_paged(x))


def where_cache_rows(on, new, old):
    """Per-slot select over slot-stacked cache pytrees: slot ``b`` of
    every buffer takes ``new`` where ``on[b]`` and keeps ``old``
    otherwise (``None`` leaves pass through).  Used by batched prefill
    paths that compute all slot rows but must only land the
    participating ones (e.g. the overlapped executor's in-tick draft
    prefill)."""
    on = jnp.asarray(on)

    def f(path, o, n):
        if o is None:
            return None
        if paging.is_paged(o):
            # block-granularity select through the shared table
            return paging.where_slots(on, n, o)
        shape = [1] * o.ndim
        shape[_slot_axis(path)] = on.shape[0]
        return jnp.where(on.reshape(shape), n.astype(o.dtype), o)

    return jax.tree_util.tree_map_with_path(
        f, old, new, is_leaf=lambda x: x is None or paging.is_paged(x))


def commit_tree_node(cfg: ModelConfig, cache, tree_caches, node_idx,
                     model_len):
    """Two-level cache sync (paper §3.4.3): move one verified tree node's KV
    from every tree cache into the model cache at position ``model_len``.

    Mapped over ``tree_caches`` first with its ``None`` entries (recurrent
    sub-layers have no tree cache) treated as leaves, so hybrid configs
    pass their state dicts through untouched.
    """

    def merge(path, tree_buf, model_buf):
        if tree_buf is None:
            return model_buf
        name = path[-1].key
        ax = cache_len_axis(name, model_buf)
        row = jax.lax.dynamic_slice_in_dim(tree_buf, node_idx, 1, axis=ax)
        return jax.lax.dynamic_update_slice_in_dim(
            model_buf, row.astype(model_buf.dtype), model_len, axis=ax)

    return jax.tree_util.tree_map_with_path(
        merge, tree_caches, cache, is_leaf=lambda x: x is None)


def _dense_node_rows(name, tree_buf, node_idx):
    """Per-row single-node gather from a dense tree buffer: row b takes its
    row ``node_idx[b]``, keeping the dense layout [*pre, B, 1, *post]."""
    ax = cache_len_axis(name, tree_buf)
    bx = ax - 1
    return jax.vmap(
        lambda tb, ni: jax.lax.dynamic_slice_in_dim(tb, ni, 1, axis=ax - 1),
        in_axes=(bx, 0), out_axes=bx)(tree_buf, node_idx)


def commit_tree_nodes(cfg: ModelConfig, cache, tree_caches, node_idx,
                      model_len, commit_mask=None):
    """Batched per-row two-level cache sync (SpecPipe-DB exit phase).

    Row b migrates its tree-cache row ``node_idx[b]`` into its model cache
    at position ``model_len[b]``.  Rows where ``commit_mask`` is False (no
    flight exiting this timestep) keep their caches bit-unchanged.  The
    batch axis of every buffer sits immediately before its length axis
    (``cache_len_axis``), which also holds for stacked (leading ``reps``
    dim) buffers.
    """
    node_idx = jnp.asarray(node_idx, jnp.int32).reshape(-1)
    model_len = jnp.asarray(model_len, jnp.int32).reshape(-1)

    def merge(path, tree_buf, model_buf):
        if tree_buf is None:
            return model_buf
        name = path[-1].key
        if paging.is_paged(model_buf):
            # paged commit: gather each row's verified node from the tree
            # pool, scatter it at ``model_len[b]`` through the model block
            # table — no dense materialisation of either buffer.
            row = (paging.take_len_rows(tree_buf, node_idx[:, None])
                   if paging.is_paged(tree_buf)
                   else _dense_node_rows(name, tree_buf, node_idx))
            return paging.write_len_rows(model_buf, row, model_len,
                                         on=commit_mask)
        if paging.is_paged(tree_buf):
            tree_buf = paging.to_dense(tree_buf)
        ax = cache_len_axis(name, model_buf)
        bx = ax - 1                    # batch axis precedes the length axis
        inner = ax - 1                 # length axis once batch is vmapped out

        def one(mb, tb, ni, ml):
            row = jax.lax.dynamic_slice_in_dim(tb, ni, 1, axis=inner)
            return jax.lax.dynamic_update_slice_in_dim(
                mb, row.astype(mb.dtype), ml, axis=inner)

        upd = jax.vmap(one, in_axes=(bx, bx, 0, 0), out_axes=bx)(
            model_buf, tree_buf, node_idx, model_len)
        if commit_mask is not None:
            sel_shape = [1] * model_buf.ndim
            sel_shape[bx] = commit_mask.shape[0]
            upd = jnp.where(jnp.asarray(commit_mask).reshape(sel_shape),
                            upd, model_buf)
        return upd

    return jax.tree_util.tree_map_with_path(
        merge, tree_caches, cache,
        is_leaf=lambda x: x is None or paging.is_paged(x))


def remap_tree_cache_rows(tree_caches, index_maps):
    """Batched post-prune tree-cache compaction (SpecPipe-DB exit phase).

    ``index_maps [B, cap]`` carries one old→new prune map per slot row
    (identity rows leave that slot's buffers bit-unchanged, so callers mix
    pruned and untouched slots in ONE gather).  Per slot the permutation
    is exactly ``core.speculative.remap_tree_caches``'s: dropped rows
    (``-1``) are pushed past the buffer end, then the inverse permutation
    gathers each surviving row to its compacted position.  Buffers may
    carry ``capacity + w`` rows (fixed-width layer-write slack) and a
    leading reps/stage dim — the length axis is resolved per buffer name,
    with the slot axis immediately before it (as in ``commit_tree_nodes``).
    """
    index_maps = jnp.asarray(index_maps, jnp.int32)

    def gather(path, buf):
        if buf is None:
            return None
        name = path[-1].key
        cap = buf.length if paging.is_paged(buf) else \
            buf.shape[cache_len_axis(name, buf)]
        im = jnp.concatenate([
            index_maps,
            jnp.full((index_maps.shape[0], cap - index_maps.shape[1]), -1,
                     jnp.int32)], axis=1)
        # inverse permutation per row: g[b, new] = old (dropped → the end)
        g = jnp.argsort(jnp.where(im >= 0, im, cap + jnp.arange(cap)[None]),
                        axis=1)
        if paging.is_paged(buf):
            # gather the permuted rows through the table, scatter them back
            # through the same table (the logical buffer is small — cap+w
            # rows — so the round-trip is the whole compaction)
            return paging.from_dense(buf, paging.take_len_rows(buf, g))
        ax = cache_len_axis(name, buf)
        bx = ax - 1                    # slot axis precedes the length axis
        return jax.vmap(lambda b, gi: jnp.take(b, gi, axis=ax - 1),
                        in_axes=(bx, 0), out_axes=bx)(buf, g)

    return jax.tree_util.tree_map_with_path(
        gather, tree_caches, is_leaf=lambda x: x is None or paging.is_paged(x))


def _hidden(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
            enc_out=None, window_override: int = -1, remat: bool = False):
    """Final-norm hidden states (pre-unembed) + MoE aux loss."""
    x = _embed_inputs(params, cfg, tokens, prefix_embeds)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    ctx = Ctx(mode="full", positions=positions,
              window_override=window_override, remat=remat)
    x, _, _, aux = _run_layers(params, cfg, x, None, None, ctx,
                               enc_out=enc_out)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def chunked_ce(table, hidden, labels, *, chunk: int = 256) -> jnp.ndarray:
    """Streaming cross-entropy: never materialises [B, S, V] logits.

    The per-chunk body is rematerialised in backward, so peak memory is one
    [B, chunk, V] logits block instead of the whole sequence (the dominant
    temp for 150k-250k vocabularies).
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    pad = (-s) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    nc = (s + pad) // chunk
    hs = hidden.reshape(b, nc, chunk, d).swapaxes(0, 1)   # [nc,B,chunk,d]
    ys = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, xs):
        hc, yc = xs
        logits = (hc @ table.T).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        safe = jnp.maximum(yc, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(yc >= 0, nll, 0.0)
        return carry + nll.sum(), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ys))
    return total / (b * s)


def loss_fn(params, cfg: ModelConfig, tokens, labels, *, prefix_embeds=None,
            enc_out=None, remat: bool = False, window_override: int = -1,
            ce_chunk: int = 256):
    hidden, aux = _hidden(params, cfg, tokens, prefix_embeds=prefix_embeds,
                          enc_out=enc_out, remat=remat,
                          window_override=window_override)
    if prefix_embeds is not None:
        hidden = hidden[:, prefix_embeds.shape[1]:]
    table = params["embed"]["table"] if cfg.tie_embeddings \
        else params["lm_head"]["table"]
    ce = chunked_ce(table, hidden, labels, chunk=ce_chunk)
    if cfg.moe is not None:
        ce = ce + cfg.moe.router_aux_weight * aux
    return ce
