"""Draft-propose / target-verify machinery shared by PipeDec and STPP.

``ModelBundle`` wraps (params, cfg) with jitted step closures keyed on the
static shapes (tree width w, buffer capacity N), so the Python-level decode
loops stay recompile-free.

Token selection at commit time follows the paper: greedy => argmax of the
target logits at the accepted node; stochastic => sample from the target's
(temperature / top-k / top-p filtered) distribution.  Either way the emitted
token is drawn from the *target* model only — the tree merely decides how
much latency the commit costs — so the output distribution is lossless.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import re
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core import tree as tree_lib
from repro.models import paging
from repro.models import transformer as tf
from repro.models.config import ModelConfig


@dataclasses.dataclass
class SamplingParams:
    """Sampling controls: temperature (0 => greedy), top-k, top-p."""
    temperature: float = 0.0  # 0 => greedy
    top_k: int = 0
    top_p: float = 1.0


def select_token(logits: jnp.ndarray, sp: SamplingParams, key) -> jnp.ndarray:
    """logits [V] -> token id ()."""
    if sp.temperature <= 0.0:
        return jnp.argmax(logits).astype(jnp.int32)
    logits = logits.astype(jnp.float32) / sp.temperature
    if sp.top_k:
        kth = jax.lax.top_k(logits, sp.top_k)[0][-1]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if sp.top_p < 1.0:
        sorted_logits = jnp.sort(logits)[::-1]
        probs = jax.nn.softmax(sorted_logits)
        cum = jnp.cumsum(probs)
        cutoff_ix = jnp.sum(cum < sp.top_p)
        cutoff = sorted_logits[jnp.minimum(cutoff_ix, logits.shape[0] - 1)]
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


# projection-weight name -> number of leading contraction axes, for the
# int8 serving path (per-out-channel symmetric quantization).  Everything
# else (embeddings, norms, biases, lm_head) stays fp32.
QUANT_WEIGHTS = {"w_q": 1, "w_k": 1, "w_v": 1, "w_o": 2,
                 "w_gate": 1, "w_up": 1, "w_down": 1}


def _tree_verify_rows_impl(params, node_tokens, node_positions, tree_mask,
                           cache, cache_len, tree_caches, tree_write_index,
                           *, bucket: int, cfg, enc_out, window_override):
    """ONE fused tree-verify dispatch over the first ``bucket`` slot rows
    of slot-stacked caches (SpecPipe-DB).

    The full arena rides through unsliced; the static ``bucket`` bounds
    the rows actually read/computed, and the updated tree-cache rows are
    scattered back — so growing/shrinking occupancy only recompiles per
    bucket size (power-of-two slot-count bucketing), never per step.
    """
    cache_b = tf.slice_cache_rows(cache, 0, bucket)
    tc_view = tf.slice_cache_rows(tree_caches, 0, bucket)
    # paged arenas: gather dense views at dispatch entry (paged leaves
    # cannot ride the layer scan) and scatter the updated tree rows back
    # through the block tables at exit — still ONE dispatch per timestep
    logits, tc_b = tf.tree_verify_step(
        params, cfg=cfg, node_tokens=node_tokens,
        node_positions=node_positions, tree_mask=tree_mask,
        cache=paging.densify(cache_b), cache_len=cache_len,
        tree_caches=paging.densify(tc_view),
        tree_write_index=tree_write_index, enc_out=enc_out,
        window_override=window_override)
    if paging.any_paged(tc_view):
        tc_b = paging.repaginate(tc_view, tc_b)
    return logits, tf.update_cache_rows(tree_caches, tc_b, 0)


def named_jit(name: str, fn, **jit_kwargs):
    """``jax.jit(fn)`` compiled as the program ``jit_<name>`` (every
    character that cannot be in an identifier becomes ``_``), so a device
    trace names what ran; ``fn`` itself is left untouched."""
    fn = functools.partial(fn)
    fn.__name__ = re.sub(r"\W", "_", name)
    return jax.jit(fn, **jit_kwargs)


class ModelBundle:
    """params+cfg with jitted prefill / decode / tree-verify / commit.

    ``calls`` counts dispatches by closure name — the call-count hook the
    SpecPipe-DB equivalence tests use to assert the fused path issues
    exactly ONE tree-verify per model per global timestep.

    Each program compiles as ``jit_<cfg.name>_<method>`` (e.g.
    ``jit_target_prefill``), except the fused ``tree_verify_rows``, which
    both models compile as ``jit_tree_verify_rows``: a profile tells the
    two apart by call order (the executor launches the target's first),
    and the four per-timestep programs then show under three names (see
    PERF.md, §3).
    """

    def __init__(self, params, cfg: ModelConfig, *, enc_out=None,
                 prefix_embeds=None, window_override: int = -1):
        self.params = params
        self.cfg = cfg
        self.enc_out = enc_out
        self.prefix_embeds = prefix_embeds
        self.window_override = window_override
        self.calls = collections.Counter()

        name = lambda method: f"{cfg.name}_{method}"  # noqa: E731
        self._prefill = named_jit(name("prefill"), functools.partial(
            tf.prefill, cfg=cfg, prefix_embeds=prefix_embeds,
            enc_out=enc_out, window_override=window_override))
        self._decode = named_jit(name("decode"), functools.partial(
            tf.decode_step, cfg=cfg, enc_out=enc_out,
            window_override=window_override))
        self._tree_verify = named_jit(name("tree_verify"), functools.partial(
            tf.tree_verify_step, cfg=cfg, enc_out=enc_out,
            window_override=window_override))
        self._tree_verify_rows = named_jit(
            "tree_verify_rows", functools.partial(
                _tree_verify_rows_impl, cfg=cfg, enc_out=enc_out,
                window_override=window_override),
            static_argnames=("bucket",))
        self._commit = named_jit(name("commit"), functools.partial(
            tf.commit_tree_node, cfg=cfg))
        self._commit_rows = named_jit(name("commit_rows"), functools.partial(
            tf.commit_tree_nodes, cfg))
        self._forward = named_jit(name("forward"), functools.partial(
            tf.forward, cfg=cfg, prefix_embeds=prefix_embeds,
            enc_out=enc_out, window_override=window_override))

    # thin wrappers (keyword plumbing) -------------------------------------
    def prefill(self, tokens, cache):
        self.calls["prefill"] += 1
        return self._prefill(self.params, tokens=tokens, cache=cache)

    def decode(self, token, cache, cache_len):
        return self._decode(self.params, token=token, cache=cache,
                            cache_len=cache_len)

    def tree_verify(self, node_tokens, node_positions, tree_mask, cache,
                    cache_len, tree_caches, tree_write_index):
        self.calls["tree_verify"] += 1
        return self._tree_verify(
            self.params, node_tokens=node_tokens,
            node_positions=node_positions, tree_mask=tree_mask, cache=cache,
            cache_len=cache_len, tree_caches=tree_caches,
            tree_write_index=tree_write_index)

    def tree_verify_rows(self, node_tokens, node_positions, tree_mask,
                         cache, cache_len, tree_caches, tree_write_index,
                         *, bucket: int):
        """Fused per-timestep dispatch over slot-stacked caches: row b is
        request b's deepest tree layer, bounded by its own ``cache_len[b]``
        / ancestor mask, written at its own ``tree_write_index[b]``."""
        self.calls["tree_verify_rows"] += 1
        return self._tree_verify_rows(
            self.params, node_tokens=node_tokens,
            node_positions=node_positions, tree_mask=tree_mask, cache=cache,
            cache_len=cache_len, tree_caches=tree_caches,
            tree_write_index=tree_write_index, bucket=bucket)

    def commit(self, cache, tree_caches, node_idx, model_len):
        self.calls["commit"] += 1
        return self._commit(cache=cache, tree_caches=tree_caches,
                            node_idx=node_idx, model_len=model_len)

    def commit_rows(self, cache, tree_caches, node_idx, model_len,
                    commit_mask):
        """Batched per-row two-level cache sync (masked rows untouched)."""
        self.calls["commit_rows"] += 1
        return self._commit_rows(cache, tree_caches, node_idx, model_len,
                                 commit_mask)

    def init_cache(self, batch, max_len):
        return tf.init_cache(self.cfg, batch, max_len)

    def init_tree_caches(self, batch, capacity):
        return tf.init_tree_caches(self.cfg, batch, capacity)

    def quantize(self) -> "ModelBundle":
        """Int8 serving copy: projection weights become per-out-channel
        symmetric int8 ``{"q8", "scale"}`` dicts (converted ONCE here) and
        ``cfg.quant = "int8"`` switches every cache this bundle builds to
        the int8 KV layout.  Dense attention families only; this bundle is
        left untouched — the fp32 path stays the bit-pinned reference.
        """
        cfg = self.cfg
        unsupported = (cfg.mla is not None or cfg.moe is not None
                       or cfg.ssm is not None or cfg.rglru is not None
                       or cfg.is_encdec)
        assert not unsupported, (
            f"int8 serving supports dense attention only, got {cfg.name!r}")
        from repro.kernels.quant import quantize_weight

        def leaf(path, w):
            n_in = QUANT_WEIGHTS.get(getattr(path[-1], "key", None))
            if n_in is None:
                return w
            if getattr(path[0], "key", None) == "stack":
                # stacked scan leaves carry a leading reps dim: quantize
                # each layer independently; the scale keeps the reps dim
                # so per-layer slicing / stage reshapes stay tree-mapped.
                return jax.vmap(lambda t: quantize_weight(t, n_in))(w)
            return quantize_weight(w, n_in)

        q_params = jax.tree_util.tree_map_with_path(leaf, self.params)
        return ModelBundle(q_params, dataclasses.replace(cfg, quant="int8"),
                           enc_out=self.enc_out,
                           prefix_embeds=self.prefix_embeds,
                           window_override=self.window_override)


def remap_tree_caches(tree_caches, index_map, capacity: int):
    """Compact tree-cache rows with the same permutation as the tree
    (rows whose index_map == -1 are dropped; stale rows are never attended).

    Buffers may have ``capacity + w`` rows (slack for fixed-width layer
    writes) and, when stacked for scan-over-layers, a leading reps dim — the
    length axis is resolved per buffer name.
    """
    def perm(cap):
        im = jnp.concatenate([
            index_map,
            jnp.full((cap - index_map.shape[0],), -1, jnp.int32)])
        # inverse permutation: g[new] = old (dropped rows pushed to the end)
        return jnp.argsort(jnp.where(im >= 0, im, cap + jnp.arange(cap)))

    def gather(path, buf):
        if buf is None:
            return None
        if paging.is_paged(buf):
            # paged rows: gather the permuted dense rows through the block
            # table and scatter them back — same permutation per slot
            g = perm(buf.length)
            idx = jnp.broadcast_to(g[None], (buf.slots, buf.length))
            return paging.from_dense(buf, paging.take_len_rows(buf, idx))
        name = path[-1].key
        ax = tf.cache_len_axis(name, buf)
        return jnp.take(buf, perm(buf.shape[ax]), axis=ax)

    return jax.tree_util.tree_map_with_path(
        gather, tree_caches,
        is_leaf=lambda x: x is None or paging.is_paged(x))


def draft_candidates(logits: jnp.ndarray, valid: jnp.ndarray, c: int):
    """Per-node top-c candidates from draft logits.

    logits: [..., w, V]; valid: [..., w].  Returns (cand_tokens
    [..., w, c], cand_logprobs [..., w, c]) with invalid rows at -inf.
    The rows are flattened to one batch axis first: on a TPU ``top_k``
    over a rank-3 operand compiles to a full sort of every row (28 ms
    for 16 x 8 rows of 152064 on a v5e), over a matrix to XLA's own
    top-k.
    """
    shape = logits.shape
    logp = jax.nn.log_softmax(
        logits.reshape(-1, shape[-1]).astype(jnp.float32), axis=-1)
    top_lp, top_tok = jax.lax.top_k(logp, c)
    top_lp = top_lp.reshape(*shape[:-1], c)
    top_tok = top_tok.reshape(*shape[:-1], c)
    top_lp = jnp.where(valid[..., None], top_lp, tree_lib.NEG_INF)
    return top_tok.astype(jnp.int32), top_lp
