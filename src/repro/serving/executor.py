"""Pluggable compute backends for SpecPipe-DB — the executor seam.

The logical scheduler (``serving.dynbatch.SpecPipeDBEngine`` multiplexing
``core.pipedec.PipeDecEngine`` state machines) decides *what* every request
computes; a ``PipelineExecutor`` decides *where and how* the per-timestep
batched work runs.  The seam is exactly the three fused dispatches a global
timestep needs, plus admission prefill:

  * ``verify_rows``  — ONE batched tree-verify per model over every active
    slot's deepest tree layer (per-row ``model_len`` / ``tree_write_index``
    / ``tree_mask [B, n, Tcap]``);
  * ``commit_rows``  — the batched two-level cache sync at exit (tree-row 0
    of every exiting slot migrates into its model cache at ``model_len``);
  * ``remap_row``    — post-prune tree-cache compaction of one slot;
  * ``prefill``      — join-on-prefill of an admitted request into its slot.

The executor owns the cache storage (the engine's states carry no cache
pytrees) and the power-of-two slot-count bucketing policy, so every
backend stays recompile-free: a dispatch covers the smallest power-of-two
prefix of slot rows spanning every active slot — at most log2(slots)+1
shapes per model.

Backends:

  * ``LocalFusedExecutor`` — PR-2's fused single-device path unchanged:
    slot-stacked ``KVArena`` pytrees, ``ModelBundle.tree_verify_rows`` /
    ``commit_rows`` dispatches.
  * ``ShardedPipelineExecutor`` — the paper's pipelined deployment, FLUSH
    schedule: the target's layer stack is partitioned over an
    ``n_stages``-device mesh (``launch.pipeline``), stage caches carry a
    leading slot axis mirroring the KV arena, and each timestep's verify
    is ONE compiled dispatch that flushes the batched entry layer around
    the ``ppermute`` activation ring (``n_stages`` hops;
    ``launch.pipeline.make_pipeline_verify``).  The draft runs replicated
    next to stage 0 (it proposes the next layer the same timestep, so it
    cannot ride the ring).  Because the flush keeps verify logits
    available at the entry timestep, the logical schedule — and therefore
    every request's token output — is bit-identical to the local backend.
  * ``OverlappedShardedExecutor`` — the same deployment in the paper's
    steady-state wall-clock regime: the ring *persists* across timesteps
    and stays full, so each global timestep is ONE tick (one stage-hop)
    instead of an ``n_stages``-hop flush — the ``flush=False`` pricing of
    ``core.sim.specpipe_db_sharded_*``, measured.  Verify logits only
    exist when a layer exits (``exit_t = t + n_stages - 1``), so
    ``verify_rows``/``tick_rows`` return *deferred* ``DeferredLogits``
    futures that the engine stores in its ``Flight``s and resolves at
    exit; exit commits and prune compactions enter the ring as a ctrl
    message trailing the in-flight layers (pruning propagation), misses
    and retirements ``kill`` the slot's in-flight layers in-ring and bump
    its tree version.  Committed tokens are bit-identical to the flush
    backend — only *when* logits materialise changes, never what is
    computed.

  * ``AsyncPipelineExecutor`` — the same schedule with the host lockstep
    BROKEN: every stage is a free-running actor thread on its own device
    pulling ring layers from a bounded inbox, applying the per-stage
    step factored out of the lockstep tick
    (``launch.pipeline.make_stage_fns``), and pushing to the next
    stage's inbox — a fast stage never waits on a slow one and the
    per-stage queue depth is uneven.  The draft is *disaggregated* onto
    a dedicated actor that speculates against the committed prefix in
    engine push order, feeding the dynamic token tree ahead of
    verification; kill/version messages short-circuit stale in-flight
    layers at whatever stage they sit instead of riding a full
    revolution.  Per-slot FIFO message order reproduces the lockstep
    schedule's per-stage arrival order exactly, so greedy outputs stay
    bit-identical — only WHEN each stage runs changes.

All backends expose ``calls`` (a Counter) as the dispatch-count hook: the
equivalence tests assert ``calls["verify_rows"]`` == one batched dispatch
per global timestep with pending entries (flush/local), and
``calls["pipeline_tick"]`` == one ring tick per executed global timestep
(overlapped); the async backend counts entry/ctrl *messages* and
per-stage steps instead (``calls["entry_msgs"]`` / ``calls["ctrl_msgs"]``
/ ``calls["stage_steps"]``).

Every backend's public calls (``prefill``, ``begin_prefill``,
``verify_rows``, ``tick_rows``, ``commit_rows``, ``remap_rows``) run
inside a ``specpipe.executor.<method>`` profiler span, and every program
an executor compiles carries a name of its own (``jit_<model>_<what>``).
"""
from __future__ import annotations

import collections
import functools
import queue
import threading
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.speculative import ModelBundle, named_jit, remap_tree_caches
from repro.launch import pipeline as pl
from repro.models import paging
from repro.models import transformer as tf
from repro.models.layers import embed
from repro.serving.scheduler import KVArena, PagedKVArena, SlotPool


def _traced(method):
    """Run an executor method inside a ``specpipe.executor.<name>``
    profiler span."""
    name = f"specpipe.executor.{method.__name__}"

    @functools.wraps(method)
    def call(self, *args, **kwargs):
        with TraceAnnotation(name):
            return method(self, *args, **kwargs)
    return call


def _full_table(slots: int, rows: int, page: int):
    """Fully-backed identity block table: slot ``b``'s logical block ``j``
    is physical block ``1 + b * mb + j`` (block 0 stays the null block).
    The sharded backends page their stage/draft arenas statically — the
    dynamic allocation/swap policies live in ``scheduler.PagedKVArena``
    behind the local backend."""
    mb = paging.n_blocks(rows, page)
    return jnp.asarray(
        1 + np.arange(slots * mb, dtype=np.int32).reshape(slots, mb))


def _paginate_full(cache, table, page: int):
    """Convert every KV leaf of a cache pytree (the
    ``CACHE_LEN_AXIS_FROM_END`` names, incl. int8 scales) to a
    fully-backed ``models.paging.Paged`` buffer sharing ``table``;
    recurrent state and other non-length leaves stay dense."""
    def conv(path, leaf):
        if leaf is None:
            return None
        name = getattr(path[-1], "key", None) if path else None
        if name not in tf.CACHE_LEN_AXIS_FROM_END:
            return leaf
        n_pre = tf.cache_len_axis(name, leaf) - 1
        return paging.make_paged(leaf, table, page, n_pre)

    return jax.tree_util.tree_map_with_path(
        conv, cache, is_leaf=lambda x: x is None)


def _stage_share(x, k: int, device):
    """Stage ``k``'s [1, ...] slice of a stage-major leaf, on ``device``:
    the shard already there when the leaf is placed over the stages (no
    copy), otherwise a copy of the slice."""
    for shard in x.addressable_shards:
        if shard.device == device and shard.index[0] == slice(k, k + 1):
            return shard.data
    return jax.device_put(x[k:k + 1], device)


class PipelineExecutor:
    """Backend interface + the shared slot-count bucketing policy.

    Subclasses implement ``prefill`` / ``verify_rows`` / ``commit_rows`` /
    ``remap_row`` against their own cache storage and expose ``arena``
    (a ``SlotPool``) for the scheduler's slot accounting."""

    slots: int
    arena: SlotPool

    def __init__(self, slots: int):
        self.slots = slots
        self.calls = collections.Counter()

    def _bucket(self, rows: int) -> int:
        """Smallest power-of-two prefix of slot rows spanning every row
        that must participate (capped at ``slots``)."""
        b = 1
        while b < rows:
            b *= 2
        return min(b, self.slots)

    # -- interface -----------------------------------------------------
    def prefill(self, slot: int, prompt):
        """Fill both models' caches for ``slot`` from a [1, len] prompt;
        returns the target's last-position logits [1, V]."""
        raise NotImplementedError

    def verify_rows(self, tokens, positions, masks, model_len, write_idx,
                    row_on):
        """ONE fused tree-verify per model over the bucketed prefix of
        slot rows.  All inputs span the full slot axis ([slots, ...]);
        returns (target logits [nb, w, V], draft logits [nb, w, V])."""
        raise NotImplementedError

    def commit_rows(self, model_len, commit_mask) -> None:
        """Batched two-level cache sync: every row with ``commit_mask``
        True migrates its tree-buffer row 0 into its model cache at its
        own ``model_len``; masked rows stay bit-unchanged."""
        raise NotImplementedError

    def remap_row(self, slot: int, index_map) -> None:
        """Post-prune tree-cache compaction on one slot's rows."""
        raise NotImplementedError

    def _draft_verify(self, tokens, positions, masks, model_len,
                      write_idx, row_on):
        """ONE bucketed draft tree-verify over the entering slot rows
        (shared by every backend: the draft proposes the next layer the
        same timestep, slot-stacked beside stage 0).  Returns the draft
        logits and the updated draft tree caches."""
        nb = self._bucket(int(np.max(np.nonzero(np.asarray(row_on))[0])) + 1)
        sl = lambda a: a[:nb]
        d_all, d_tree = self.draft.tree_verify_rows(
            sl(tokens), sl(positions), sl(masks), self._draft_cache(),
            sl(model_len), self._draft_tree(), sl(write_idx), bucket=nb)
        self.calls["verify_rows"] += 1
        return d_all, d_tree

    def _draft_cache(self):
        raise NotImplementedError

    def _draft_tree(self):
        raise NotImplementedError

    def remap_rows(self, index_maps, row_mask) -> None:
        """Batched exit-phase prune/remap: slot ``b``'s tree caches are
        compacted with ``index_maps[b]`` wherever ``row_mask[b]``
        (``index_maps`` rows for unmasked slots must be identity).  This
        base implementation loops ``remap_row`` over the masked slots —
        kept as the equivalence reference; backends override it with ONE
        batched gather per model (``tf.remap_tree_cache_rows``)."""
        for slot in np.nonzero(np.asarray(row_mask))[0]:
            self.remap_row(int(slot), index_maps[int(slot)])


class LocalFusedExecutor(PipelineExecutor):
    """PR-2's fused single-device path behind the executor seam: the
    slot-stacked ``KVArena`` is the storage, ``ModelBundle``'s jitted
    ``tree_verify_rows`` / ``commit_rows`` closures are the dispatches.

    ``paged=True`` swaps the arena for a ``PagedKVArena``: every KV leaf
    becomes a block pool + per-slot table (``models.paging``), the
    scheduler allocates/swaps/preempts blocks, and the jitted dispatches
    are unchanged — they densify the bucketed views at entry and scatter
    the updated tree rows back through the tables at exit."""

    def __init__(self, target: ModelBundle, draft: ModelBundle, *,
                 slots: int, max_len: int, tree_capacity: int,
                 capacity: int, paged: bool = False, page: int = 16,
                 model_blocks: Optional[int] = None,
                 tree_blocks: Optional[int] = None,
                 lazy_tree: bool = False):
        super().__init__(slots)
        self.target, self.draft = target, draft
        self.capacity = capacity
        self.paged = bool(paged)
        if self.paged:
            self.arena = PagedKVArena(
                target, draft, slots=slots, max_len=max_len,
                tree_capacity=tree_capacity, page=page,
                model_blocks=model_blocks, tree_blocks=tree_blocks,
                lazy_tree=lazy_tree)
        else:
            self.arena = KVArena(target, draft, slots=slots,
                                 max_len=max_len,
                                 tree_capacity=tree_capacity)

    @_traced
    def prefill(self, slot: int, prompt):
        t_cache, d_cache, t_tree, d_tree = self.arena.caches(slot)
        t_logits, t_cache = self.target.prefill(prompt, t_cache)
        _, d_cache = self.draft.prefill(prompt, d_cache)
        self.arena.store(slot, (t_cache, d_cache, t_tree, d_tree))
        return t_logits

    def _draft_cache(self):
        return self.arena.stacked[1]

    def _draft_tree(self):
        return self.arena.stacked[3]

    @_traced
    def verify_rows(self, tokens, positions, masks, model_len, write_idx,
                    row_on):
        nb = self._bucket(int(np.max(np.nonzero(np.asarray(row_on))[0])) + 1)
        sl = lambda a: a[:nb]
        t_cache, _, t_tree, _ = self.arena.stacked
        v_all, t_tree = self.target.tree_verify_rows(
            sl(tokens), sl(positions), sl(masks), t_cache, sl(model_len),
            t_tree, sl(write_idx), bucket=nb)
        d_all, d_tree = self._draft_verify(tokens, positions, masks,
                                           model_len, write_idx, row_on)
        self.arena.set_tree_caches(t_tree, d_tree)
        return v_all, d_all

    @_traced
    def commit_rows(self, model_len, commit_mask) -> None:
        node0 = jnp.zeros((self.slots,), jnp.int32)  # row 0 is the root
        t_cache, d_cache, t_tree, d_tree = self.arena.stacked
        t_cache = self.target.commit_rows(t_cache, t_tree, node0, model_len,
                                          commit_mask)
        d_cache = self.draft.commit_rows(d_cache, d_tree, node0, model_len,
                                         commit_mask)
        self.arena.set_model_caches(t_cache, d_cache)
        self.calls["commit_rows"] += 1

    def remap_row(self, slot: int, index_map) -> None:
        _, _, t_tree, d_tree = self.arena.stacked
        t_row = remap_tree_caches(tf.slice_cache_rows(t_tree, slot, 1),
                                  index_map, self.capacity)
        d_row = remap_tree_caches(tf.slice_cache_rows(d_tree, slot, 1),
                                  index_map, self.capacity)
        self.arena.set_tree_caches(
            tf.update_cache_rows(t_tree, t_row, slot),
            tf.update_cache_rows(d_tree, d_row, slot))

    @_traced
    def remap_rows(self, index_maps, row_mask) -> None:
        """ONE batched gather per model over the slot-stacked arena
        (identity rows leave unmasked slots bit-unchanged)."""
        if not np.any(np.asarray(row_mask)):
            return
        _, _, t_tree, d_tree = self.arena.stacked
        imaps = jnp.asarray(np.asarray(index_maps), jnp.int32)
        self.arena.set_tree_caches(_remap_rows_jit(t_tree, imaps),
                                   _remap_rows_jit(d_tree, imaps))
        self.calls["remap_rows"] += 1


# one compiled batched remap shared by every backend (retraces per cache
# pytree structure, i.e. once per model)
_remap_rows_jit = named_jit("remap_rows", tf.remap_tree_cache_rows)


def _sharded_verify_impl(stage_p, stage_valid, model_kv, tree_kv, x,
                         node_positions, tree_mask, write_idx, model_len,
                         row_on, *, bucket, verify_pass):
    """ONE compiled mesh dispatch: flush the bucketed, already embedded
    entry rows ``x`` [bucket, w, d] through every pipeline stage
    (``make_pipeline_verify``) and scatter the updated tree-cache rows
    back.  Returns the exiting activations; the caller unembeds them on
    the last stage's device, where the head lives.

    Paged stage arenas gather their bucketed dense views HERE — inside
    this one compiled dispatch but outside the shard_map'd flush (a
    ``Paged`` leaf's pool/table axes do not line up with the tree-mapped
    ``P(stage_axis)`` specs) — and the updated tree rows scatter back
    through the block tables at exit."""
    sl = lambda a: a[:bucket]

    def rows(c):
        return jax.tree_util.tree_map(
            lambda t: (paging.slice_slots(t, 0, bucket)
                       if paging.is_paged(t) else
                       None if t is None else t[:, :bucket]),
            c, is_leaf=lambda x: x is None or paging.is_paged(x))

    mkv_v = [rows(c) for c in model_kv]
    tkv_v = [rows(c) for c in tree_kv]
    entry = {
        "act": x,
        "positions": sl(node_positions),
        "mask": sl(tree_mask),
        "write_idx": sl(write_idx),
        "model_len": sl(model_len),
        "valid": sl(row_on),
    }
    exit_act, _, tkv_b = verify_pass(
        stage_p, stage_valid, [paging.densify(c) for c in mkv_v],
        [paging.densify(c) for c in tkv_v], entry)

    def put_back(full_c, view_c, upd_c):
        def f(full, view, upd):
            if full is None:
                return None
            if paging.is_paged(full):
                return paging.adopt_pool(full, paging.from_dense(view, upd))
            return jax.lax.dynamic_update_slice_in_dim(
                full, upd.astype(full.dtype), 0, axis=1)
        return jax.tree_util.tree_map(
            f, full_c, view_c, upd_c,
            is_leaf=lambda x: x is None or paging.is_paged(x))

    new_tree_kv = [put_back(f, v, u)
                   for f, v, u in zip(tree_kv, tkv_v, tkv_b)]
    return exit_act, new_tree_kv


def _sharded_prefill_impl(stage_p, stage_valid, model_kv, x, slot, *,
                          prefill_pass):
    """ONE compiled mesh dispatch for a slot's admission prefill: the
    embedded prompt ``x`` [1, L, d] crosses the stages
    (``make_pipeline_prefill``), each writing its own cache rows.  Paged
    arenas gather dense views here and scatter back at exit."""
    new_kv, hidden = prefill_pass(stage_p, stage_valid,
                                  [paging.densify(c) for c in model_kv], x,
                                  slot)
    return [paging.repaginate(v, c) for v, c in zip(model_kv, new_kv)], \
        hidden


class ShardedPipelineExecutor(PipelineExecutor):
    """SpecPipe-DB on the sharded ``launch.pipeline`` deployment.

    The target's uniform layer stack is partitioned over the mesh's
    "model" axis (``n_stages`` devices, ``stage_params`` layout); its
    model + tree KV live in stage-layout arenas — lists (per in-stage
    layer) of [S, slots, rows, ...] buffers, the leading slot dim
    mirroring the slot-stacked ``KVArena``.  Each global timestep issues
    exactly ONE sharded dispatch (``calls["pipeline_verify"]``): the
    batched entry layer rides the ``ppermute`` activation ring through
    all stages with its per-row metadata frozen at entry, and the exiting
    hidden states are unembedded into the verify logits.  The draft model
    (small, replicated) verifies/proposes through the same local fused
    dispatch the ``LocalFusedExecutor`` uses.

    Placement: each stage's layers stay on their stage's device (a target
    built by ``launch.pipeline.init_stage_placed`` is never gathered), the
    embedding table lives on the first stage's device and the unembedding
    head on the last's; tokens are embedded there, the mesh program moves
    activations only, and the exit is unembedded on the last device.
    Admission prefill crosses the stages the same way
    (``make_pipeline_prefill``), so no device needs the whole target.
    """

    def __init__(self, target: ModelBundle, draft: ModelBundle, *,
                 slots: int, max_len: int, tree_capacity: int,
                 capacity: int, n_stages: Optional[int] = None, mesh=None,
                 dtype=jnp.float32, paged: bool = False, page: int = 16):
        super().__init__(slots)
        self.target, self.draft = target, draft
        self.capacity, self.max_len = capacity, max_len
        self.dtype = dtype
        self.paged, self.page = bool(paged), int(page)
        width = tree_capacity - capacity
        assert width >= 1, "tree_capacity must include the width-w slack"
        if mesh is None:
            n = n_stages or len(jax.devices())
            mesh = pl.make_stage_mesh(n)
        self.mesh = mesh
        self.n_stages = mesh.shape["model"]
        assert n_stages is None or n_stages == self.n_stages, \
            "n_stages must equal the mesh's 'model' axis size"
        self.plcfg = pl.PipelineConfig(
            n_stages=self.n_stages, width=width, tree_capacity=capacity,
            max_len=max_len)
        self.lps, self._padded = pl.stage_layout(target.cfg, self.n_stages)
        self.stage_p, self.stage_valid = pl.stage_params(
            target.cfg, target.params, self.n_stages)
        devs = pl.stage_devices(mesh)
        self._last = devs[-1]
        self._replicated = NamedSharding(mesh, P())
        self._embed_p = jax.device_put(target.params["embed"], devs[0])
        self._head_p = jax.device_put(
            pl.head_params(target.params, target.cfg), self._last)
        cfg = target.cfg
        self._embed_j = named_jit(f"{cfg.name}_embed", embed)
        self._logits_j = named_jit(f"{cfg.name}_logits",
                                   lambda p, x: tf._logits(p, cfg, x))
        self.model_kv, self.tree_kv = pl.init_stage_caches(
            target.cfg, self.plcfg, dtype, batch=slots)
        self._d_cache = draft.init_cache(slots, max_len)
        self._d_tree = draft.init_tree_caches(slots, tree_capacity)
        if self.paged:
            # the sharded backends page their arenas *statically*: every
            # slot is fully backed through an identity table (the dynamic
            # block allocation/swap policies live behind the local
            # backend's PagedKVArena), so the sharded paths exercise the
            # same pool/table indirection end to end with unchanged
            # schedules.  One table per row geometry, shared by every
            # leaf of that geometry across stage layers + the draft.
            mt = _full_table(slots, max_len, self.page)
            tt = _full_table(slots, tree_capacity, self.page)
            self.model_kv = [_paginate_full(c, mt, self.page)
                             for c in self.model_kv]
            self.tree_kv = [_paginate_full(c, tt, self.page)
                            for c in self.tree_kv]
            self._d_cache = _paginate_full(self._d_cache, mt, self.page)
            self._d_tree = _paginate_full(self._d_tree, tt, self.page)
        self.arena = SlotPool(slots)

        verify_pass = pl.make_pipeline_verify(target.cfg, self.plcfg, mesh,
                                              dtype)
        prefill_pass = pl.make_pipeline_prefill(target.cfg, self.plcfg, mesh)
        self._verify = named_jit(
            f"{cfg.name}_pipeline_verify",
            functools.partial(_sharded_verify_impl, verify_pass=verify_pass),
            static_argnames=("bucket",))
        self._prefill = named_jit(
            f"{cfg.name}_pipeline_prefill",
            functools.partial(_sharded_prefill_impl,
                              prefill_pass=prefill_pass))
        self._commit = named_jit(
            f"{cfg.name}_pipeline_commit",
            functools.partial(self._commit_impl, cfg=target.cfg))

    def _draft_cache(self):
        return self._d_cache

    def _draft_tree(self):
        return self._d_tree

    # -- target stage-arena plumbing ------------------------------------
    @staticmethod
    def _commit_impl(model_kv, tree_kv, node_idx, model_len, commit_mask,
                     *, cfg):
        return [tf.commit_tree_nodes(cfg, mkv, tkv, node_idx, model_len,
                                     commit_mask)
                for mkv, tkv in zip(model_kv, tree_kv)]

    def _embed(self, tokens):
        """Embed on the first stage's device, then hand the activations
        to every stage (the mesh programs take them replicated)."""
        return jax.device_put(self._embed_j(self._embed_p, tokens),
                              self._replicated)

    def _unembed(self, act):
        """Verify logits from exit activations, on the last stage's
        device where the head lives."""
        return self._logits_j(self._head_p, jax.device_put(act, self._last))

    # -- interface ------------------------------------------------------
    @_traced
    def prefill(self, slot: int, prompt):
        x = tf._embed_inputs({"embed": self._embed_p}, self.target.cfg,
                             jnp.asarray(prompt), self.target.prefix_embeds)
        self.model_kv, hidden = self._prefill(
            self.stage_p, self.stage_valid, self.model_kv,
            jax.device_put(x, self._replicated), jnp.int32(slot))
        t_logits = self._unembed(hidden[:, -1])
        d_view = tf.slice_cache_rows(self._d_cache, slot, 1)
        _, d_row = self.draft.prefill(prompt, paging.densify(d_view))
        if paging.any_paged(d_view):
            d_row = paging.repaginate(d_view, d_row)
        self._d_cache = tf.update_cache_rows(self._d_cache, d_row, slot)
        return t_logits

    @_traced
    def verify_rows(self, tokens, positions, masks, model_len, write_idx,
                    row_on):
        nb = self._bucket(int(np.max(np.nonzero(np.asarray(row_on))[0])) + 1)
        exit_act, self.tree_kv = self._verify(
            self.stage_p, self.stage_valid, self.model_kv, self.tree_kv,
            self._embed(np.asarray(tokens)[:nb]), positions, masks,
            write_idx, model_len, jnp.asarray(np.asarray(row_on)),
            bucket=nb)
        v_all = self._unembed(exit_act)
        d_all, self._d_tree = self._draft_verify(tokens, positions, masks,
                                                 model_len, write_idx,
                                                 row_on)
        self.calls["pipeline_verify"] += 1
        return v_all, d_all

    @_traced
    def commit_rows(self, model_len, commit_mask) -> None:
        node0 = jnp.zeros((self.slots,), jnp.int32)
        self.model_kv = self._commit(self.model_kv, self.tree_kv, node0,
                                     model_len, commit_mask)
        self._d_cache = self.draft.commit_rows(
            self._d_cache, self._d_tree, node0, model_len, commit_mask)
        self.calls["commit_rows"] += 1

    def remap_row(self, slot: int, index_map) -> None:
        is_leaf = lambda x: x is None or paging.is_paged(x)

        def one(c):
            row = jax.tree_util.tree_map(
                lambda t: (paging.slice_slots(t, slot, 1)
                           if paging.is_paged(t) else
                           None if t is None else t[:, slot:slot + 1]),
                c, is_leaf=is_leaf)
            row = remap_tree_caches(row, index_map, self.capacity)

            def put(full, r):
                if full is None:
                    return None
                if paging.is_paged(full):
                    # the remapped view's pool IS the updated arena
                    return paging.adopt_pool(full, r)
                return full.at[:, slot:slot + 1].set(r.astype(full.dtype))

            return jax.tree_util.tree_map(put, c, row, is_leaf=is_leaf)

        self.tree_kv = [one(c) for c in self.tree_kv]
        self._d_tree = self._draft_remap_row(slot, index_map)

    def _draft_remap_row(self, slot: int, index_map):
        d_row = remap_tree_caches(
            tf.slice_cache_rows(self._d_tree, slot, 1), index_map,
            self.capacity)
        return tf.update_cache_rows(self._d_tree, d_row, slot)

    @_traced
    def remap_rows(self, index_maps, row_mask) -> None:
        """ONE batched gather per model: the stage-layout tree arenas
        ([S, slots, rows, ...] leaves) and the replicated draft's
        slot-stacked tree cache compact every pruned slot together."""
        if not np.any(np.asarray(row_mask)):
            return
        imaps = jnp.asarray(np.asarray(index_maps), jnp.int32)
        self.tree_kv = _remap_rows_jit(self.tree_kv, imaps)
        self._d_tree = _remap_rows_jit(self._d_tree, imaps)
        self.calls["remap_rows"] += 1


def _overlap_tick_impl(stage_p, stage_valid, model_kv, tree_kv, ring, x,
                       node_positions, tree_mask, write_idx, model_len,
                       entry_on, entry_version, p_x, p_len, p_on, p_off,
                       ctrl_commit, ctrl_len, ctrl_imap, ctrl_clear,
                       ctrl_active, kill, *, tick, prefill_cap):
    """ONE steady-state ring tick on the mesh: ingest the batched,
    already embedded entry layer ``x`` into stage 0, apply the (gated)
    pruning-propagation ctrl at whichever stage it reached this tick, and
    advance every in-flight layer — and the prefill lane — one stage.
    Returns the exiting activations; the caller unembeds them on the last
    stage's device, where the head lives.

    Admission prefill rides the SAME dispatch: ONE embedded prompt chunk
    ``p_x`` (up to ``prefill_cap`` tokens, written at per-slot cache
    offset ``p_off``) enters the ring's prefill lane, so admitting a
    request of ANY prompt length costs the ring zero extra dispatches —
    long prompts stream chunk by chunk over consecutive ticks.  The
    stage-resident state (``model_kv``/``tree_kv``/``ring``) is donated by
    the caller so XLA updates the buffers in place.

    Paged arenas gather dense views here — inside this one compiled
    dispatch but outside the shard_map'd tick (``Paged`` pool/table axes
    do not line up with the tree-mapped stage specs) — and scatter every
    updated row back through the block tables before returning."""
    paged_t = paging.any_paged(model_kv)
    if paged_t:
        mkv_v, tkv_v = model_kv, tree_kv
        model_kv = [paging.densify(c) for c in model_kv]
        tree_kv = [paging.densify(c) for c in tree_kv]
    entry = {
        "act": x,
        "positions": node_positions,
        "mask": tree_mask,
        "write_idx": write_idx,
        "model_len": model_len,
        "valid": entry_on,
        "version": entry_version,
    }
    ctrl = {"commit": ctrl_commit, "commit_len": ctrl_len,
            "index_map": ctrl_imap, "clear": ctrl_clear,
            "active": ctrl_active}
    pentry = None
    if prefill_cap:
        pentry = {"act": p_x, "len": p_len, "on": p_on, "off": p_off}
    model_kv, tree_kv, ring, exit_out = tick(
        stage_p, stage_valid, model_kv, tree_kv, ring, entry, kill, ctrl,
        pentry)
    if paged_t:
        model_kv = [paging.repaginate(v, c)
                    for v, c in zip(mkv_v, model_kv)]
        tree_kv = [paging.repaginate(v, c) for v, c in zip(tkv_v, tree_kv)]
    return (model_kv, tree_kv, ring, exit_out["act"], exit_out["valid"],
            exit_out["version"], exit_out.get("p_last"),
            exit_out.get("p_valid"))


def _draft_chunk_impl(d_params, p_tokens, d_cache, p_on, p_off, *, d_cfg):
    """The replicated draft's prefill of the entering prompt chunks,
    dispatched beside the ring tick: its caches are slot-stacked, so one
    batched chunk pass covers every joining slot; the chunk writes land
    at each slot's own ``p_off``, rows beyond the prompt length are never
    attended, and non-entering slots keep their buffers bit-unchanged."""
    dc = paging.densify(d_cache)
    dc = tf.where_cache_rows(
        p_on, tf.prefill_chunk(d_params, d_cfg, p_tokens, dc, p_off)[1], dc)
    return paging.repaginate(d_cache, dc)


class DeferredLogits:
    """Future for one slot's verify logits ([w, V]).

    Issued by ``OverlappedShardedExecutor`` at a layer's entry, stored in
    the engine's ``Flight.logits``, and resolved by the ring tick of the
    layer's exit timestep (``exit_t = entry_t + n_stages - 1``); a kill
    (miss / retire) marks every outstanding future of the slot dead, so a
    stale flight can never commit."""

    __slots__ = ("slot", "version", "_value", "dead")

    def __init__(self, slot: int, version: int):
        self.slot, self.version = slot, version
        self._value, self.dead = None, False

    def resolve(self):
        if self.dead:
            raise RuntimeError(
                f"stale flight: slot {self.slot} tree version "
                f"{self.version} was pruned/retired while in flight")
        if self._value is None:
            raise RuntimeError(
                f"slot {self.slot} flight consumed before its exit tick")
        return self._value


class DeferredPrefill:
    """Future for one slot's admission-prefill logits ([1, V]).

    Issued by ``OverlappedShardedExecutor.begin_prefill`` when the
    request's prompt enters the ring's prefill lane; resolved by the
    tick of the lane's exit timestep (``entry_t + n_stages - 1``), at
    which point the engine finishes the request's ``init_state`` with
    the resolved last-position logits.  A ``kill`` of the slot while the
    prompt is still riding marks the future dead — it will never
    resolve and must not be consumed.  ``AsyncPipelineExecutor.prefill``
    uses the same future for the prompt riding its stage actors."""

    __slots__ = ("slot", "_value", "dead")

    def __init__(self, slot: int):
        self.slot, self._value, self.dead = slot, None, False

    @property
    def ready(self) -> bool:
        return self._value is not None

    def resolve(self):
        if self.dead:
            raise RuntimeError(
                f"stale prefill: slot {self.slot} was killed while its "
                f"prompt was in flight")
        if self._value is None:
            raise RuntimeError(
                f"slot {self.slot} prefill consumed before its exit tick")
        return self._value


class OverlappedShardedExecutor(ShardedPipelineExecutor):
    """Steady-state overlapped schedule on the sharded deployment: ONE
    ring tick per global timestep with the ring always full — and kept
    as cheap as the hardware allows (gated ctrl, donated buffers,
    prefill-in-ring).

    Differences from the flush parent, all at the seam:

      * ``tick_rows`` (and ``verify_rows``) dispatch ONE
        ``make_pipedec_tick`` per timestep on a *persistent* ring and
        return ``DeferredLogits`` futures — the target's verify logits
        for an entering layer materialise only at its exit tick.  The
        ring/stage-cache/draft-cache pytrees are *donated* through the
        jitted tick (``donate=True``) so XLA updates them in place
        instead of copying them in and out every tick.
      * ``commit_rows`` / ``remap_row(s)`` queue the target-side cache
        mutation as the next tick's ctrl message (it must trail the
        in-flight layers stage by stage — pruning propagation); the
        replicated draft applies immediately, exactly as on the flush
        backend.  The ctrl channel is *gated* (``gate_ctrl=True``): the
        executor raises the per-tick ``active`` predicate only when exit
        ctrl was actually queued, so the all-identity message that rides
        most ticks costs each stage a predicate check instead of a full
        commit-scatter + prune-gather (``calls["ctrl_active_ticks"]`` /
        ``calls["pipeline_tick"]`` is the measured ctrl-active rate).
      * ``begin_prefill(slot, prompt)`` (``prefill_cap > 0``) overlaps
        admission prefill with the ring: the prompt is split into
        ``prefill_cap``-token chunks that enter the tick's prefill lane
        on consecutive ticks as a special layer kind (version-bumped
        slot, dead tree exit), each chunk writing the stage caches at
        its own per-slot offset (``p_off`` ring metadata) — the target
        stage by stage inside the tick, the replicated draft in a chunk
        pass beside it — so admission at ANY prompt length issues no
        separate target prefill and never idles the ring.  Returns a
        ``DeferredPrefill`` future resolved at the FINAL chunk's exit
        tick; ``None`` only when the lane is disabled
        (``prefill_cap == 0``).
      * ``kill(slot)`` invalidates the slot's in-flight layers in-ring
        (miss / retire) and bumps its tree version; ``drain()`` advances
        the ring with dead entries until every outstanding future
        (verify and prefill) has resolved (shutdown/test helper — the
        per-timestep ticks already resolve every live flight).

    All three cost levers preserve bit-identity: gating only skips
    messages that are the identity, donation only changes buffer
    aliasing, and the in-ring prefill computes the same per-layer math as
    the separate dispatch (pad rows are causally invisible).  The engine
    must tick every executed timestep (entries or not) and its
    ``PipeDecConfig.n_stages`` must equal the mesh's stage count — the
    ring IS the flight bookkeeping, so the fill latencies must agree.
    """

    overlapped = True

    def __init__(self, target: ModelBundle, draft: ModelBundle, *,
                 slots: int, max_len: int, tree_capacity: int,
                 capacity: int, n_stages: Optional[int] = None, mesh=None,
                 dtype=jnp.float32, gate_ctrl: bool = True,
                 donate: bool = True, prefill_cap: int = 64,
                 paged: bool = False, page: int = 16):
        super().__init__(target, draft, slots=slots, max_len=max_len,
                         tree_capacity=tree_capacity, capacity=capacity,
                         n_stages=n_stages, mesh=mesh, dtype=dtype,
                         paged=paged, page=page)
        self.gate_ctrl, self.donate = bool(gate_ctrl), bool(donate)
        if self.paged:
            # paged leaves share ONE block-table array per row geometry
            # across stage layers + the draft — XLA rejects donating the
            # same buffer twice, so the paged tick runs undonated
            self.donate = False
        # the draft is attention-family by construction (it tree-verifies
        # through the same per-row API), so its padded in-tick prefill is
        # causally invisible beyond each prompt's length — a recurrent
        # draft could not ride here (pad tokens would enter its state),
        # but such a draft cannot tree-verify at all
        self.prefill_cap = min(int(prefill_cap), max_len)
        if any(b.prefix_embeds is not None or b.enc_out is not None
               or b.window_override >= 0 for b in (target, draft)):
            # the in-ring prefill embeds raw prompt tokens only —
            # ModelBundle prefill semantics (prefix_embeds, enc_out,
            # window_override) must go through the parent's
            # separate-dispatch prefill, which bakes them in
            self.prefill_cap = 0
        self._ring = pl.init_ring(target.cfg, self.plcfg, dtype=self.dtype,
                                  batch=slots, ctrl=True,
                                  prefill_cap=self.prefill_cap)
        tick = pl.make_pipedec_tick(target.cfg, self.plcfg, self.mesh,
                                    prefill_cap=self.prefill_cap)
        impl = functools.partial(_overlap_tick_impl, tick=tick,
                                 prefill_cap=self.prefill_cap)
        # donate the persistent state pytrees (model_kv, tree_kv, ring;
        # d_cache through the draft's chunk pass) so XLA aliases them in
        # place
        self._tick = named_jit(
            f"{target.cfg.name}_pipeline_tick", impl,
            donate_argnums=(2, 3, 4) if self.donate else ())
        self._draft_chunk = named_jit(
            f"{draft.cfg.name}_prefill_chunk",
            functools.partial(_draft_chunk_impl, d_cfg=draft.cfg),
            donate_argnums=(2,) if self.donate else ())
        # per-slot tree version counters + outstanding-flight futures
        self._versions = np.zeros((slots,), np.int32)
        self._handles = [collections.deque() for _ in range(slots)]
        self._p_handles: dict = {}
        # chunked prefill bookkeeping: queued (chunk, offset) pairs not
        # yet entered, and outstanding lane exits per slot — the
        # DeferredPrefill resolves when the LAST chunk exits
        self._p_queue: dict = {}
        self._p_exits: dict = {}
        self._identity_imap = np.tile(
            np.arange(capacity, dtype=np.int32), (slots, 1))
        self._kill_mask = np.zeros((slots,), bool)
        self._reset_ctrl()
        self._reset_prefill()
        w = self.plcfg.width
        tcap = capacity + w
        self.dead_entry = (
            jnp.zeros((slots, w), jnp.int32),        # tokens
            jnp.zeros((slots, w), jnp.int32),        # positions
            jnp.zeros((slots, w, tcap), bool),       # masks
            jnp.zeros((slots,), jnp.int32),          # model_len
            jnp.full((slots,), capacity, jnp.int32),  # write_idx (parked)
        )

    def _reset_ctrl(self) -> None:
        self._ctrl_commit = np.zeros((self.slots,), bool)
        self._ctrl_len = np.zeros((self.slots,), np.int32)
        self._ctrl_imap = self._identity_imap.copy()
        self._ctrl_clear = np.zeros((self.slots,), bool)
        self._ctrl_active = False

    def _reset_prefill(self) -> None:
        cap = max(self.prefill_cap, 1)
        self._p_tokens = np.zeros((self.slots, cap), np.int32)
        self._p_len = np.zeros((self.slots,), np.int32)
        self._p_on = np.zeros((self.slots,), bool)
        self._p_off = np.zeros((self.slots,), np.int32)

    def _stage_chunk(self, slot: int, chunk, off: int) -> None:
        """Load one prompt chunk into the slot's prefill-lane entry row
        for the next tick (tokens + per-slot cache offset)."""
        self._p_tokens[slot] = 0
        self._p_tokens[slot, :len(chunk)] = chunk
        self._p_len[slot] = len(chunk)
        self._p_off[slot] = off
        self._p_on[slot] = True

    # -- prefill-in-ring ------------------------------------------------
    @_traced
    def begin_prefill(self, slot: int, prompt):
        """Queue ``slot``'s admission prefill into the ring: the prompt
        is split into ``prefill_cap``-token chunks that enter the
        prefill lane on consecutive ticks (each chunk written at its
        own cache offset), so prompts of ANY length stream through the
        ring with zero separate prefill dispatches.  Both models'
        chunk prefills run inside each tick's single dispatch.  Returns
        a ``DeferredPrefill`` future resolved at the FINAL chunk's exit
        tick, or ``None`` only when the lane is disabled
        (``prefill_cap == 0`` — caller falls back to the
        separate-dispatch ``prefill``)."""
        pr = np.asarray(prompt).reshape(-1).astype(np.int32)
        if not self.prefill_cap:
            return None
        if self._handles[slot] or slot in self._p_handles:
            raise RuntimeError(
                f"slot {slot} still has outstanding futures at admission")
        cap = self.prefill_cap
        chunks = [(pr[i:i + cap], i)
                  for i in range(0, len(pr), cap)] or [(pr, 0)]
        self._versions[slot] += 1        # version-bumped slot
        self._stage_chunk(slot, *chunks[0])
        if chunks[1:]:
            self._p_queue[slot] = collections.deque(chunks[1:])
        self._p_exits[slot] = len(chunks)
        h = DeferredPrefill(slot)
        self._p_handles[slot] = h
        self.calls["prefill_in_ring"] += 1
        self.calls["prefill_chunks"] += len(chunks)
        return h

    # -- the per-timestep ring tick -------------------------------------
    def _dispatch_tick(self, tokens, positions, masks, model_len,
                       write_idx, row_on, counter: str) -> None:
        """Run one compiled ring tick (consuming any queued ctrl, kill
        and prefill entries) and resolve the futures of every layer —
        and every prefill — that exited."""
        ctrl_active = self._ctrl_active or not self.gate_ctrl
        p_x = self._embed(self._p_tokens) if self.prefill_cap else None
        (self.model_kv, self.tree_kv, self._ring, exit_act, exit_valid,
         exit_version, p_last, p_valid) = self._tick(
            self.stage_p, self.stage_valid, self.model_kv, self.tree_kv,
            self._ring, self._embed(np.asarray(tokens)), positions, masks,
            write_idx, model_len, jnp.asarray(np.asarray(row_on)),
            jnp.asarray(self._versions), p_x, jnp.asarray(self._p_len),
            jnp.asarray(self._p_on), jnp.asarray(self._p_off),
            jnp.asarray(self._ctrl_commit), jnp.asarray(self._ctrl_len),
            jnp.asarray(self._ctrl_imap), jnp.asarray(self._ctrl_clear),
            jnp.asarray(ctrl_active), jnp.asarray(self._kill_mask))
        if self._p_on.any():
            self._d_cache = self._draft_chunk(
                self.draft.params, jnp.asarray(self._p_tokens),
                self._d_cache, jnp.asarray(self._p_on),
                jnp.asarray(self._p_off))
        if ctrl_active and counter == "pipeline_tick":
            # drain ticks are counted separately — the ctrl-active rate
            # (ctrl_active_ticks / pipeline_tick) prices steady state only
            self.calls["ctrl_active_ticks"] += 1
        self._reset_ctrl()
        self._reset_prefill()
        self._kill_mask[:] = False
        # the lane is free again — feed each streaming prompt's next
        # queued chunk so it enters with the NEXT tick (chunk c+1 reaches
        # every stage exactly one tick behind chunk c's writes there)
        for slot in list(self._p_queue):
            q = self._p_queue[slot]
            self._stage_chunk(slot, *q.popleft())
            if not q:
                del self._p_queue[slot]
        self.calls[counter] += 1

        ev, evers = np.asarray(exit_valid), np.asarray(exit_version)
        exit_logits = self._unembed(exit_act) if ev.any() else None
        for slot in np.nonzero(ev)[0]:
            q = self._handles[int(slot)]
            if not q:
                raise RuntimeError(
                    f"ring exit for slot {slot} with no outstanding flight")
            h = q.popleft()
            if h.version != int(evers[slot]):
                raise RuntimeError(
                    f"tree-version mismatch at ring exit: slot {slot} "
                    f"entered at version {h.version}, exited carrying "
                    f"{int(evers[slot])}")
            h._value = exit_logits[slot]

        if self.prefill_cap and np.asarray(p_valid).any():
            p_logits = self._unembed(p_last)
            for slot in np.nonzero(np.asarray(p_valid))[0]:
                s = int(slot)
                if s not in self._p_exits:
                    raise RuntimeError(
                        f"prefill exit for slot {s} with no "
                        f"outstanding prefill future")
                self._p_exits[s] -= 1
                if self._p_exits[s] == 0:
                    # the FINAL chunk's exit carries the prompt's
                    # last-position logits — earlier chunk exits only
                    # mark ring progress
                    del self._p_exits[s]
                    self._p_handles.pop(s)._value = p_logits[s:s + 1]

    @_traced
    def tick_rows(self, tokens, positions, masks, model_len, write_idx,
                  row_on):
        """ONE ring tick for this global timestep.

        ``row_on`` marks the slot rows entering a new tree layer; all
        other metadata rows are dead and ride masked.  Returns
        ``(d_all, handles)``: ``handles`` maps each entering slot to the
        ``DeferredLogits`` future of its exit tick, ``d_all`` is the
        draft's proposal logits over the bucketed entering rows (``None``
        when nothing enters — the tick still runs, advancing the ring).
        """
        row_on_np = np.asarray(row_on)
        handles = {}
        for slot in np.nonzero(row_on_np)[0]:
            h = DeferredLogits(int(slot), int(self._versions[slot]))
            self._handles[int(slot)].append(h)
            handles[int(slot)] = h

        self._dispatch_tick(tokens, positions, masks, model_len,
                            write_idx, row_on_np, "pipeline_tick")

        d_all = None
        if row_on_np.any():
            d_all, self._d_tree = self._draft_verify(
                tokens, positions, masks, model_len, write_idx, row_on_np)
        return d_all, handles

    # -- PipelineExecutor seam ------------------------------------------
    @_traced
    def verify_rows(self, tokens, positions, masks, model_len, write_idx,
                    row_on):
        """Standard seam, overlapped semantics: returns (handles, d_all)
        where ``handles`` are deferred futures instead of logits."""
        d_all, handles = self.tick_rows(tokens, positions, masks,
                                        model_len, write_idx, row_on)
        return handles, d_all

    @_traced
    def commit_rows(self, model_len, commit_mask) -> None:
        """Queue the target-side exit commit as the next tick's ctrl
        message (it must trail the in-flight layers through the ring);
        the replicated draft commits immediately, like the flush
        backend."""
        mask = np.asarray(commit_mask)
        ml = np.asarray(model_len).astype(np.int32)
        self._ctrl_commit |= mask
        self._ctrl_len = np.where(mask, ml, self._ctrl_len)
        if mask.any():
            self._ctrl_active = True
        node0 = jnp.zeros((self.slots,), jnp.int32)
        self._d_cache = self.draft.commit_rows(
            self._d_cache, self._d_tree, node0, model_len, commit_mask)
        self.calls["commit_rows"] += 1

    def remap_row(self, slot: int, index_map) -> None:
        self._ctrl_imap[slot] = np.asarray(index_map, np.int32)
        self._ctrl_active = True
        self._d_tree = self._draft_remap_row(slot, index_map)

    @_traced
    def remap_rows(self, index_maps, row_mask) -> None:
        rm = np.asarray(row_mask)
        if not rm.any():
            return
        imaps = np.asarray(index_maps, np.int32)
        self._ctrl_imap = np.where(rm[:, None], imaps, self._ctrl_imap)
        self._ctrl_active = True
        self._d_tree = _remap_rows_jit(self._d_tree,
                                       jnp.asarray(imaps, jnp.int32))
        self.calls["remap_rows"] += 1

    # -- pruning propagation: miss / retire -----------------------------
    def kill(self, slot: int, *, drop_ctrl: bool = False) -> None:
        """Invalidate the slot's in-flight ring layers (miss / retire):
        the kill enters with the next tick, stale layers stop writing
        their stage tree-cache rows and exit dead, and the slot's tree
        version advances so no stale future can ever resolve.
        ``drop_ctrl=True`` (retire) also cancels the slot's queued ctrl
        AND neutralises its ctrl messages still riding the ring (via the
        next tick's ``clear`` mask) — the slot is being recycled, and a
        retired occupant's in-flight commits/prunes must never write
        into the next occupant's freshly prefilled caches.  A miss keeps
        both: the missed request's earlier commits stay valid and must
        finish propagating stage by stage."""
        self._versions[slot] += 1
        self._kill_mask[slot] = True
        for h in self._handles[slot]:
            h.dead = True
        self._handles[slot].clear()
        # a prefill still riding (or queued) for the slot dies with it:
        # the tick masks the lane via ``kill``, so its future would
        # otherwise never resolve and drain() could never finish
        ph = self._p_handles.pop(slot, None)
        if ph is not None:
            ph.dead = True
        if self.prefill_cap:
            self._p_on[slot] = False
            self._p_len[slot] = 0
            self._p_off[slot] = 0
            self._p_tokens[slot] = 0
            self._p_queue.pop(slot, None)
            self._p_exits.pop(slot, None)
        if drop_ctrl:
            self._ctrl_commit[slot] = False
            self._ctrl_len[slot] = 0
            self._ctrl_imap[slot] = self._identity_imap[slot]
            self._ctrl_clear[slot] = True
        self.calls["kill"] += 1

    def drain(self) -> int:
        """Advance the ring with dead entries until every outstanding
        future — verify AND prefill — has resolved (at most
        ``n_stages - 1`` ticks, plus one tick per still-queued prompt
        chunk of a streaming prefill).  The engine's per-timestep ticks
        already resolve every live flight, so this is a shutdown/test
        helper, counted separately from the steady-state dispatches."""
        tokens, positions, masks, model_len, write_idx = self.dead_entry
        row_on = np.zeros((self.slots,), bool)
        limit = self.n_stages + max(
            [len(q) for q in self._p_queue.values()], default=0)
        n = 0
        while any(self._handles) or self._p_handles:
            assert n < limit, "ring failed to drain"
            self._dispatch_tick(tokens, positions, masks, model_len,
                                write_idx, row_on, "drain_tick")
            n += 1
        return n


# ---------------------------------------------------------------------------
# Async free-running stages + disaggregated draft
# ---------------------------------------------------------------------------

class AsyncExecutorError(RuntimeError):
    """A stage/draft actor raised (original traceback attached), or the
    host timed out waiting on the async pipe.  Raised on the HOST thread
    by every blocking executor operation so a failed actor can never
    hang the engine — ``sharded_check`` converts it into a
    ``SHARDED_CHECK fail`` status line."""


class _Abort(Exception):
    """Internal: another actor already failed; unwind this one quietly."""


class _AsyncDeferredLogits(DeferredLogits):
    """A ``DeferredLogits`` whose resolve *pumps* the exit queue: the
    async pipe delivers exits whenever the last stage finishes, so the
    engine blocks here (bounded, error-propagating) until this flight's
    exit has been consumed."""

    __slots__ = ("_ex",)

    def __init__(self, slot: int, version: int, ex):
        super().__init__(slot, version)
        self._ex = ex

    def resolve(self):
        while self._value is None and not self.dead:
            self._ex._pump()
        return super().resolve()


class _DraftVerifyResult:
    """Future for one timestep's batched draft proposal logits
    ([bucket, w, V]), filled by the draft actor.  The engine's batched
    expansion, its first consumer, waits on it once (``resolve``);
    ``__getitem__`` hands ``apply_entry`` a per-slot ``resolve()``-able
    row (the lazy counterpart of slicing the eager array)."""

    __slots__ = ("_ex", "_event", "_value")

    def __init__(self, ex):
        self._ex = ex
        self._event = threading.Event()
        self._value = None

    def __getitem__(self, slot: int):
        return _DeferredDraftRow(self, int(slot))

    def wait(self):
        deadline = time.monotonic() + self._ex.timeout_s
        while not self._event.wait(0.05):
            self._ex._check_errors()
            if time.monotonic() > deadline:
                raise AsyncExecutorError(
                    f"timed out after {self._ex.timeout_s}s waiting for "
                    f"the draft actor's verify")
        return self._value

    resolve = wait


class _DeferredDraftRow:
    """One slot's row of a pending draft verify ([w, V] once resolved)."""

    __slots__ = ("_all", "slot")

    def __init__(self, all_, slot: int):
        self._all, self.slot = all_, slot

    def resolve(self):
        return self._all.wait()[self.slot]


class AsyncPipelineExecutor(PipelineExecutor):
    """Free-running per-stage actors + a disaggregated draft actor — the
    host lockstep of the overlapped schedule, broken.

    Every stage ``k`` is a daemon thread pinned to its own device that
    pulls messages from a bounded inbox, applies its compiled per-stage
    step (``launch.pipeline.make_stage_fns`` — the SAME math the
    lockstep tick composes inside its ``shard_map`` body), and pushes to
    stage ``k+1``'s inbox; the last stage unembeds exits into an
    unbounded exit queue the engine thread consumes.  A fast stage never
    waits on a slow one, and per-stage queue depth is uneven
    (``stage_counters`` records occupancy/idle per stage).  The draft
    model lives on a dedicated actor with its own device and cache
    ownership: verify/commit/remap/prefill jobs are applied in engine
    push order, so speculation runs continuously ahead of the target's
    in-flight verifications (``draft_lead()`` is the gauge).

    Message protocol (all slot-batched, one message per engine timestep
    lane):

      * ``layer`` — the entering tree layer: tokens + per-row metadata +
        a per-slot tree-version snapshot.  Stage 0 embeds; each stage
        recomputes the row's liveness (``snapshot == current version``)
        at *processing* time, so a ``kill`` short-circuits a stale layer
        at whatever stage it currently sits (the stale rows stop writing
        immediately) instead of riding a full revolution.
      * ``ctrl`` — pruning propagation: exit-commit + prune index map
        with a ctrl-version snapshot; pushed BEFORE the next entry so
        per-stage FIFO order equals the lockstep schedule's per-stage
        arrival order (ctrl trails every pre-prune layer, leads every
        post-prune one).  A retire (``kill(drop_ctrl=True)``) bumps the
        ctrl version, neutralising the slot's in-flight ctrl wherever it
        sits; a miss does NOT (its earlier commits must finish
        propagating).
      * ``prefill`` — admission prefill (the async backend has no prefill
        lane; ``prefill_cap == 0``): the embedded prompt rides the pipe
        as one message, each stage writing the slot's cache rows with
        its own layers, landing AFTER the retired occupant's
        (suppressed) stale messages — FIFO gives the recycle ordering
        for free — and the last stage returns the prompt's logits.

    Bit-identity argument: each stage processes one global message
    sequence FIFO, which reproduces the lockstep schedule's per-stage
    arrival order exactly; the per-stage compute is the same factored
    function on the same batched rows; and stale-layer writes that the
    version race suppresses earlier (or later) than the lockstep kill
    mask would only ever land in rows a live tree rewrites before
    attending.  Greedy tokens therefore match the lockstep executors
    bit for bit — pinned by ``sharded_check --async`` in CI.

    Failure semantics: an actor exception is recorded, flips a shared
    ``failed`` event (unwinding the other actors), and re-raises on the
    host thread as ``AsyncExecutorError`` from every blocking call
    within ``timeout_s`` — the pipe fails loudly, never hangs.
    ``shutdown()`` drains, stops and joins all actor threads
    (idempotent; the executor restarts lazily on next use).
    """

    overlapped = True     # engine drives the deferred-logits schedule
    prefill_cap = 0       # admission uses the separate-dispatch prefill

    def __init__(self, target: ModelBundle, draft: ModelBundle, *,
                 slots: int, max_len: int, tree_capacity: int,
                 capacity: int, n_stages: Optional[int] = None,
                 dtype=jnp.float32, inbox_depth: int = 8,
                 timeout_s: float = 180.0, devices=None):
        super().__init__(slots)
        self.target, self.draft = target, draft
        self.capacity, self.max_len = capacity, max_len
        self.dtype = dtype
        self.timeout_s = float(timeout_s)
        self.inbox_depth = int(inbox_depth)
        width = tree_capacity - capacity
        assert width >= 1, "tree_capacity must include the width-w slack"
        self.n_stages = int(n_stages or len(jax.devices()))
        self.plcfg = pl.PipelineConfig(
            n_stages=self.n_stages, width=width, tree_capacity=capacity,
            max_len=max_len)
        self.lps, self._padded = pl.stage_layout(target.cfg, self.n_stages)
        devs = list(devices) if devices is not None else jax.devices()
        # one stage per device (round-robin when the host has fewer
        # devices than stages); the draft actor takes the next device
        self._devices = [devs[k % len(devs)] for k in range(self.n_stages)]
        self._draft_device = devs[self.n_stages % len(devs)]
        self.arena = SlotPool(slots)

        is_leaf = lambda x: x is None

        def put_stage(tree, k):
            return jax.tree_util.tree_map(
                lambda t: None if t is None else
                jax.device_put(t[k], self._devices[k]),
                tree, is_leaf=is_leaf)

        def own_share(tree, k):
            return jax.tree.map(
                lambda t: _stage_share(t, k, self._devices[k]), tree)

        layers, valid = pl.stage_params(target.cfg, target.params,
                                        self.n_stages)
        model_kv, tree_kv = pl.init_stage_caches(target.cfg, self.plcfg,
                                                 dtype, batch=slots)
        valid = np.asarray(valid)
        # per-stage actor state: param slices ([1, ...] leaves, taken
        # from the shard already on the stage's device when the target
        # was placed) + cache slices committed to the stage's device
        # (each list entry owned by ONE actor thread)
        self._sp = [[own_share(layers[l], k) for l in range(self.lps)]
                    for k in range(self.n_stages)]
        self._sv = [valid[k] for k in range(self.n_stages)]
        self._kv = [[put_stage(model_kv[l], k) for l in range(self.lps)]
                    for k in range(self.n_stages)]
        self._tkv = [[put_stage(tree_kv[l], k) for l in range(self.lps)]
                     for k in range(self.n_stages)]
        # draft state, owned by the draft actor
        self._d_cache = jax.device_put(draft.init_cache(slots, max_len),
                                       self._draft_device)
        self._d_tree = jax.device_put(
            draft.init_tree_caches(slots, tree_capacity),
            self._draft_device)

        self._embed_p = jax.device_put(target.params["embed"],
                                       self._devices[0])
        self._head_last = jax.device_put(
            pl.head_params(target.params, target.cfg), self._devices[-1])

        stage_apply, stage_ctrl, stage_prefill = pl.make_stage_fns(
            target.cfg, self.plcfg)
        cfg = target.cfg
        unstack = lambda sp: [jax.tree.map(lambda t: t[0], lp) for lp in sp]
        self._apply_j = named_jit(
            f"{cfg.name}_stage_apply",
            lambda sp, *a: stage_apply(unstack(sp), *a))
        self._ctrl_j = named_jit(f"{cfg.name}_stage_ctrl", stage_ctrl)
        self._prefill_j = named_jit(
            f"{cfg.name}_stage_prefill",
            lambda sp, *a: pl.prefill_slot(stage_prefill, unstack(sp), *a))
        self._embed_j = named_jit(f"{cfg.name}_embed", embed)
        self._logits_j = named_jit(f"{cfg.name}_logits",
                                   lambda p, x: tf._logits(p, cfg, x))

        # per-slot versions: layer staleness (bumped on EVERY kill) vs
        # ctrl staleness (bumped only on drop_ctrl retires — a miss must
        # let the missed slot's in-flight commits finish propagating)
        self._versions = np.zeros((slots,), np.int64)
        self._ctrl_versions = np.zeros((slots,), np.int64)
        self._handles = [collections.deque() for _ in range(slots)]
        self._identity_imap = np.tile(
            np.arange(capacity, dtype=np.int32), (slots, 1))
        self._reset_ctrl()
        w = self.plcfg.width
        tcap = capacity + w
        self.dead_entry = (
            jnp.zeros((slots, w), jnp.int32),        # tokens
            jnp.zeros((slots, w), jnp.int32),        # positions
            jnp.zeros((slots, w, tcap), bool),       # masks
            jnp.zeros((slots,), jnp.int32),          # model_len
            jnp.full((slots,), capacity, jnp.int32),  # write_idx (parked)
        )

        # actor plumbing (threads start lazily on first use)
        self._inboxes = [queue.Queue(maxsize=self.inbox_depth)
                         for _ in range(self.n_stages)]
        self._exit_q: queue.Queue = queue.Queue()
        self._draft_q: queue.Queue = queue.Queue()
        self._errors: list = []
        self._failed = threading.Event()
        self._gate = threading.Event()   # test hook: pause()/resume()
        self._gate.set()
        self._threads: list = []
        self._started = False
        self._seq = 0
        self._pushed = self._consumed = 0
        self._draft_pushed = self._draft_done = 0
        self._draft_verified = 0
        self._exit_layers_consumed = 0
        self._max_draft_lead = 0
        self._calls_lock = threading.Lock()
        self.stage_counters = [
            {"msgs": 0, "layers": 0, "stale_rows": 0, "ctrl_applied": 0,
             "ctrl_skipped": 0, "busy_s": 0.0, "idle_s": 0.0,
             "max_depth": 0}
            for _ in range(self.n_stages)]

    # -- small shared helpers -------------------------------------------
    def _reset_ctrl(self) -> None:
        self._ctrl_commit = np.zeros((self.slots,), bool)
        self._ctrl_len = np.zeros((self.slots,), np.int32)
        self._ctrl_imap = self._identity_imap.copy()
        self._ctrl_active = False

    def _count(self, key: str, n: int = 1) -> None:
        with self._calls_lock:
            self.calls[key] += n

    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    # -- host-side error/timeout propagation ----------------------------
    def _check_errors(self) -> None:
        if self._errors:
            who, tb = self._errors[0]
            raise AsyncExecutorError(
                f"async pipeline actor '{who}' failed:\n{tb}")

    def _push(self, msg) -> None:
        """Feed stage 0's bounded inbox (bounded wait, error-raising)."""
        self._ensure_started()
        deadline = time.monotonic() + self.timeout_s
        while True:
            self._check_errors()
            try:
                self._inboxes[0].put(msg, timeout=0.1)
                break
            except queue.Full:
                if time.monotonic() > deadline:
                    raise AsyncExecutorError(
                        f"timed out after {self.timeout_s}s feeding the "
                        f"stage-0 inbox (pipe stalled)")
        self._pushed += 1

    def _pump(self) -> None:
        """Consume at least one message from the exit queue (bounded
        wait, error-raising) — the engine thread's only exit-consumption
        path, so handle bookkeeping is single-threaded."""
        deadline = time.monotonic() + self.timeout_s
        while True:
            self._check_errors()
            try:
                msg = self._exit_q.get(timeout=0.1)
            except queue.Empty:
                if time.monotonic() > deadline:
                    raise AsyncExecutorError(
                        f"timed out after {self.timeout_s}s waiting for "
                        f"a pipeline exit")
                continue
            self._consume_exit(msg)
            return

    def _pump_ready(self) -> None:
        """Drain whatever exits are already delivered (non-blocking)."""
        while True:
            try:
                msg = self._exit_q.get_nowait()
            except queue.Empty:
                return
            self._consume_exit(msg)

    def _consume_exit(self, msg) -> None:
        self._consumed += 1
        if msg[0] == "exit_prefill":
            _, _seq, logits, handle = msg
            handle._value = logits
            return
        if msg[0] != "exit_layer":
            return                       # ctrl/stop pass-through
        _, _seq, logits, row_on, versions = msg
        self._exit_layers_consumed += 1
        for slot in np.nonzero(row_on)[0]:
            s = int(slot)
            if versions[s] != self._versions[s]:
                # run-ahead exit of a flight killed after it left the
                # last stage — its future is already dead; dropping the
                # stale logits is the async analogue of the lockstep
                # exit_valid mask
                self._count("stale_exits")
                continue
            q = self._handles[s]
            if not q:
                raise AsyncExecutorError(
                    f"ring exit for slot {s} with no outstanding flight")
            h = q.popleft()
            if h.version != int(versions[s]):
                raise AsyncExecutorError(
                    f"tree-version mismatch at ring exit: slot {s} "
                    f"entered at version {h.version}, exited carrying "
                    f"{int(versions[s])}")
            h._value = logits[s]

    # -- actor-side primitives (bounded, abort-aware) -------------------
    def _aget(self, q):
        while True:
            if self._failed.is_set():
                raise _Abort
            try:
                return q.get(timeout=0.2)
            except queue.Empty:
                continue

    def _aput(self, q, msg) -> None:
        while True:
            if self._failed.is_set():
                raise _Abort
            try:
                q.put(msg, timeout=0.2)
                return
            except queue.Full:
                continue

    def _wait_gate(self) -> None:
        while not self._gate.wait(0.2):
            if self._failed.is_set():
                raise _Abort

    def pause(self) -> None:
        """Test hook: hold every stage actor BEFORE its next message, so
        a test can stage messages + kills deterministically."""
        self._gate.clear()

    def resume(self) -> None:
        self._gate.set()

    # -- actor loops -----------------------------------------------------
    def _ensure_started(self) -> None:
        if self._started:
            return
        self._started = True
        self._threads = []
        for k in range(self.n_stages):
            t = threading.Thread(target=self._stage_loop, args=(k,),
                                 name=f"async-stage-{k}", daemon=True)
            t.start()
            self._threads.append(t)
        t = threading.Thread(target=self._draft_loop, name="async-draft",
                             daemon=True)
        t.start()
        self._threads.append(t)

    def _stage_loop(self, k: int) -> None:
        ctr = self.stage_counters[k]
        inbox = self._inboxes[k]
        out = (self._inboxes[k + 1] if k + 1 < self.n_stages
               else self._exit_q)
        try:
            while True:
                t_idle = time.perf_counter()
                msg = self._aget(inbox)
                ctr["idle_s"] += time.perf_counter() - t_idle
                ctr["max_depth"] = max(ctr["max_depth"],
                                       inbox.qsize() + 1)
                self._wait_gate()
                t0 = time.perf_counter()
                kind = msg[0]
                if kind == "stop":
                    self._aput(out, msg)
                    return
                if kind == "layer":
                    msg = self._stage_layer(k, ctr, msg)
                elif kind == "ctrl":
                    self._stage_ctrl_msg(k, ctr, msg)
                elif kind == "prefill":
                    msg = self._stage_prefill(k, msg)
                ctr["msgs"] += 1
                ctr["busy_s"] += time.perf_counter() - t0
                self._aput(out, msg)
        except _Abort:
            pass
        except BaseException:
            self._errors.append((f"stage{k}", traceback.format_exc()))
            self._failed.set()

    def _stage_layer(self, k: int, ctr, msg):
        (_, seq, x, positions, masks, model_len, write_idx, row_on,
         versions) = msg
        # liveness at PROCESSING time: a kill bumps the slot's version,
        # so the stale layer stops writing at whatever stage it sits —
        # no revolution wait
        live = row_on & (versions == self._versions)
        stale = int(np.count_nonzero(row_on & ~live))
        if stale:
            ctr["stale_rows"] += stale
        if k == 0:
            x = self._embed_j(self._embed_p, x)   # x carries tokens here
        else:
            x = jax.device_put(x, self._devices[k])
        x, self._tkv[k] = self._apply_j(
            self._sp[k], self._sv[k], self._kv[k], self._tkv[k], x,
            positions, masks, write_idx, model_len, live)
        ctr["layers"] += 1
        self._count("stage_steps")
        if k == self.n_stages - 1:
            logits = self._logits_j(self._head_last, x)
            return ("exit_layer", seq, logits, row_on, versions)
        return ("layer", seq, x, positions, masks, model_len, write_idx,
                row_on, versions)

    def _stage_ctrl_msg(self, k: int, ctr, msg) -> None:
        _, _seq, commit_on, commit_len, imap, cvers = msg
        # ctrl liveness at processing time: only a retire bumps the ctrl
        # version (the lockstep `clear` mask), so a recycled slot's
        # trailing commits/prunes neutralise mid-flight while a missed
        # slot's keep propagating
        live = cvers == self._ctrl_versions
        commit_on = commit_on & live
        imap = np.where(live[:, None], imap, self._identity_imap)
        if not commit_on.any() and np.array_equal(imap,
                                                  self._identity_imap):
            ctr["ctrl_skipped"] += 1     # fully neutralised: the no-op
            return
        self._kv[k], self._tkv[k] = self._ctrl_j(
            self._kv[k], self._tkv[k], commit_on,
            np.where(live, commit_len, 0), imap)
        ctr["ctrl_applied"] += 1

    def _stage_prefill(self, k: int, msg):
        _, seq, slot, x, handle = msg
        x = jax.device_put(x, self._devices[k])
        self._kv[k], x = self._prefill_j(self._sp[k], self._sv[k],
                                         self._kv[k], x, np.int32(slot))
        if k == self.n_stages - 1:
            return ("exit_prefill", seq,
                    self._logits_j(self._head_last, x[:, -1]), handle)
        return ("prefill", seq, slot, x, handle)

    def _draft_loop(self) -> None:
        try:
            while True:
                job = self._aget(self._draft_q)
                kind = job[0]
                if kind == "stop":
                    return
                if kind == "verify":
                    self._draft_verify_job(job)
                elif kind == "commit":
                    _, ml, mask = job
                    node0 = jnp.zeros((self.slots,), jnp.int32)
                    self._d_cache = self.draft.commit_rows(
                        self._d_cache, self._d_tree, node0, ml, mask)
                elif kind == "remap":
                    _, imaps = job
                    self._d_tree = _remap_rows_jit(
                        self._d_tree, jnp.asarray(imaps, jnp.int32))
                elif kind == "remap_row":
                    _, slot, imap = job
                    d_row = remap_tree_caches(
                        tf.slice_cache_rows(self._d_tree, slot, 1), imap,
                        self.capacity)
                    self._d_tree = tf.update_cache_rows(self._d_tree,
                                                        d_row, slot)
                elif kind == "prefill":
                    _, slot, prompt = job
                    d_view = tf.slice_cache_rows(self._d_cache, slot, 1)
                    _, d_row = self.draft.prefill(prompt, d_view)
                    self._d_cache = tf.update_cache_rows(self._d_cache,
                                                         d_row, slot)
                self._draft_done += 1
        except _Abort:
            pass
        except BaseException:
            self._errors.append(("draft", traceback.format_exc()))
            self._failed.set()

    def _draft_verify_job(self, job) -> None:
        _, tokens, positions, masks, model_len, write_idx, row_on, box \
            = job
        nb = self._bucket(int(np.max(np.nonzero(row_on)[0])) + 1)
        sl = lambda a: a[:nb]
        d_all, self._d_tree = self.draft.tree_verify_rows(
            sl(tokens), sl(positions), sl(masks), self._d_cache,
            sl(model_len), self._d_tree, sl(write_idx), bucket=nb)
        self._count("verify_rows")
        self._draft_verified += 1
        lead = self._draft_verified - self._exit_layers_consumed
        self._max_draft_lead = max(self._max_draft_lead, lead)
        box._value = d_all
        box._event.set()

    def _submit_draft(self, job) -> None:
        self._ensure_started()
        self._draft_q.put(job)
        self._draft_pushed += 1

    # -- PipelineExecutor seam ------------------------------------------
    @_traced
    def prefill(self, slot: int, prompt):
        """Admission prefill through the pipe (it has no prefill lane):
        the prompt is embedded on stage 0's device and rides the stages
        as ONE message — FIFO-ordered after the retired occupant's stale
        messages and before the new occupant's first entry — each stage
        writing the slot's rows with its own layers; blocks until the
        last stage returns the prompt's logits.  The draft prefill is a
        job on the draft actor, in the same engine push order."""
        x = tf._embed_inputs({"embed": self._embed_p}, self.target.cfg,
                             jnp.asarray(prompt), self.target.prefix_embeds)
        handle = DeferredPrefill(int(slot))
        self._push(("prefill", self._next_seq(), int(slot), x, handle))
        self._submit_draft(("prefill", int(slot),
                            np.asarray(prompt)))
        while not handle.ready:
            self._pump()
        return handle.resolve()

    @_traced
    def tick_rows(self, tokens, positions, masks, model_len, write_idx,
                  row_on):
        """One engine timestep: push the queued ctrl message (if any),
        then the entering layer message + the draft verify job.  Returns
        ``(d_all, handles)`` like the overlapped backend — ``handles``
        are blocking ``DeferredLogits``, ``d_all`` a lazy draft-verify
        future (``None`` when nothing enters).  Empty timesteps push
        NOTHING: the async pipe has no dead ticks to pay."""
        self._ensure_started()
        self._check_errors()
        self._pump_ready()
        row_on_np = np.asarray(row_on).astype(bool).copy()
        if self._ctrl_active:
            self._push(("ctrl", self._next_seq(),
                        self._ctrl_commit.copy(), self._ctrl_len.copy(),
                        self._ctrl_imap.copy(),
                        self._ctrl_versions.copy()))
            self._count("ctrl_msgs")
            self._reset_ctrl()
        handles = {}
        d_all = None
        if row_on_np.any():
            vers = self._versions.copy()
            for slot in np.nonzero(row_on_np)[0]:
                h = _AsyncDeferredLogits(int(slot), int(vers[slot]), self)
                self._handles[int(slot)].append(h)
                handles[int(slot)] = h
            tok = np.asarray(tokens, np.int32).copy()
            pos = np.asarray(positions, np.int32).copy()
            msk = np.asarray(masks, bool).copy()
            ml = np.asarray(model_len, np.int32).copy()
            wi = np.asarray(write_idx, np.int32).copy()
            self._push(("layer", self._next_seq(), tok, pos, msk, ml, wi,
                        row_on_np, vers))
            self._count("entry_msgs")
            d_all = _DraftVerifyResult(self)
            self._submit_draft(("verify", tok, pos, msk, ml, wi,
                                row_on_np, d_all))
        self._count("pipeline_tick")
        return d_all, handles

    @_traced
    def verify_rows(self, tokens, positions, masks, model_len, write_idx,
                    row_on):
        """Standard seam, async semantics: (handles, d_all) with
        blocking deferred futures."""
        d_all, handles = self.tick_rows(tokens, positions, masks,
                                        model_len, write_idx, row_on)
        return handles, d_all

    @_traced
    def commit_rows(self, model_len, commit_mask) -> None:
        """Queue the target-side exit commit into the next ctrl message
        (it must trail the in-flight layers stage by stage); the draft
        commit is a job on the draft actor in the same push order."""
        mask = np.asarray(commit_mask).copy()
        ml = np.asarray(model_len).astype(np.int32)
        self._ctrl_commit |= mask
        self._ctrl_len = np.where(mask, ml, self._ctrl_len)
        if mask.any():
            self._ctrl_active = True
        self._submit_draft(("commit", ml.copy(), mask))
        self._count("commit_rows")

    def remap_row(self, slot: int, index_map) -> None:
        imap = np.asarray(index_map, np.int32)
        self._ctrl_imap[slot] = imap
        self._ctrl_active = True
        self._submit_draft(("remap_row", int(slot), imap.copy()))

    @_traced
    def remap_rows(self, index_maps, row_mask) -> None:
        rm = np.asarray(row_mask)
        if not rm.any():
            return
        imaps = np.asarray(index_maps, np.int32)
        self._ctrl_imap = np.where(rm[:, None], imaps, self._ctrl_imap)
        self._ctrl_active = True
        self._submit_draft(("remap", imaps.copy()))
        self._count("remap_rows")

    def kill(self, slot: int, *, drop_ctrl: bool = False) -> None:
        """Invalidate the slot's in-flight layers WHEREVER they sit:
        bumping the version makes every stage's next liveness check
        suppress the stale rows immediately — the short-circuit the
        lockstep ring can only apply one tick at a time.  Outstanding
        futures die; ``drop_ctrl=True`` (retire) additionally cancels
        the slot's queued ctrl and neutralises its in-flight ctrl
        messages via the ctrl-version bump (a miss keeps them — its
        earlier commits must finish propagating)."""
        self._versions[slot] += 1
        for h in self._handles[slot]:
            h.dead = True
        self._handles[slot].clear()
        if drop_ctrl:
            self._ctrl_commit[slot] = False
            self._ctrl_len[slot] = 0
            self._ctrl_imap[slot] = self._identity_imap[slot]
            self._ctrl_versions[slot] += 1
        self._count("kill")

    def drain(self) -> int:
        """Block until every pushed message has come out the far end and
        the draft actor's job queue is empty (bounded, error-raising).
        Leaves the pipe idle and every future resolved."""
        if not self._started:
            return 0
        n = 0
        while self._consumed < self._pushed:
            self._pump()
            n += 1
        deadline = time.monotonic() + self.timeout_s
        while self._draft_done < self._draft_pushed:
            self._check_errors()
            if time.monotonic() > deadline:
                raise AsyncExecutorError(
                    f"timed out after {self.timeout_s}s draining the "
                    f"draft actor")
            time.sleep(0.002)
        if any(self._handles):
            raise AsyncExecutorError(
                "drained pipe left unresolved flights — exit/handle "
                "bookkeeping out of sync")
        self._count("drain")
        return n

    def shutdown(self) -> None:
        """Drain the pipe, stop the actors and join their threads
        (idempotent; a later use restarts the actors lazily).  After a
        failure the drain is skipped and the threads are released via
        the shared abort event."""
        if not self._started:
            return
        self._gate.set()
        if not self._errors:
            try:
                self.drain()
            except AsyncExecutorError:
                pass
        stop = ("stop", self._next_seq())
        for q in (self._inboxes[0], self._draft_q):
            try:
                q.put(stop, timeout=1.0)
            except queue.Full:
                self._failed.set()
        deadline = time.monotonic() + min(self.timeout_s, 30.0)
        while not self._failed.is_set():
            try:
                msg = self._exit_q.get(timeout=0.1)
            except queue.Empty:
                if self._errors or time.monotonic() > deadline:
                    break
                continue
            if msg[0] == "stop":
                break
            self._consume_exit(msg)
        self._failed.set()               # release any blocked actor
        for t in self._threads:
            t.join(timeout=10.0)
        alive = [t.name for t in self._threads if t.is_alive()]
        self._threads = []
        self._started = False
        self._failed = threading.Event()
        if alive:
            raise AsyncExecutorError(
                f"actor threads failed to join: {alive}")

    # -- introspection ---------------------------------------------------
    def draft_lead(self) -> int:
        """How many verify jobs the disaggregated draft has completed
        ahead of the target exits the engine has consumed — the
        speculation run-ahead depth."""
        return self._draft_verified - self._exit_layers_consumed

    def counters(self) -> dict:
        """Snapshot of the per-stage actor counters (msgs processed,
        layer steps, stale rows suppressed, ctrl applied/skipped, busy
        and idle seconds, max inbox depth) plus the draft-lead gauges
        and message totals — what the async demo prints."""
        return {
            "stages": [dict(c) for c in self.stage_counters],
            "draft_lead": self.draft_lead(),
            "max_draft_lead": self._max_draft_lead,
            "pushed": self._pushed,
            "consumed": self._consumed,
        }

    def _draft_cache(self):
        return self._d_cache

    def _draft_tree(self):
        return self._d_tree
