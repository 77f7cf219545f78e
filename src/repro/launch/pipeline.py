"""Paper-faithful pipeline-parallel deployment (shard_map over "model").

The paper's cluster runs MPMD stages coordinated by Redis; on TPU the same
schedule is SPMD: every device executes one *tick* per timestep.

One PipeDec tick (= paper timestep, Fig. 2):
  * stage 0 ingests the newest tree layer (from the draft model); every
    other stage keeps the in-flight layer its ring slot holds;
  * each stage first applies the *control* message that reached it this
    tick (exit-commit + prune compaction — the paper's pruning-propagation
    stage, see below), then applies its layer block to the tree layer it
    holds, reading/writing its local slice of the two-level KV cache;
  * the activation leaving the last stage is gathered and unembedded into
    the verification logits of the layer that completed the pipeline;
  * activations + metadata rotate one stage forward via
    ``jax.lax.ppermute`` — this collective IS the paper's transmission
    scheduler (Appendix A), compiled instead of orchestrated.

A layer entering at timestep t therefore exits at ``t + n_stages - 1`` —
the same pipeline-fill latency the logical engine's ``Flight.exit_t``
books, so one tick per timestep IS the engine schedule, compiled.

Each in-flight layer carries its metadata (absolute positions, ancestor
mask rows, tree-buffer write index, committed length, and a per-slot tree
**version** counter) in the same ring so every stage uses the values
frozen at that layer's entry — exactly the paper's data-flow semantics.

SpecPipe-DB rides the same ring *batched*: every ring/entry leaf and every
stage cache carries a leading slot axis (``batch`` = KV slots), so one tick
moves EVERY in-flight request's tree layer one stage forward.

The per-stage math itself (layer application, ctrl commit+compact, chunk
prefill) is factored into ``make_stage_fns`` so it has exactly ONE
definition: the lockstep tick below composes those functions inside a
``shard_map`` body, and the free-running async executor
(``serving.executor.AsyncPipelineExecutor``) jits the *same* functions
per stage actor — which is how the async schedule stays bit-identical to
the lockstep references by construction.

Two lockstep executor schedules drive this tick (``serving.executor``);
a third (async) backend replaces the tick with free-running per-stage
actors over the same stage functions:

  * **flush** (``ShardedPipelineExecutor`` via ``make_pipeline_verify``):
    each global timestep pushes the batched entry layer through all
    ``n_stages`` hops inside ONE compiled dispatch, so verify logits are
    available at the *entry* timestep and buffered by the engine until
    exit.  Bit-exact by construction; prices at ``n_stages`` hops per
    timestep (``core.sim.specpipe_db_sharded_* flush=True``).
  * **overlapped** (``OverlappedShardedExecutor``): the ring persists
    across timesteps and stays *full* — ONE tick per global timestep, the
    paper's steady-state wall-clock regime (``flush=False`` pricing).
    Verify logits only exist at the layer's *exit* timestep, so the
    engine's ``Flight``s resolve deferred-logit futures, and correctness
    under pruning needs the in-ring mechanisms this module compiles:

      - **gated ctrl channel** (pruning propagation): the exit decision
        at timestep t (commit length + old→new prune ``index_map``)
        enters the ring at t+1 and reaches stage k at tick t+1+k —
        exactly after stage k processed every pre-prune in-flight layer
        (stage k runs layer j at tick j+k) and exactly before it
        processes the first post-prune layer.  Each stage applies
        commit-then-compact to its local cache slice on arrival, so
        pre-prune layers always read pre-prune rows and post-prune layers
        always read compacted rows — the in-flight schedule computes
        bit-identical logits to the flush.  The channel is *gated*: an
        ``active`` predicate enters with the message and rides the ring
        beside it (``c_active``, one bool per stage slot), and each
        stage's commit-scatter + prune-gather is wrapped in
        ``jax.lax.cond`` on its local predicate — the all-identity /
        no-commit message that rides most ticks costs a predicate check
        instead of a full scatter+gather per stage.  The executor only
        raises the predicate on timesteps where exit ctrl was actually
        queued, and an inactive message is by construction the identity,
        so gating is bit-exact.
      - **kill + version** (miss / retire invalidation): a ``kill [B]``
        input invalidates every in-flight layer of a pruned-to-miss or
        retired slot wherever it is in the ring (stale layers stop
        writing their stage tree-cache rows and exit with
        ``valid=False``); the per-slot ``version`` counter rides with
        each layer and is returned at exit so the executor can prove a
        resolved future belongs to the slot's *current* tree.
      - **prefill-in-ring** (overlapped admission): with
        ``prefill_cap > 0`` the ring carries a second lane
        (``p_act [S, B, Pcap, d]`` + per-slot ``p_len``/``p_on``/
        ``p_off``) for admission prefills.  A joining request's padded
        prompt *chunk* enters at stage 0 as a special layer kind the
        same tick the in-flight tree layers advance; each stage applies
        its layers in *chunk* (prefill) mode to the lane — gated by
        ``jax.lax.cond`` on "any prefill at this stage", so the empty
        lane that rides most ticks is free — writing the slot's
        model-cache rows [p_off, p_off + Pcap) stage by stage.  The
        chunk's last-position hidden state exits ``n_stages - 1`` ticks
        later (``p_last``/``p_valid``; the lane never touches the tree
        exit, so the prefill is a *dead exit* there), and admitting a
        request no longer costs the ring a separate dispatch or an idle
        timestep.  Prompts longer than ``prefill_cap`` stream through
        the lane over several consecutive ticks (*chunked prefill*):
        the executor feeds chunk c at tick t+c with its row offset
        ``p_off = c * Pcap``, so stage k sees chunk c at tick t+c+k —
        strictly after it wrote chunk c-1's rows — and each chunk
        attends over every earlier chunk's cached rows, which makes the
        cached K/V bit-identical to a one-shot prefill (row projections
        are row-independent; see ``attention.attn_prefill_chunk``).
        Pad rows beyond ``p_len`` are causally masked at positions <
        len and only ever overwrite model rows that the growing
        ``model_len`` (or the next chunk) overwrites again before
        reading — outputs stay bit-identical to the separate-dispatch
        prefill.

Supports attention-family architectures (dense / VLM / MoE-with-attention);
recurrent families use chain-mode speculative decoding instead (DESIGN.md
§Arch-applicability).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.models import attention as attn_mod
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.models.layers import embed, rmsnorm, unembed


@dataclasses.dataclass(frozen=True)
class PipelineConfig:
    """Static shape of the pipelined deployment: stage count, tree layer
    width w (rows per ring entry), tree KV capacity and model KV
    length.
    """
    n_stages: int
    width: int            # w (tree layer width)
    tree_capacity: int    # tree KV buffer rows
    max_len: int          # model KV buffer rows


def stage_layout(cfg: ModelConfig, n_stages: int) -> Tuple[int, int]:
    """(layers_per_stage, padded_total). Only the uniform 'stack' region is
    pipelined; prefix/tail layers (rare) fold into stage 0 / S-1 ... we
    require a pure-stack arch for the pipeline deployment."""
    n_prefix, reps, tail = tf.layout(cfg)
    assert n_prefix == 0 and not tail, \
        "pipeline deployment expects a uniform layer stack"
    lps = -(-reps // n_stages)
    return lps, lps * n_stages


def stage_params(cfg: ModelConfig, params, n_stages: int):
    """Stage layout: a LIST of ``lps`` per-layer trees, each leaf [S, ...]
    (stage dim stacked/sharded over 'model'; the within-stage layer dim is
    unrolled into separate buffers so XLA cannot hoist whole-stack
    converts/copies ahead of the layer loop — §Perf H3) + validity [S, Lps].
    """
    lps, padded = stage_layout(cfg, n_stages)
    reps = tf.layout(cfg)[1]
    valid = (jnp.arange(padded) < reps).reshape(n_stages, lps)
    if lps == 1 and padded == reps:
        # one layer per stage: the stack already IS the stage layout, and
        # a stack placed by ``init_stage_placed`` stays where it is
        return [params["stack"]], valid

    def reshape(x):
        pad = padded - reps
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((pad, *x.shape[1:]), x.dtype)], 0)
        return x.reshape(n_stages, lps, *x.shape[1:])

    stacked = jax.tree.map(reshape, params["stack"])
    layers = [jax.tree.map(lambda t: t[:, l], stacked) for l in range(lps)]
    return layers, valid


def make_stage_mesh(n_stages: int, devices=None):
    """The ("data"=1, "model"=n_stages) mesh of the one-stage-per-device
    deployment.  Its axes are Auto: the pipeline programs leave sharding
    propagation to the compiler, and JAX's default explicit axes would
    type every intermediate with a sharding those programs never name."""
    auto = (jax.sharding.AxisType.Auto,) * 2
    return jax.make_mesh((1, n_stages), ("data", "model"), axis_types=auto,
                         devices=devices)


def stage_devices(mesh):
    """The mesh's devices in stage order (stage k runs on entry k)."""
    return list(mesh.devices.reshape(-1))


def init_stage_placed(key, cfg: ModelConfig, mesh, dtype=jnp.float32):
    """Random-init params for the one-stage-per-device deployment, each
    piece created on the device that runs it: stage k's layers on stage
    device k (the stack is one array sharded over "model"), ``embed`` on
    the first stage's device, ``final_norm`` and ``lm_head`` on the
    last's.  No device ever holds the whole model.  Values match
    ``tf.init_model(key, cfg)`` up to rounding."""
    devs = stage_devices(mesh)
    n_prefix, reps, tail = tf.layout(cfg)
    if n_prefix or tail or reps % len(devs):
        raise ValueError("placed init needs a uniform layer stack that "
                         "divides evenly over the stages")
    lps = reps // len(devs)
    shards = [jax.jit(functools.partial(tf.init_stack, cfg=cfg, lo=k * lps,
                                        hi=(k + 1) * lps, dtype=dtype),
                      out_shardings=SingleDeviceSharding(d))(key)
              for k, d in enumerate(devs)]
    stacked = NamedSharding(mesh, P("model"))
    stack = jax.tree.map(
        lambda *xs: jax.make_array_from_single_device_arrays(
            (reps, *xs[0].shape[1:]), stacked, list(xs)), *shards)

    def head(names, dev):
        return jax.jit(lambda k: {n: v for n, v in
                                  tf.init_model(k, cfg, dtype).items()
                                  if n in names},
                       out_shardings=SingleDeviceSharding(dev))(key)

    return {"stack": stack, **head(("embed",), devs[0]),
            **head(("final_norm", "lm_head"), devs[-1])}


def head_params(params, cfg: ModelConfig):
    """The leaves ``tf._logits`` reads: final norm + unembedding table."""
    table = "embed" if cfg.tie_embeddings else "lm_head"
    return {"final_norm": params["final_norm"], table: params[table]}


def prefill_slot(stage_prefill, stage_p, valid_row, kv, x, slot):
    """One stage's admission prefill of ONE slot: the prompt activations
    ``x`` [1, L, d] run through this stage's layers in chunk mode from
    offset 0, writing row ``slot`` of its [slots, rows, ...] caches."""
    row = [jax.tree.map(
        lambda t: jax.lax.dynamic_slice_in_dim(t, slot, 1, 0), c)
        for c in kv]
    row, x = stage_prefill(stage_p, valid_row, row, x,
                           jnp.ones((1,), bool), jnp.zeros((1,), jnp.int32))
    kv = [jax.tree.map(
        lambda t, r: jax.lax.dynamic_update_slice_in_dim(
            t, r.astype(t.dtype), slot, 0), c, nr)
        for c, nr in zip(kv, row)]
    return kv, x


def init_stage_caches(cfg: ModelConfig, pcfg: PipelineConfig,
                      dtype=jnp.float32, batch: int = 1):
    """Per-stage model + tree caches: lists (per in-stage layer) of
    [S, B, rows, ...] buffers.  ``batch`` is the KV-slot axis mirroring
    the slot-stacked ``serving.scheduler.KVArena`` (B=1 = the
    single-request deployment)."""
    lps, _ = stage_layout(cfg, pcfg.n_stages)
    kv = attn_mod.init_kv_cache(cfg, batch, pcfg.max_len, dtype)
    tkv = attn_mod.init_kv_cache(cfg, batch, pcfg.tree_capacity + pcfg.width,
                                 dtype)
    tile = lambda c: [jax.tree.map(
        lambda x: jnp.zeros((pcfg.n_stages, *x.shape), x.dtype), c)
        for _ in range(lps)]
    return tile(kv), tile(tkv)


def init_ring(cfg: ModelConfig, pcfg: PipelineConfig, dtype=jnp.float32,
              batch: int = 1, ctrl: bool = False, prefill_cap: int = 0):
    """In-flight activation + metadata ring, one slot per stage.  Every
    leaf carries the KV-slot axis ``batch`` right after the stage dim —
    a batched tick moves every slot's layer one stage forward together.

    ``ctrl=True`` (the overlapped executor) adds the pruning-propagation
    channel: per stage-slot exit-commit mask/length and an old→new prune
    ``index_map`` that each stage applies to its local cache slice the
    tick the message reaches it (identity maps are the no-op, so the
    channel is always well-formed), plus the per-stage ``c_active``
    gating predicate that rides beside the message (False = the message
    is the identity and the stage skips the whole application).

    ``prefill_cap > 0`` adds the prefill lane (overlapped admission):
    per-stage padded prompt-chunk activations ``p_act`` with their
    ``p_len``/``p_on``/``p_off`` metadata (``p_off`` is the chunk's
    absolute row offset — the per-slot chunk cursor of chunked
    prefill), advancing one stage per tick like the tree layers."""
    s, w = pcfg.n_stages, pcfg.width
    ring = {
        "act": jnp.zeros((s, batch, w, cfg.d_model), dtype),
        "positions": jnp.zeros((s, batch, w), jnp.int32),
        "mask": jnp.zeros((s, batch, w, pcfg.tree_capacity + pcfg.width),
                          bool),
        "write_idx": jnp.zeros((s, batch), jnp.int32),
        "model_len": jnp.zeros((s, batch), jnp.int32),
        "valid": jnp.zeros((s, batch), bool),
        "version": jnp.zeros((s, batch), jnp.int32),
    }
    if ctrl:
        ring["c_commit"] = jnp.zeros((s, batch), bool)
        ring["c_len"] = jnp.zeros((s, batch), jnp.int32)
        ring["c_imap"] = jnp.broadcast_to(
            jnp.arange(pcfg.tree_capacity, dtype=jnp.int32),
            (s, batch, pcfg.tree_capacity))
        ring["c_active"] = jnp.zeros((s,), bool)
    if prefill_cap:
        ring["p_act"] = jnp.zeros((s, batch, prefill_cap, cfg.d_model),
                                  dtype)
        ring["p_len"] = jnp.zeros((s, batch), jnp.int32)
        ring["p_on"] = jnp.zeros((s, batch), bool)
        ring["p_off"] = jnp.zeros((s, batch), jnp.int32)
    return ring


def make_stage_fns(cfg: ModelConfig, pcfg: PipelineConfig):
    """The per-stage compute, defined ONCE for every pipeline schedule.

    Returns ``(stage_apply, stage_ctrl, stage_prefill)``:

      * ``stage_apply(stage_p, valid_row, kv, tkv, x, positions, mask,
        write_idx, model_len, in_valid) -> (x_out, new_tkv)`` — apply one
        stage's layer block to its in-flight batched tree layer
        ([B, w, d]; per-row metadata frozen at that layer's ring entry).
        Invalid rows (``in_valid`` or a padded ``valid_row`` layer) pass
        activations through untouched and leave the tree cache unwritten.
      * ``stage_ctrl(kv, tkv, commit_on, commit_len, index_map) ->
        (kv, tkv)`` — the pruning-propagation message applied to one
        stage's local cache slice: exit-commit tree row 0 into the model
        cache, then compact the tree rows through the old→new
        ``index_map`` (identity map + ``commit_on=False`` is the no-op).
      * ``stage_prefill(stage_p, valid_row, kv, x, on, off) ->
        (new_kv, x_out)`` — one stage's layers in chunk (prefill) mode
        over a padded prompt lane [B, Pcap, d], writing participating
        slots' model-cache rows [off, off + Pcap).

    The lockstep ``make_pipedec_tick`` composes these inside its
    ``shard_map`` body; ``serving.executor.AsyncPipelineExecutor`` jits
    the very same functions once per free-running stage actor.  One
    definition of the math is what makes the two schedules bit-identical
    on greedy workloads — they differ only in WHEN each stage runs, not
    in what it computes.
    """
    kinds = tf.unit_kinds(cfg)
    assert kinds == ("attn",), "pipeline stages support attention stacks"
    lps, _ = stage_layout(cfg, pcfg.n_stages)

    def stage_apply(stage_p, valid_row, kv, tkv, x, positions, mask,
                    write_idx, model_len, in_valid):
        """Apply this stage's layers to its in-flight batched tree layer
        ([B, w, d] activations; per-row metadata rides with the layer)."""
        ctx = tf.Ctx(mode="tree", positions=positions,
                     cache_len=jnp.asarray(model_len, jnp.int32),
                     tree_write_index=jnp.asarray(write_idx, jnp.int32),
                     tree_mask=mask)
        xs = x  # [B, w, d]
        new_tkv = []
        for l in range(lps):
            # per-layer param/cache buffers (lists over the in-stage dim)
            unit_p = stage_p[l]
            c = [kv[l]]
            tc = [tkv[l]]
            y, _, ntc, _ = tf._apply_unit(unit_p, cfg, kinds, xs, c, tc, ctx)
            ok = valid_row[l] & in_valid                 # [B]
            xs = jnp.where(ok[:, None, None], y, xs)
            new_tkv.append(jax.tree.map(
                lambda old, new, k=ok: jnp.where(
                    k.reshape((-1,) + (1,) * (old.ndim - 1)), new, old),
                tc[0], ntc[0]))
        return xs, new_tkv

    def stage_ctrl(kv, tkv, commit_on, commit_len, index_map):
        """Commit-then-compact one stage's local caches (the pruning
        propagation message; identity map + no commit is the no-op)."""
        node0 = jnp.zeros_like(commit_len)
        kv = [tf.commit_tree_nodes(cfg, kv[l], tkv[l], node0, commit_len,
                                   commit_on)
              for l in range(lps)]
        tkv = [tf.remap_tree_cache_rows(tkv[l], index_map)
               for l in range(lps)]
        return kv, tkv

    def stage_prefill(stage_p, valid_row, kv, x, on, off):
        """Apply this stage's layers in CHUNK (prefill) mode over the
        padded prompt lane ([B, Pcap, d]), writing each participating
        slot's model-cache rows [off[b], off[b] + Pcap) — the same
        per-layer math ``tf.prefill_chunk`` runs, partitioned stage by
        stage.  A whole prompt that fits the lane is the off == 0
        single-chunk case."""
        cap = x.shape[1]
        off = jnp.asarray(off, jnp.int32)
        positions = off[:, None] + jnp.arange(cap, dtype=jnp.int32)[None]
        ctx = tf.Ctx(mode="chunk", positions=positions, cache_len=off)
        xs = x
        new_kv = []
        for l in range(lps):
            y, nc, _, _ = tf._apply_unit(stage_p[l], cfg, kinds, xs,
                                         [kv[l]], None, ctx)
            ok = valid_row[l] & on                       # [B]
            xs = jnp.where(ok[:, None, None], y, xs)
            new_kv.append(jax.tree.map(
                lambda old, new, k=ok: jnp.where(
                    k.reshape((-1,) + (1,) * (old.ndim - 1)),
                    new.astype(old.dtype), old),
                kv[l], nc[0]))
        return new_kv, xs

    return stage_apply, stage_ctrl, stage_prefill


def make_pipedec_tick(cfg: ModelConfig, pcfg: PipelineConfig, mesh,
                      prefill_cap: int = 0):
    """Build the jittable one-timestep LOCKSTEP pipeline tick
    (slot-batched): one ``shard_map`` dispatch advances every stage in
    unison.  The per-stage math comes from ``make_stage_fns``; the async
    executor runs those same functions free-running instead of calling
    this tick.

    Inputs (global shapes; ``B`` = KV slots, B=1 = single-request):
      stage_p:    unit params [S, Lps, ...]        (stage-sharded)
      stage_valid:[S, Lps] bool
      caches:     (model_kv, tree_kv) [S, B, rows, ...] per in-stage layer
      ring:       see init_ring (every leaf [S, B, ...])
      entry:      dict with the NEW layer for stage 0:
                  tokens->embedded x [B, w, d], positions [B, w],
                  mask [B, w, tcap+w], write_idx [B], model_len [B],
                  valid [B], version [B]
      kill:       [B] bool or None — invalidate every in-flight layer of
                  these slots (miss / retire: the pruning-propagation
                  kill; the entry ingested THIS tick is never killed)
      ctrl:       None, or {"commit" [B] bool, "commit_len" [B] i32,
                  "index_map" [B, cap] i32, "clear" [B] bool,
                  "active" [] bool} — the exit decision of the previous
                  timestep, entering at stage 0 and applied by each stage
                  (commit row 0 → model cache, then compact the tree
                  rows) the tick it arrives, BEFORE that stage's layer
                  compute.  Identity index_map + commit False is the
                  per-slot no-op; ``active`` is the *gate*: it rides the
                  ring beside the message (``c_active``) and each stage
                  wraps the whole commit-scatter + prune-gather in
                  ``jax.lax.cond`` on it, so an inactive (all-identity)
                  message costs a predicate check instead of a
                  scatter+gather per stage.  The caller must only raise
                  ``active`` when the message is not the identity.
                  ``clear`` neutralises the slot's ctrl messages still
                  RIDING the ring (retire: the slot is being recycled,
                  and a retired occupant's in-flight commits/prunes must
                  never reach the next occupant's freshly prefilled
                  caches); a miss must NOT clear — the missed request's
                  earlier commits stay valid and must finish propagating.
      pentry:     (only when ``prefill_cap > 0``) {"act" [B, Pcap, d],
                  "len" [B] i32, "on" [B] bool, "off" [B] i32} —
                  admission prefill *chunks* entering the prefill lane
                  at stage 0 (``off`` = the chunk's absolute row
                  offset; 0 for a whole prompt that fits the lane).
                  Each stage applies its layers in chunk (prefill) mode
                  to the lane the tick it holds it — under
                  ``jax.lax.cond`` on "any prefill at this stage", so
                  the empty lane is free — writing the slot's
                  model-cache rows [off, off + Pcap).  Chunks of one
                  slot must be fed on consecutive ticks in order; each
                  chunk's queries attend over the rows every earlier
                  chunk already wrote at this stage.  The chunk's
                  last-position hidden state is returned at exit
                  (``p_last [B, d]``, ``p_valid [B]``); the tree-layer
                  exit for those slots stays dead.

    Stage 0 ingests the entry THIS tick (and processes it this tick), so
    an entry at tick t exits at tick ``t + n_stages - 1`` — the engine's
    ``Flight.exit_t``.  Returns (new model_kv, new tree_kv, new ring,
    exit: {act [B, w, d], valid [B], version [B](, p_last, p_valid)}).
    """
    s_axis = "model"
    n_stages = pcfg.n_stages
    stage_apply, stage_ctrl, stage_prefill = make_stage_fns(cfg, pcfg)

    def tick(stage_p, stage_valid, model_kv, tree_kv, ring, entry,
             kill=None, ctrl=None, pentry=None):
        assert (pentry is not None) == bool(prefill_cap), \
            "pass pentry iff the tick was built with prefill_cap > 0"

        def body(stage_p, stage_valid, model_kv, tree_kv, ring, entry,
                 kill, ctrl, pentry):
            # local slices carry a leading stage dim of 1 (dropped below)
            sp = [jax.tree.map(lambda t: t[0], lp) for lp in stage_p]
            sv = stage_valid[0]
            kv = [jax.tree.map(lambda t: t[0], lc) for lc in model_kv]
            tkv = [jax.tree.map(lambda t: t[0], lc) for lc in tree_kv]

            idx = jax.lax.axis_index(s_axis)
            is0 = (idx == 0)

            # 1. kill: invalidate the in-flight layers of pruned/retired
            # slots wherever they are in the ring — they stop writing and
            # exit dead (their tree version is stale)
            valid_r = ring["valid"]
            if kill is not None:
                valid_r = valid_r & ~kill[None]

            # 2. ingest: stage 0 adopts the new layer (+ the ctrl message
            # entering behind the in-flight layers); every other stage
            # works on the layer its ring slot holds
            pick = lambda e, r: jnp.where(is0, e[None], r)
            cur = {
                "act": pick(entry["act"], ring["act"]),
                "positions": pick(entry["positions"], ring["positions"]),
                "mask": pick(entry["mask"], ring["mask"]),
                "write_idx": pick(entry["write_idx"], ring["write_idx"]),
                "model_len": pick(entry["model_len"], ring["model_len"]),
                "valid": pick(entry["valid"], valid_r),
                "version": pick(entry["version"], ring["version"]),
            }
            if ctrl is not None:
                # retire-clear: neutralise the slot's ctrl wherever it is
                # in the ring (a recycled slot's old occupant may still
                # have commit/remap messages trailing its killed layers)
                clr = ctrl["clear"]
                cap_i = ctrl["index_map"].shape[-1]
                ring_commit = ring["c_commit"] & ~clr[None]
                ring_len = jnp.where(clr[None], 0, ring["c_len"])
                ring_imap = jnp.where(
                    clr[None, :, None],
                    jnp.arange(cap_i, dtype=jnp.int32)[None, None],
                    ring["c_imap"])
                cur["c_commit"] = pick(ctrl["commit"], ring_commit)
                cur["c_len"] = pick(ctrl["commit_len"], ring_len)
                cur["c_imap"] = pick(ctrl["index_map"], ring_imap)
                cur["c_active"] = jnp.where(
                    is0, jnp.reshape(ctrl["active"], (1,)),
                    ring["c_active"])

                # 3. pruning propagation: apply the ctrl that reached this
                # stage — commit first (tree row 0 is still the exiting
                # root), then compact this stage's tree rows.  The message
                # trails every pre-prune in-flight layer and leads every
                # post-prune one, so each stage flips its local caches at
                # exactly the schedule point the flush executor does
                # centrally.  Gated: the whole commit-scatter +
                # prune-gather runs under ``lax.cond`` on the message's
                # ``c_active`` flag — the all-identity message that rides
                # most ticks costs one predicate check per stage.
                def apply_ctrl(ops):
                    kv_, tkv_ = ops
                    return stage_ctrl(kv_, tkv_, cur["c_commit"][0],
                                      cur["c_len"][0], cur["c_imap"][0])

                kv, tkv = jax.lax.cond(cur["c_active"][0], apply_ctrl,
                                       lambda ops: ops, (kv, tkv))

            # 3b. prefill lane: a joining slot's padded prompt advances
            # one stage per tick beside the tree layers; the stage
            # applies its layers in full mode (writing the slot's
            # model-cache rows) only when a prefill actually sits here —
            # the empty lane costs one any() per tick.
            p_x = None
            if prefill_cap:
                p_on_r = ring["p_on"]
                if kill is not None:
                    p_on_r = p_on_r & ~kill[None]
                cur["p_act"] = pick(pentry["act"], ring["p_act"])
                cur["p_len"] = pick(pentry["len"], ring["p_len"])
                cur["p_on"] = pick(pentry["on"], p_on_r)
                cur["p_off"] = pick(pentry["off"], ring["p_off"])
                pon = cur["p_on"][0]
                kv, p_x = jax.lax.cond(
                    jnp.any(pon),
                    lambda kv_, px: stage_prefill(sp, sv, kv_, px, pon,
                                                  cur["p_off"][0]),
                    lambda kv_, px: (kv_, px),
                    kv, cur["p_act"][0])

            # 4. compute: this stage's layers over the layer it holds
            x, new_tkv = stage_apply(
                sp, sv, kv, tkv, cur["act"][0], cur["positions"][0],
                cur["mask"][0], cur["write_idx"][0], cur["model_len"][0],
                cur["valid"][0])

            # 5. exit: the layer the last stage just finished
            is_last = (idx == n_stages - 1)
            fl = is_last.astype(x.dtype)
            exit_act = jax.lax.psum(x * fl, s_axis)
            exit_valid = jax.lax.psum(
                (cur["valid"][0] & is_last).astype(jnp.int32), s_axis) > 0
            exit_version = jax.lax.psum(
                cur["version"][0] * is_last.astype(jnp.int32), s_axis)
            exit_out = {"act": exit_act, "valid": exit_valid,
                        "version": exit_version}
            if prefill_cap:
                # the prefill lane's exit: the prompt's last-position
                # hidden state after every stage's layers (the tree exit
                # above stays dead for joining slots)
                last = jnp.clip(cur["p_len"][0] - 1, 0, prefill_cap - 1)
                x_last = jnp.take_along_axis(
                    p_x, last[:, None, None], axis=1)[:, 0]      # [B, d]
                exit_out["p_last"] = jax.lax.psum(
                    x_last * is_last.astype(x_last.dtype), s_axis)
                exit_out["p_valid"] = jax.lax.psum(
                    (pon & is_last).astype(jnp.int32), s_axis) > 0

            # 6. rotate one stage forward (paper's transmission step);
            # stage 0's slot empties (refilled by the next ingest)
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            shift = lambda v: jax.lax.ppermute(v, s_axis, perm)
            # rotate the POST-compute activations; the stale pre-compute
            # acts must not ride (nor cost a dead collective)
            new_ring = {k: shift(v) for k, v in cur.items()
                        if k not in ("act", "p_act")}
            new_ring["act"] = shift(x[None])
            if prefill_cap:
                new_ring["p_act"] = shift(p_x[None])

            new_kv = [jax.tree.map(lambda t: t[None], lc) for lc in kv]
            new_tkv = [jax.tree.map(lambda t: t[None], lc) for lc in new_tkv]
            return (new_kv, new_tkv, new_ring, exit_out)

        kv_spec = jax.tree.map(lambda _: P(s_axis), model_kv)
        tkv_spec = jax.tree.map(lambda _: P(s_axis), tree_kv)
        ring_spec = jax.tree.map(lambda _: P(s_axis), ring)
        entry_spec = jax.tree.map(lambda _: P(), entry)
        kill_spec = None if kill is None else P()
        ctrl_spec = None if ctrl is None else jax.tree.map(
            lambda _: P(), ctrl)
        pentry_spec = None if pentry is None else jax.tree.map(
            lambda _: P(), pentry)
        exit_spec = {"act": P(), "valid": P(), "version": P()}
        if prefill_cap:
            exit_spec["p_last"] = P()
            exit_spec["p_valid"] = P()
        out = shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(s_axis), stage_p),
                      P(s_axis), kv_spec, tkv_spec, ring_spec, entry_spec,
                      kill_spec, ctrl_spec, pentry_spec),
            out_specs=(kv_spec, tkv_spec, ring_spec, exit_spec),
            check_vma=False,
        )(stage_p, stage_valid, model_kv, tree_kv, ring, entry, kill, ctrl,
          pentry)
        return out

    return tick


def make_pipeline_verify(cfg: ModelConfig, pcfg: PipelineConfig, mesh,
                         dtype=jnp.float32):
    """One-dispatch batched tree-verify through the sharded pipeline (the
    FLUSH executor schedule).

    Ingests a batched entry layer into stage 0 of a fresh ring, then runs
    exactly ``n_stages`` ticks so the layer traverses every stage and
    exits — yielding the same verification hidden states the
    single-device ``tree_verify_step`` computes, but partitioned
    stage-by-stage over the mesh with the metadata riding the ``ppermute``
    ring.  The whole flush is ONE compiled computation, so the serving
    executor issues exactly one sharded dispatch per global timestep
    (``tests/test_pipeline.py`` pins the tick count: stage 0 ingests AND
    processes on the same tick, so ``n_stages`` hops suffice — no
    trailing dead-entry tick).

    The flush keeps verify logits available at the layer's *entry*
    timestep, which is what keeps the logical engine's schedule — and
    therefore its outputs — bit-identical to the local backends without
    any in-ring pruning machinery; the steady-state one-tick-per-timestep
    deployment is ``serving.executor.OverlappedShardedExecutor``.

    Returns ``verify(stage_p, stage_valid, model_kv, tree_kv, entry) ->
    (exit_act [B, w, d], exit_valid [B], new_tree_kv)``.
    """
    tick = make_pipedec_tick(cfg, pcfg, mesh)

    def verify(stage_p, stage_valid, model_kv, tree_kv, entry):
        batch = entry["act"].shape[0]
        ring = init_ring(cfg, pcfg, dtype=dtype, batch=batch)
        ent = dict(entry)
        ent.setdefault("version", jnp.zeros((batch,), jnp.int32))
        dead = dict(ent, valid=jnp.zeros_like(ent["valid"]))
        exit_out = None
        for _ in range(pcfg.n_stages):
            model_kv, tree_kv, ring, exit_out = tick(
                stage_p, stage_valid, model_kv, tree_kv, ring, ent)
            ent = dead
        return exit_out["act"], exit_out["valid"], tree_kv

    return verify


def make_pipeline_prefill(cfg: ModelConfig, pcfg: PipelineConfig, mesh):
    """Separate-dispatch admission prefill through the sharded stages (the
    flush executor's ``prefill``): ONE compiled dispatch carries one
    slot's embedded prompt [1, L, d] from stage to stage over the
    ``ppermute`` ring, each stage writing its own cache rows, so no device
    ever needs another stage's layers.

    Returns ``prefill(stage_p, stage_valid, model_kv, x, slot) ->
    (new model_kv, hidden [1, L, d] after the last stage)``.
    """
    s_axis = "model"
    n_stages = pcfg.n_stages
    _, _, stage_prefill = make_stage_fns(cfg, pcfg)
    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def prefill(stage_p, stage_valid, model_kv, x, slot):
        def body(stage_p, stage_valid, model_kv, x, slot):
            sp = [jax.tree.map(lambda t: t[0], lp) for lp in stage_p]
            sv = stage_valid[0]
            kv = [jax.tree.map(lambda t: t[0], lc) for lc in model_kv]
            idx = jax.lax.axis_index(s_axis)
            for hop in range(n_stages):
                # only the stage holding the prompt this hop computes
                kv, x = jax.lax.cond(
                    idx == hop,
                    lambda kv_, x_: prefill_slot(stage_prefill, sp, sv, kv_,
                                                 x_, slot),
                    lambda kv_, x_: (kv_, x_), kv, x)
                if hop < n_stages - 1:
                    x = jax.lax.ppermute(x, s_axis, perm)
            last = (idx == n_stages - 1).astype(x.dtype)
            return ([jax.tree.map(lambda t: t[None], lc) for lc in kv],
                    jax.lax.psum(x * last, s_axis))

        kv_spec = jax.tree.map(lambda _: P(s_axis), model_kv)
        return shard_map(
            body, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(s_axis), stage_p), P(s_axis),
                      kv_spec, P(), P()),
            out_specs=(kv_spec, P()), check_vma=False,
        )(stage_p, stage_valid, model_kv, x, slot)

    return prefill
