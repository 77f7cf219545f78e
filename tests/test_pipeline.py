"""shard_map pipeline tick: lowering + numerical equivalence vs the
single-device tree-verify step (1-stage CPU mesh).  The ring and stage
caches are slot-batched (leading B axis) since the executor-layer PR —
B=1 here is the single-request deployment.

Since the overlapped-execution PR the tick is ingest-first: stage 0
adopts AND processes the entry on the same tick, so an entry at tick t
exits at tick ``t + n_stages - 1`` (the engine's ``Flight.exit_t``) and
``make_pipeline_verify`` needs exactly ``n_stages`` ticks — both pinned
here.  The tick also carries the overlapped schedule's pruning-
propagation inputs (per-slot tree ``version`` metadata, a ``kill`` mask,
and the in-ring commit/remap ctrl channel); the ctrl application is
pinned bit-identical to the central ``commit_tree_nodes`` +
``remap_tree_cache_rows`` path the flush executor uses.  Multi-stage
in-flight behaviour (stale layers behind a kill) runs on a REAL 8-device
mesh via ``repro.launch.sharded_check`` (see tests/test_sharded_check.py).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import pipeline as pl
from repro.models import transformer as tf
from repro.models.layers import embed


def _setup(cfg, n_stages=1):
    params = tf.init_model(jax.random.PRNGKey(0), cfg)
    mesh = pl.make_stage_mesh(n_stages)
    pcfg = pl.PipelineConfig(n_stages=n_stages, width=4, tree_capacity=16,
                             max_len=32)
    sp, valid = pl.stage_params(cfg, params, n_stages)
    return params, mesh, pcfg, sp, valid


def _reference(cfg, params, pcfg):
    """Prefill, then one reference tree-verify of a root layer."""
    cache = tf.init_cache(cfg, 1, 32)
    prompt = jnp.asarray([[5, 3, 2, 7]], jnp.int32)
    logits0, cache = tf.prefill(params, cfg, prompt, cache)
    root = jnp.argmax(logits0, -1)  # [1]

    tcaps = tf.init_tree_caches(cfg, 1, pcfg.tree_capacity + pcfg.width)
    mask = np.zeros((4, pcfg.tree_capacity + pcfg.width), bool)
    mask[0, 0] = True
    tokens = jnp.zeros((1, 4), jnp.int32).at[0, 0].set(root[0])
    positions = jnp.asarray([[4, 0, 0, 0]], jnp.int32)
    ref_logits, _ = tf.tree_verify_step(params, cfg, tokens, positions,
                                        jnp.asarray(mask), cache, 4, tcaps, 0)
    return cache, tokens, positions, mask, ref_logits


def _stage_model_kv(cache):
    """Copy a prefilled (stacked) model cache into 1-stage layout
    ([S=1, B=1, rows, ...] per in-stage layer)."""
    stacked = cache["stack"][0]  # unit has one sublayer: {k,v} [reps,1,...]
    reps = len(jax.tree.leaves(stacked)[0])
    return [jax.tree.map(lambda t: t[l][None], stacked)
            for l in range(reps)]


def _entry(params, tokens, positions, mask, batch=1):
    cat = lambda a: jnp.concatenate([a] * batch, 0)
    return {
        "act": cat(embed(params["embed"], tokens)),
        "positions": cat(positions),
        "mask": cat(jnp.asarray(mask)[None]),
        "write_idx": jnp.zeros((batch,), jnp.int32),
        "model_len": jnp.full((batch,), 4, jnp.int32),
        "valid": jnp.ones((batch,), bool),
        "version": jnp.zeros((batch,), jnp.int32),
    }


def test_tick_matches_tree_verify(tiny_dense):
    """Ingest-first semantics: ONE tick ingests, processes AND exits the
    entry on a 1-stage mesh (entry at t exits at t + n_stages - 1)."""
    cfg = tiny_dense
    params, mesh, pcfg, sp, valid = _setup(cfg)
    _, tree_kv = pl.init_stage_caches(cfg, pcfg)
    ring = pl.init_ring(cfg, pcfg)
    tick = pl.make_pipedec_tick(cfg, pcfg, mesh)

    cache, tokens, positions, mask, ref_logits = _reference(cfg, params,
                                                            pcfg)
    model_kv = _stage_model_kv(cache)
    entry = _entry(params, tokens, positions, mask)
    with mesh:
        _, _, _, exit_out = jax.jit(tick)(sp, valid, model_kv, tree_kv,
                                          ring, entry)

    got = exit_out["act"]  # [1, w, d] final hidden of the exiting layer
    got_logits = tf._logits(params, cfg, got)[0]
    np.testing.assert_allclose(np.asarray(got_logits[0]),
                               np.asarray(ref_logits[0, 0]),
                               rtol=2e-4, atol=2e-4)
    assert bool(exit_out["valid"][0])
    assert int(exit_out["version"][0]) == 0


def test_tick_version_rides_to_exit(tiny_dense):
    """The per-slot tree version frozen at entry is returned at exit —
    the overlapped executor's proof that a resolved future belongs to the
    slot's current tree."""
    cfg = tiny_dense
    params, mesh, pcfg, sp, valid = _setup(cfg)
    _, tree_kv = pl.init_stage_caches(cfg, pcfg)
    ring = pl.init_ring(cfg, pcfg)
    tick = pl.make_pipedec_tick(cfg, pcfg, mesh)
    cache, tokens, positions, mask, _ = _reference(cfg, params, pcfg)
    model_kv = _stage_model_kv(cache)
    entry = dict(_entry(params, tokens, positions, mask),
                 version=jnp.full((1,), 7, jnp.int32))
    with mesh:
        _, _, _, exit_out = jax.jit(tick)(sp, valid, model_kv, tree_kv,
                                          ring, entry)
    assert bool(exit_out["valid"][0])
    assert int(exit_out["version"][0]) == 7


def test_pipeline_verify_flush_matches_tree_verify(tiny_dense):
    """``make_pipeline_verify`` (the sharded flush executor's
    one-dispatch schedule) reproduces the reference tree-verify logits,
    and invalid rows leave the tree caches bit-untouched."""
    cfg = tiny_dense
    params, mesh, pcfg, sp, valid = _setup(cfg)
    _, tree_kv = pl.init_stage_caches(cfg, pcfg, batch=2)
    verify = pl.make_pipeline_verify(cfg, pcfg, mesh)

    cache, tokens, positions, mask, ref_logits = _reference(cfg, params,
                                                            pcfg)
    model_kv1 = _stage_model_kv(cache)
    # batch 2: row 0 live, row 1 invalid (rides along fully masked)
    model_kv = [jax.tree.map(
        lambda t: jnp.concatenate([t, jnp.zeros_like(t)], axis=1), c)
        for c in model_kv1]
    entry = _entry(params, tokens, positions, mask, batch=2)
    entry["valid"] = jnp.asarray([True, False])
    with mesh:
        exit_act, exit_valid, new_tkv = jax.jit(verify)(
            sp, valid, model_kv, tree_kv, entry)

    got_logits = tf._logits(params, cfg, exit_act)
    np.testing.assert_allclose(np.asarray(got_logits[0, 0]),
                               np.asarray(ref_logits[0, 0]),
                               rtol=2e-4, atol=2e-4)
    assert bool(exit_valid[0]) and not bool(exit_valid[1])
    # the invalid row's tree-cache rows are bit-unchanged (zeros)
    for c_new, c_old in zip(new_tkv, tree_kv):
        jax.tree.map(lambda n, o: np.testing.assert_array_equal(
            np.asarray(n[:, 1]), np.asarray(o[:, 1])), c_new, c_old)
    # the live row DID write its layer into the tree cache
    wrote = any(
        bool(jnp.any(n[:, 0] != o[:, 0]))
        for c_new, c_old in zip(new_tkv, tree_kv)
        for n, o in zip(jax.tree.leaves(c_new), jax.tree.leaves(c_old)))
    assert wrote


def test_pipeline_verify_runs_exactly_n_stages_ticks(tiny_dense,
                                                     monkeypatch):
    """The flush dispatch is exactly ``n_stages`` hops — the old trailing
    dead-entry tick (ingest-after-process semantics) is gone."""
    cfg = tiny_dense
    counts = {"ticks": 0}
    real = pl.make_pipedec_tick

    def counting(*args, **kwargs):
        tick = real(*args, **kwargs)

        def wrapped(*a, **k):
            counts["ticks"] += 1
            return tick(*a, **k)

        return wrapped

    monkeypatch.setattr(pl, "make_pipedec_tick", counting)
    params, mesh, pcfg, sp, valid = _setup(cfg)
    _, tree_kv = pl.init_stage_caches(cfg, pcfg)
    verify = pl.make_pipeline_verify(cfg, pcfg, mesh)
    cache, tokens, positions, mask, _ = _reference(cfg, params, pcfg)
    model_kv = _stage_model_kv(cache)
    entry = _entry(params, tokens, positions, mask)
    with mesh:
        _, exit_valid, _ = verify(sp, valid, model_kv, tree_kv, entry)
    assert bool(exit_valid[0]), "the layer must complete within the flush"
    assert counts["ticks"] == pcfg.n_stages


def test_tick_ctrl_matches_central_commit_and_remap(tiny_dense):
    """In-ring pruning propagation == the flush executor's central path:
    a ctrl message (commit mask/length + prune index_map) applied by the
    tick produces bit-identical model/tree caches to
    ``commit_tree_nodes`` + ``remap_tree_cache_rows`` applied directly,
    and an identity ctrl is a bit-exact no-op."""
    cfg = tiny_dense
    params, mesh, pcfg, sp, valid = _setup(cfg)
    _, tree_kv = pl.init_stage_caches(cfg, pcfg)
    tick = pl.make_pipedec_tick(cfg, pcfg, mesh)
    cache, tokens, positions, mask, _ = _reference(cfg, params, pcfg)
    model_kv = _stage_model_kv(cache)
    ring = pl.init_ring(cfg, pcfg, ctrl=True)
    cap = pcfg.tree_capacity
    identity = jnp.arange(cap, dtype=jnp.int32)[None]
    no_ctrl = {"commit": jnp.zeros((1,), bool),
               "commit_len": jnp.zeros((1,), jnp.int32),
               "index_map": identity,
               "clear": jnp.zeros((1,), bool),
               "active": jnp.ones((), bool)}
    kill0 = jnp.zeros((1,), bool)
    entry = _entry(params, tokens, positions, mask)
    dead = dict(entry, valid=jnp.zeros((1,), bool))

    with mesh:
        # tick 1 writes the root layer's KV into tree row 0 (identity
        # ctrl riding along — with the gate OPEN — must be a bit-exact
        # no-op)
        model_kv0 = [jax.tree.map(lambda t: t.copy(), c) for c in model_kv]
        model_kv, tree_kv, ring, _ = jax.jit(tick)(
            sp, valid, model_kv, tree_kv, ring, entry, kill0, no_ctrl)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), model_kv, model_kv0)

        # a prune keeping old row 0 at new row 0 and dropping the rest,
        # plus a commit of row 0 at model_len=4
        imap = jnp.full((cap,), -1, jnp.int32).at[0].set(0)
        ctrl = {"commit": jnp.ones((1,), bool),
                "commit_len": jnp.full((1,), 4, jnp.int32),
                "index_map": imap[None],
                "clear": jnp.zeros((1,), bool),
                "active": jnp.ones((), bool)}
        got_kv, got_tkv, _, _ = jax.jit(tick)(
            sp, valid, model_kv, tree_kv, ring, dead, kill0, ctrl)

    node0 = jnp.zeros((1,), jnp.int32)
    want_kv = [tf.commit_tree_nodes(cfg, mkv, tkv, node0,
                                    jnp.full((1,), 4, jnp.int32),
                                    jnp.ones((1,), bool))
               for mkv, tkv in zip(model_kv, tree_kv)]
    want_tkv = [tf.remap_tree_cache_rows(c, imap[None]) for c in tree_kv]
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got_kv, want_kv)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got_tkv, want_tkv)


def test_tick_ctrl_gate_skips_inactive_message(tiny_dense):
    """The gated ctrl channel: with ``active=False`` the stage skips the
    commit-scatter + prune-gather entirely — even a (mis-addressed)
    non-identity message leaves every cache bit-untouched, proving the
    ``lax.cond`` short-circuits rather than applying an identity op."""
    cfg = tiny_dense
    params, mesh, pcfg, sp, valid = _setup(cfg)
    _, tree_kv = pl.init_stage_caches(cfg, pcfg)
    tick = pl.make_pipedec_tick(cfg, pcfg, mesh)
    cache, tokens, positions, mask, _ = _reference(cfg, params, pcfg)
    model_kv = _stage_model_kv(cache)
    ring = pl.init_ring(cfg, pcfg, ctrl=True)
    cap = pcfg.tree_capacity
    kill0 = jnp.zeros((1,), bool)
    entry = _entry(params, tokens, positions, mask)
    dead = dict(entry, valid=jnp.zeros((1,), bool))
    no_ctrl = {"commit": jnp.zeros((1,), bool),
               "commit_len": jnp.zeros((1,), jnp.int32),
               "index_map": jnp.arange(cap, dtype=jnp.int32)[None],
               "clear": jnp.zeros((1,), bool),
               "active": jnp.ones((), bool)}
    with mesh:
        model_kv, tree_kv, ring, _ = jax.jit(tick)(
            sp, valid, model_kv, tree_kv, ring, entry, kill0, no_ctrl)
        # a REAL commit+prune message, but with the gate closed
        imap = jnp.full((cap,), -1, jnp.int32).at[0].set(0)
        gated = {"commit": jnp.ones((1,), bool),
                 "commit_len": jnp.full((1,), 4, jnp.int32),
                 "index_map": imap[None],
                 "clear": jnp.zeros((1,), bool),
                 "active": jnp.zeros((), bool)}
        got_kv, got_tkv, _, _ = jax.jit(tick)(
            sp, valid, model_kv, tree_kv, ring, dead, kill0, gated)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got_kv, model_kv)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), got_tkv, tree_kv)


def test_tick_prefill_lane_matches_prefill(tiny_dense):
    """Prefill-in-ring: a prompt entering the tick's prefill lane exits
    with last-position logits and stage model-cache rows BIT-IDENTICAL
    to ``tf.prefill``, while off slots stay untouched and the tree exit
    stays dead for the joining slot."""
    cfg = tiny_dense
    params, mesh, pcfg, sp, valid = _setup(cfg)
    pcap = 8
    model_kv, tree_kv = pl.init_stage_caches(cfg, pcfg, batch=2)
    ring = pl.init_ring(cfg, pcfg, batch=2, ctrl=True, prefill_cap=pcap)
    tick = pl.make_pipedec_tick(cfg, pcfg, mesh, prefill_cap=pcap)

    prompt = np.asarray([5, 3, 2, 7, 11], np.int32)
    ptok = np.zeros((2, pcap), np.int32)
    ptok[0, :len(prompt)] = prompt
    pentry = {"act": embed(params["embed"], jnp.asarray(ptok)),
              "len": jnp.asarray([len(prompt), 0], jnp.int32),
              "on": jnp.asarray([True, False]),
              "off": jnp.zeros((2,), jnp.int32)}
    w = pcfg.width
    dead_entry = {
        "act": jnp.zeros((2, w, cfg.d_model)),
        "positions": jnp.zeros((2, w), jnp.int32),
        "mask": jnp.zeros((2, w, pcfg.tree_capacity + w), bool),
        "write_idx": jnp.zeros((2,), jnp.int32),
        "model_len": jnp.zeros((2,), jnp.int32),
        "valid": jnp.zeros((2,), bool),
        "version": jnp.zeros((2,), jnp.int32),
    }
    cap = pcfg.tree_capacity
    no_ctrl = {"commit": jnp.zeros((2,), bool),
               "commit_len": jnp.zeros((2,), jnp.int32),
               "index_map": jnp.broadcast_to(
                   jnp.arange(cap, dtype=jnp.int32), (2, cap)),
               "clear": jnp.zeros((2,), bool),
               "active": jnp.zeros((), bool)}
    kill0 = jnp.zeros((2,), bool)
    with mesh:
        model_kv, tree_kv, ring, ex = jax.jit(tick)(
            sp, valid, model_kv, tree_kv, ring, dead_entry, kill0,
            no_ctrl, pentry)

    assert bool(ex["p_valid"][0]) and not bool(ex["p_valid"][1])
    assert not bool(ex["valid"][0]), "tree exit stays dead while joining"
    got = tf._logits(params, cfg, ex["p_last"][0:1])
    ref_cache = tf.init_cache(cfg, 1, 32)
    ref_logits, ref_cache = tf.prefill(params, cfg,
                                       jnp.asarray(prompt)[None], ref_cache)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref_logits))
    # stage model-cache rows [0, len) of the joining slot == the scan
    # prefill's rows; the off slot's rows are bit-untouched (zeros)
    stacked = ref_cache["stack"][0]
    reps = len(jax.tree.leaves(stacked)[0])
    for l in range(reps):
        want = jax.tree.map(lambda t, l=l: np.asarray(t[l][0, :len(prompt)]),
                            stacked)
        got_rows = jax.tree.map(
            lambda t: np.asarray(t[0, 0, :len(prompt)]), model_kv[l])
        jax.tree.map(np.testing.assert_array_equal, got_rows, want)
        for leaf in jax.tree.leaves(
                jax.tree.map(lambda t: np.asarray(t[0, 1]), model_kv[l])):
            assert not leaf.any(), "off slot must stay untouched"


def test_remap_tree_cache_rows_matches_per_row_reference(tiny_dense):
    """The batched gather (``remap_rows`` seam) equals the per-slot
    ``core.speculative.remap_tree_caches`` loop, identity rows
    included."""
    from repro.core.speculative import remap_tree_caches

    cfg = tiny_dense
    cap, slack, slots = 11, 4, 3
    tkv = jax.tree.map(
        lambda t: jax.random.normal(jax.random.PRNGKey(1), t.shape),
        tf.init_tree_caches(cfg, slots, cap + slack))
    rng = np.random.default_rng(0)
    imaps = np.tile(np.arange(cap, dtype=np.int32), (slots, 1))
    # slot 0: a real prune (drop half the rows, compact the rest)
    keep = np.sort(rng.choice(cap, size=cap // 2, replace=False))
    imaps[0] = -1
    imaps[0][keep] = np.arange(len(keep))
    # slot 1: identity (untouched); slot 2: reversal
    imaps[2] = np.arange(cap, dtype=np.int32)[::-1]

    got = tf.remap_tree_cache_rows(tkv, jnp.asarray(imaps))
    for slot in range(slots):
        want_row = remap_tree_caches(
            tf.slice_cache_rows(tkv, slot, 1), jnp.asarray(imaps[slot]),
            cap)
        got_row = tf.slice_cache_rows(got, slot, 1)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), got_row, want_row)
    # the identity slot is bit-untouched
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a[1]), np.asarray(b[1])),
        tf.slice_cache_rows(got, 1, 1), tf.slice_cache_rows(tkv, 1, 1))


def test_pipeline_prefill_matches_prefill(tiny_dense):
    """The flush executor's separate-dispatch prefill crosses the stages
    (``make_pipeline_prefill``): the slot's stage cache rows and the
    last-position logits match ``tf.prefill`` to float rounding, and the
    other slot's rows stay untouched."""
    cfg = tiny_dense
    params, mesh, pcfg, sp, valid = _setup(cfg)
    model_kv, _ = pl.init_stage_caches(cfg, pcfg, batch=2)
    prefill = jax.jit(pl.make_pipeline_prefill(cfg, pcfg, mesh))
    prompt = np.asarray([5, 3, 2, 7, 11], np.int32)
    x = embed(params["embed"], jnp.asarray(prompt)[None])
    model_kv, hidden = prefill(sp, valid, model_kv, x, jnp.int32(1))

    ref_logits, ref_cache = tf.prefill(params, cfg, jnp.asarray(prompt)[None],
                                       tf.init_cache(cfg, 1, 32))
    got = tf._logits(params, cfg, hidden[:, -1])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref_logits),
                               rtol=1e-5, atol=1e-5)
    stacked = ref_cache["stack"][0]
    for l in range(len(model_kv)):
        want = jax.tree.map(lambda t, l=l: np.asarray(t[l][0]), stacked)
        got_rows = jax.tree.map(lambda t: np.asarray(t[0, 1]), model_kv[l])
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-5), got_rows, want)
        for leaf in jax.tree.leaves(model_kv[l]):
            assert not np.asarray(leaf)[0, 0].any(), "slot 0 untouched"


def test_init_stage_placed_matches_init_model(tiny_dense):
    """Placed init gives ``init_model``'s values (up to rounding), with the
    stack sharded over the stage mesh and the stage layout taken as is
    when each stage holds one layer."""
    cfg = dataclasses.replace(tiny_dense, num_layers=1)
    mesh = pl.make_stage_mesh(1)
    key = jax.random.PRNGKey(4)
    placed = pl.init_stage_placed(key, cfg, mesh)
    want = tf.init_model(key, cfg)
    assert set(placed) == set(want)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7), placed, want)
    layers, valid = pl.stage_params(cfg, placed, 1)
    assert layers[0] is placed["stack"] and bool(valid.all())
    for leaf in jax.tree.leaves(placed["stack"]):
        assert leaf.sharding.spec[0] == "model"
