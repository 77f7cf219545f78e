"""PipeDec phase-level pins: pipeline-fill latency, expansion capacity
guard, flight-index dtype stability, and the batched per-row commit.

These pin the invariants the fused SpecPipe-DB dispatch relies on — the
DB engine drives the same gather-entry / apply-fused / exit-commit phases,
so a drift here silently changes the shared pipeline schedule.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tree as tree_lib
from repro.core.pipedec import (PipeDecConfig, PipeDecEngine,
                                remap_flight_indices)
from repro.core.speculative import ModelBundle, draft_candidates
from repro.models import transformer as tf

PCFG = PipeDecConfig(n_stages=3, width=4, branch=2)


@pytest.fixture(scope="module")
def bundles(tiny_dense, tiny_draft):
    tp = tf.init_model(jax.random.PRNGKey(0), tiny_dense)
    dp = tf.init_model(jax.random.PRNGKey(9), tiny_draft)
    return ModelBundle(tp, tiny_dense), ModelBundle(dp, tiny_draft)


# --------------------------------------------------------------------------
# entry→exit latency (the module docstring's schedule contract)
# --------------------------------------------------------------------------
@pytest.mark.parametrize("stages", [1, 2, 4])
def test_pipeline_fill_latency(bundles, stages):
    """A layer entering at timestep t exits at t + n_stages - 1 (the entry
    timestep itself is stage 1), so the first post-prefill commit lands at
    local timestep n_stages exactly — pinned so the schedule can't drift."""
    target, draft = bundles
    eng = PipeDecEngine(target, draft,
                        PipeDecConfig(n_stages=stages, width=4, branch=2))
    st = eng.init_state(np.array([1, 5, 9], np.int32), 8)
    eng.step(st)
    if stages > 1:  # with 1 stage the entry exits within its own timestep
        assert len(st.flights) == 1
        assert st.flights[0].exit_t == 1 + stages - 1  # Flight contract
    while st.stats.commits == 0:
        eng.step(st)
    assert st.t == stages, "first commit == pipeline-fill latency"


# --------------------------------------------------------------------------
# expansion capacity guard (off-by-one regression)
# --------------------------------------------------------------------------
def test_tree_expand_truncates_at_capacity():
    """At ``n_nodes + w == cap + 1`` a full-width layer no longer fits:
    ``tree_expand`` silently clamps the lowest-ranked candidate — the
    behaviour the engine guard must defer around, never admit."""
    w, c = 2, 2
    pcfg = PipeDecConfig(n_stages=2, width=w, branch=c, max_depth=6)
    cap = pcfg.capacity
    t = tree_lib.tree_init(cap, 7)
    rng = np.random.default_rng(0)
    for _ in range(2):
        logits = jnp.asarray(rng.normal(size=(w, 32)), jnp.float32)
        tok, lp = draft_candidates(logits, jnp.ones((w,), bool), c)
        t = tree_lib.tree_expand(t, tok, lp, w)
    assert int(t.layer_size) == w  # full deepest layer to expand from

    # saturation: pretend the packed prefix holds cap + 1 - w nodes
    t_sat = t._replace(n_nodes=jnp.asarray(cap + 1 - w, jnp.int32))
    logits = jnp.asarray(rng.normal(size=(w, 32)), jnp.float32)
    tok, lp = draft_candidates(logits, jnp.ones((w,), bool), c)
    grown = tree_lib.tree_expand(t_sat, tok, lp, w)
    assert int(grown.layer_size) == w - 1, \
        "layer silently truncated at the buffer edge"


def test_expansion_guard_defers_at_saturation():
    """The engine guard admits a layer only when all ``w`` slots fit:
    ``n_nodes + w <= cap`` expands, ``n_nodes + w == cap + 1`` defers
    (the old ``<= cap + 1`` guard admitted the truncating expand above)."""
    w = 2
    pcfg = PipeDecConfig(n_stages=2, width=w, branch=2, max_depth=6)
    cap = pcfg.capacity
    can_expand = lambda t: bool(tree_lib.can_grow(t, w, pcfg.depth_cap))
    tree = tree_lib.tree_init(cap, 3)

    ok = tree._replace(n_nodes=jnp.asarray(cap - w, jnp.int32))
    assert can_expand(ok)
    exact = tree._replace(n_nodes=jnp.asarray(cap + 1 - w, jnp.int32))
    assert not can_expand(exact), "off-by-one: truncating expand admitted"
    full = tree._replace(n_nodes=jnp.asarray(cap, jnp.int32))
    assert not can_expand(full)


def test_deep_tree_small_capacity_stays_lossless(bundles):
    """Capacity-saturation end-to-end: a deep narrow tree (width 2, depth
    cap 8 ⇒ capacity 17) with a perfect draft drives n_nodes against the
    buffer edge; output must still match plain autoregressive decode."""
    from repro.core.baselines import generate_autoregressive
    target, _ = bundles
    prompt = np.array([3, 3, 8], np.int32)
    ar = generate_autoregressive(target, prompt, 12)
    eng = PipeDecEngine(target, target,
                        PipeDecConfig(n_stages=2, width=2, branch=2,
                                      max_depth=8))
    out, stats = eng.generate(prompt, 12)
    assert np.array_equal(ar, out)
    assert stats.commits >= 12


# --------------------------------------------------------------------------
# flight-index dtype stability
# --------------------------------------------------------------------------
def test_remap_flight_indices_int32():
    node_idx = np.array([0, 3, -1, 7], np.int32)
    imap = jnp.asarray([0, -1, 1, 2, -1, -1, -1, 3], jnp.int32)
    out = remap_flight_indices(node_idx, imap)
    assert out.dtype == np.int32
    np.testing.assert_array_equal(out, [0, 2, -1, 3])
    # second prune cycle keeps the dtype stable (was int64 before)
    out2 = remap_flight_indices(out, imap)
    assert out2.dtype == np.int32


def test_flight_indices_stay_int32_through_engine(bundles):
    """Every in-flight node-index buffer stays int32 across hit/prune
    cycles of a real decode."""
    target, _ = bundles
    eng = PipeDecEngine(target, target, PCFG)  # self-draft => hits/prunes
    st = eng.init_state(np.array([2, 7, 1], np.int32), 10)
    while not st.done:
        eng.step(st)
        for fl in st.flights:
            assert fl.node_idx.dtype == np.int32
        if st.last_draft is not None:
            assert st.last_draft[0].dtype == np.int32
    assert st.stats.hits > 0, "prune cycles actually exercised"


# --------------------------------------------------------------------------
# batched per-row commit == per-row loop of the scalar commit
# --------------------------------------------------------------------------
def test_commit_tree_nodes_matches_scalar_commit(tiny_dense):
    cfg = tiny_dense
    rows, max_len, tcap = 3, 16, 8
    key = jax.random.PRNGKey(4)

    def randomize(tree, salt):
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        ks = jax.random.split(jax.random.fold_in(key, salt), len(leaves))
        return jax.tree_util.tree_unflatten(
            treedef, [jax.random.normal(k, l.shape, l.dtype)
                      for k, l in zip(ks, leaves)])

    cache = randomize(tf.init_cache(cfg, rows, max_len), 0)
    tcache = randomize(tf.init_tree_caches(cfg, rows, tcap), 1)
    node_idx = jnp.asarray([2, 0, 5], jnp.int32)
    model_len = jnp.asarray([4, 9, 1], jnp.int32)
    mask = jnp.asarray([True, False, True])

    got = tf.commit_tree_nodes(cfg, cache, tcache, node_idx, model_len,
                               mask)
    for r in range(rows):
        row_c = tf.slice_cache_rows(cache, r, 1)
        row_t = tf.slice_cache_rows(tcache, r, 1)
        if bool(mask[r]):
            want = tf.commit_tree_node(cfg, row_c, row_t,
                                       int(node_idx[r]), int(model_len[r]))
        else:
            want = row_c  # masked rows bit-unchanged
        got_row = tf.slice_cache_rows(got, r, 1)
        for (pw, lw), (pg, lg) in zip(
                jax.tree_util.tree_leaves_with_path(want),
                jax.tree_util.tree_leaves_with_path(got_row)):
            assert pw == pg
            np.testing.assert_array_equal(np.asarray(lw), np.asarray(lg),
                                          err_msg=f"row {r} {pw}")
