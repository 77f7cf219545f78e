"""Batched tree expansion: every slot's tree grows in ONE program.

``TreeBatch.expand_rows`` (``core.dynbatch.expand_rows``) vmaps the same
``tree_lib.expand_from_draft`` that the per-request
``PipeDecEngine.maybe_expand`` runs, so over a stacked ``TreeBatch`` it
must give the per-slot result bit for bit: fresh candidates for rows that
entered, candidates carried by a slot the depth or capacity cap deferred,
invalid (-1) rows, node indices out of order and indices remapped by a
prune.  In the serving engine the program runs at most once a timestep
on every fused backend, and the live ``expanded`` / ``expand_deferred``
counters agree with the per-request tallies of the looped reference.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dynbatch
from repro.core import tree as tree_lib
from repro.core.dynbatch import TreeBatch
from repro.core.pipedec import (DecodeState, PipeDecConfig, PipeDecEngine,
                                remap_flight_indices)
from repro.core.speculative import ModelBundle
from repro.models import transformer as tf
from repro.serving import (AsyncPipelineExecutor, OverlappedShardedExecutor,
                           Request, ShardedPipelineExecutor, SpecPipeDBEngine)

W, C, DEPTH_CAP = 4, 2, 3
PCFG = PipeDecConfig(n_stages=3, width=W, branch=C, max_depth=DEPTH_CAP)
CAP = PCFG.capacity
VOCAB = 128
MAX_LEN = 128


@pytest.fixture(scope="module")
def bundles(tiny_dense, tiny_draft):
    tp = tf.init_model(jax.random.PRNGKey(0), tiny_dense)
    dp = tf.init_model(jax.random.PRNGKey(9), tiny_draft)
    return ModelBundle(tp, tiny_dense), ModelBundle(dp, tiny_draft)


# --------------------------------------------------------------------------
# batched expand == per-slot maybe_expand, bit for bit
# --------------------------------------------------------------------------
def _grow(rng, depth, root=5):
    """A tree of ``depth`` full random layers."""
    t = tree_lib.tree_init(CAP, root)
    for _ in range(depth):
        lp = jnp.asarray(-rng.random((W, C)), jnp.float32)
        tok = jnp.asarray(rng.integers(0, VOCAB, (W, C)), jnp.int32)
        t = tree_lib.tree_expand(t, tok, lp, W)
    return t


def _entry_idx(t):
    _, idx, valid, _ = tree_lib.last_layer(t, W)
    return np.where(np.asarray(valid), np.asarray(idx), -1).astype(np.int32)


def _prune_first_child(t, nidx):
    child = int(np.asarray(tree_lib.root_argmax_child(t)))
    t, imap = tree_lib.tree_prune_to_child(t, child)
    return t, remap_flight_indices(nidx, imap)


def _case(kind, rng):
    """(tree, draft logits [W, V], entry node indices, entered now?, the
    prune between the two timesteps or None) for one slot."""
    logits = rng.normal(size=(W, VOCAB)).astype(np.float32)
    if kind == "entered":
        t = _grow(rng, 1)
        return t, logits, _entry_idx(t), True, None
    if kind == "depth_cap":
        t = _grow(rng, DEPTH_CAP)
        return t, logits, _entry_idx(t), True, None
    if kind == "capacity_cap":
        t = _grow(rng, 1)
        t = t._replace(n_nodes=jnp.asarray(CAP + 1 - W, jnp.int32))
        return t, logits, _entry_idx(t), True, None
    if kind == "invalid_rows":
        t = _grow(rng, 1)
        nidx = _entry_idx(t)
        nidx[1] = nidx[3] = -1
        return t, logits, nidx, True, None
    if kind == "out_of_order":
        t = _grow(rng, 2)
        perm = np.array([2, 0, 3, 1])
        return t, logits[perm], _entry_idx(t)[perm], True, None
    if kind == "pruned":
        # deferred at the depth cap, then a prune (a hit) frees a layer:
        # it grows next timestep from carried candidates, remapped indices
        t = _grow(rng, DEPTH_CAP)
        return t, logits, _entry_idx(t), True, _prune_first_child
    if kind == "idle":
        # no draft held: never due, its candidate rows stay untouched
        return _grow(rng, 1), logits, _entry_idx(_grow(rng, 1)), False, None
    raise ValueError(kind)


CASES = {
    "entered": ["entered", "entered"],
    "depth_cap": ["depth_cap", "entered"],
    "capacity_cap": ["entered", "capacity_cap"],
    "invalid_rows": ["invalid_rows", "idle"],
    "out_of_order": ["out_of_order", "entered"],
    "pruned": ["pruned", "entered"],
    "mixed": ["entered", "depth_cap", "capacity_cap", "invalid_rows",
              "out_of_order", "pruned", "idle", "entered"],
}


def _assert_tree_equal(got, want, what):
    for name in tree_lib.Tree._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, name)),
                                      np.asarray(getattr(want, name)),
                                      err_msg=f"{what}: {name}")


@pytest.mark.parametrize("kinds", list(CASES.values()), ids=list(CASES))
def test_batched_expand_bitmatches_maybe_expand(bundles, kinds):
    eng = PipeDecEngine(*bundles, PCFG)
    rng = np.random.default_rng(len(kinds))
    cases = [_case(k, rng) for k in kinds]
    slots = len(cases)

    states = []
    for t, logits, nidx, entered, _ in cases:
        st = DecodeState(committed=[0], tree=t, t_cache=None, d_cache=None,
                         t_tree=None, d_tree=None, model_len=0,
                         key=jax.random.PRNGKey(0), max_new_tokens=4,
                         limit=8, pending=not entered)
        if entered:
            st.last_draft = (nidx.copy(), jnp.asarray(logits))
        states.append(st)
    tb = TreeBatch(slots, CAP)
    for slot, st in enumerate(states):
        tb.adopt_row(slot, st.tree)

    def timestep(d_all, row_on, tag):
        due = np.array([eng.wants_expand(st) for st in states])
        nidx = np.full((slots, W), -1, np.int32)
        for slot, st in enumerate(states):
            if due[slot]:
                nidx[slot] = st.last_draft[0]
        grown = tb.expand_rows(d_all, row_on, nidx, due, w=W, c=C,
                               depth_cap=DEPTH_CAP)
        for slot, st in enumerate(states):
            held = st.last_draft is not None
            eng.maybe_expand(st)
            assert bool(grown[slot]) == (held and st.last_draft is None), \
                (tag, slot)
            _assert_tree_equal(tb.get_row(slot), st.tree,
                               f"{tag} slot {slot} ({kinds[slot]})")
        return grown

    # timestep 1: the rows that entered take fresh candidates
    row_on = np.array([entered for *_, entered, _ in cases])
    d_all = jnp.asarray(np.stack([c[1] for c in cases]))
    grown = timestep(d_all, row_on, "fresh")
    expect = {"entered": True, "depth_cap": False, "capacity_cap": False,
              "invalid_rows": True, "out_of_order": True, "pruned": False,
              "idle": False}
    assert [bool(g) for g in grown] == [expect[k] for k in kinds]

    # between the timesteps: prune the slots that ask for it (tree and
    # the held entry indices, as ``exit_apply`` does on a hit)
    for slot, (*_, prune) in enumerate(cases):
        if prune is not None:
            st = states[slot]
            st.tree, nidx = prune(st.tree, st.last_draft[0])
            st.last_draft = (nidx, st.last_draft[1])
            tb.set_row(slot, st.tree)
            assert (nidx == -1).any() and (nidx >= 0).any()

    # timestep 2: nothing enters; deferred slots retry from the carried
    # candidates
    grown = timestep(None, np.zeros((slots,), bool), "carried")
    assert [bool(g) for g in grown] == [k == "pruned" for k in kinds]


# --------------------------------------------------------------------------
# serving engine: one expand program a timestep, live counters
# --------------------------------------------------------------------------
# a depth cap below the fill latency: a tree reaches it before its root's
# layer exits, so the cap defers expansions at every timestep it binds
SERVE = PipeDecConfig(n_stages=3, width=W, branch=C, max_depth=2)
SERVE1 = PipeDecConfig(n_stages=1, width=W, branch=C)


def _executor(name, bundles, slots, pcfg):
    if name == "local":
        return None
    cls = {"sharded": ShardedPipelineExecutor,
           "overlapped": OverlappedShardedExecutor,
           "async": AsyncPipelineExecutor}[name]
    # one CPU device: the lockstep meshes take one stage, the async
    # backend round-robins its stage actors over it
    return cls(*bundles, slots=slots, max_len=MAX_LEN,
               tree_capacity=pcfg.tree_buffer_capacity,
               capacity=pcfg.capacity,
               n_stages=pcfg.n_stages if name == "async" else 1)


def _requests(slots):
    rng = np.random.default_rng(slots)
    return [Request(i, rng.integers(0, 100, size=int(rng.integers(3, 8)))
                    .astype(np.int32), int(rng.integers(4, 8)),
                    arrival_t=int(i // slots))
            for i in range(slots + 2)]


@pytest.mark.parametrize("slots", [2, 4])
@pytest.mark.parametrize("backend",
                         ["local", "sharded", "overlapped", "async"])
def test_one_expand_program_per_timestep_and_live_counters(
        bundles, monkeypatch, backend, slots):
    pcfg = SERVE1 if backend == "overlapped" else SERVE
    reqs = _requests(slots)

    looped = SpecPipeDBEngine(*bundles, pcfg, max_len=MAX_LEN,
                              max_slots=slots, fused=False)
    for r in reqs:
        looped.submit(r)
    want = looped.run()

    launches = []
    real = dynbatch._expand_rows_jit

    def counted(*args, **kwargs):
        launches.append(eng.stats.timesteps)
        return real(*args, **kwargs)

    monkeypatch.setattr(dynbatch, "_expand_rows_jit", counted)
    ex = _executor(backend, bundles, slots, pcfg)
    eng = SpecPipeDBEngine(*bundles, pcfg, max_len=MAX_LEN, max_slots=slots,
                           executor=ex)
    monkeypatch.setattr(eng.inner, "maybe_expand", None)   # never per slot
    for r in reqs:
        eng.submit(r)
    try:
        got = eng.run()
    finally:
        if backend == "async":
            ex.shutdown()

    st = eng.stats
    # one program at most a timestep, and one every timestep with an entry
    assert len(launches) == len(set(launches))
    entered = {t + 1 for t, n in enumerate(st.verify_dispatches) if n}
    assert entered <= set(launches)
    # live counters == per-request tallies == the looped reference's
    per_req = st.per_request.values()
    assert st.expanded == sum(g.expanded for g in per_req) > 0
    assert st.expand_deferred == sum(g.expand_deferred for g in per_req)
    ref = looped.stats
    assert st.expanded == ref.expanded
    assert st.expanded + st.expand_deferred == \
        sum(g.expanded + g.expand_deferred for g in ref.per_request.values())
    for uid, res in want.items():
        np.testing.assert_array_equal(got[uid].tokens, res.tokens)
        mine, theirs = got[uid].stats, res.stats
        assert (mine.expanded, mine.expand_deferred) == \
            (theirs.expanded, theirs.expand_deferred), uid
    if pcfg.depth_cap < pcfg.n_stages:
        assert st.expand_deferred > 0, "the depth cap deferred some slot"

