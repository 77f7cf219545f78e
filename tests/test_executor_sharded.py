"""Executor-layer tests: SpecPipe-DB on pluggable compute backends.

The logical scheduler must produce bit-identical per-request outputs on
every backend — ``LocalFusedExecutor`` (PR-2's fused single-device path),
``ShardedPipelineExecutor`` (the paper's pipelined deployment, flush
schedule), ``OverlappedShardedExecutor`` (the steady-state schedule: ONE
ring tick per timestep, deferred exit logits, in-ring pruning
propagation), and the single-request ``PipeDecEngine`` — because the
executor seam changes *where and when* the batched verify logits
materialise, never *what* is computed.  The 8-stage acceptance pin runs
in a subprocess (``repro.launch.sharded_check --overlap``, in
``test_sharded_check.py``) so the forced host-device count never leaks
into this process; the in-process tests use
a 1-stage mesh, which exercises the same ring/psum/stage-masking, ctrl
and kill code paths (in-flight layers *behind* a prune need >1 stage and
are covered by the subprocess pin's pruning-propagation scenario).
"""
import jax
import numpy as np
import pytest

from repro.core.pipedec import PipeDecConfig, PipeDecEngine
from repro.core.speculative import ModelBundle
from repro.models import transformer as tf
from repro.serving import (AsyncExecutorError, AsyncPipelineExecutor,
                           OverlappedShardedExecutor, Request,
                           ShardedPipelineExecutor, SpecPipeDBEngine,
                           generate_with_executor)

PCFG = PipeDecConfig(n_stages=3, width=4, branch=2)
# the overlapped ring length equals pcfg.n_stages, and in-process tests
# only have a 1-device mesh — multi-stage overlap runs via subprocess
PCFG1 = PipeDecConfig(n_stages=1, width=4, branch=2)
MAX_LEN = 128


@pytest.fixture(scope="module")
def bundles(tiny_dense, tiny_draft):
    tp = tf.init_model(jax.random.PRNGKey(0), tiny_dense)
    dp = tf.init_model(jax.random.PRNGKey(9), tiny_draft)
    return ModelBundle(tp, tiny_dense), ModelBundle(dp, tiny_draft)


def _mk_reqs(seed, n, arrivals=None, max_new=None):
    rng = np.random.default_rng(seed)
    return [Request(i,
                    rng.integers(0, 100, size=int(rng.integers(3, 8)))
                    .astype(np.int32),
                    int(max_new[i]) if max_new else int(rng.integers(3, 7)),
                    arrival_t=int(arrivals[i]) if arrivals else 0)
            for i in range(n)]


def _sharded(bundles, slots, n_stages=1, cls=ShardedPipelineExecutor,
             pcfg=PCFG):
    target, draft = bundles
    return cls(
        target, draft, slots=slots, max_len=MAX_LEN,
        tree_capacity=pcfg.tree_buffer_capacity, capacity=pcfg.capacity,
        n_stages=n_stages)


def _overlapped(bundles, slots):
    return _sharded(bundles, slots, cls=OverlappedShardedExecutor,
                    pcfg=PCFG1)


def _async(bundles, slots, pcfg=PCFG):
    # the async backend round-robins stage actors over the available
    # devices, so a 3-stage actor chain runs fine on the 1-device test
    # process (unlike the lockstep mesh executors)
    return _sharded(bundles, slots, cls=AsyncPipelineExecutor,
                    n_stages=pcfg.n_stages, pcfg=pcfg)


def test_sharded_executor_bitmatches_local_and_single(bundles):
    """Staggered arrivals + slot churn on the sharded backend (1-stage
    mesh): per-uid outputs bit-match the local fused backend and the
    single-request engine."""
    target, draft = bundles
    reqs = _mk_reqs(3, 4, arrivals=[0, 1, 4, 6], max_new=[4, 5, 3, 4])
    single = PipeDecEngine(target, draft, PCFG, max_len=MAX_LEN)
    want = {r.uid: single.generate(r.prompt, r.max_new_tokens)[0]
            for r in reqs}

    outs = {}
    for name, ex in (("local", None), ("sharded", _sharded(bundles, 2))):
        eng = SpecPipeDBEngine(target, draft, PCFG, max_len=MAX_LEN,
                               max_slots=2, executor=ex)
        for r in reqs:
            eng.submit(r)
        outs[name] = eng.run()
    for uid, tokens in want.items():
        np.testing.assert_array_equal(outs["local"][uid].tokens, tokens,
                                      err_msg=f"local vs single uid={uid}")
        np.testing.assert_array_equal(outs["sharded"][uid].tokens, tokens,
                                      err_msg=f"sharded vs single uid={uid}")


def test_sharded_one_batched_tick_per_timestep(bundles):
    """The dispatch-count hook: every global timestep with pending entries
    issues exactly ONE sharded pipeline dispatch (and one local draft
    dispatch) — never one per slot."""
    target, draft = bundles
    reqs = _mk_reqs(4, 3, arrivals=[0, 0, 2], max_new=[4, 3, 4])
    ex = _sharded(bundles, 2)
    eng = SpecPipeDBEngine(target, draft, PCFG, max_len=MAX_LEN,
                           max_slots=2, executor=ex)
    before = {b: dict(b.calls) for b in (target, draft)}
    for r in reqs:
        eng.submit(r)
    eng.run()

    disp = eng.stats.verify_dispatches
    assert len(disp) == eng.stats.timesteps
    assert max(disp) == 1
    assert ex.calls["pipeline_verify"] == sum(disp)
    assert ex.calls["verify_rows"] == sum(disp)
    # draft rides the same fused dispatch cadence, replicated locally
    assert draft.calls["tree_verify_rows"] - \
        before[draft].get("tree_verify_rows", 0) == sum(disp)
    # neither model ever falls back to the per-slot looped dispatch
    for b in (target, draft):
        assert b.calls["tree_verify"] == before[b].get("tree_verify", 0)
    # the target's verify runs through the sharded ring, not its bundle
    assert target.calls["tree_verify_rows"] == \
        before[target].get("tree_verify_rows", 0)
    assert eng.stats.peak_occupancy == 2, "slots actually shared"


def test_generate_with_executor_b1_path(bundles):
    """The B=1 PipeDecEngine path runs against either executor and
    bit-matches the direct single-request engine."""
    target, draft = bundles
    prompt = np.asarray([5, 3, 2, 7, 11], np.int32)
    single = PipeDecEngine(target, draft, PCFG, max_len=MAX_LEN)
    want, want_stats = single.generate(prompt, 6)

    for ex in (None, _sharded(bundles, 1)):
        got, stats = generate_with_executor(target, draft, PCFG, prompt, 6,
                                            executor=ex, max_len=MAX_LEN)
        np.testing.assert_array_equal(got, want)
        assert stats.commits == want_stats.commits
        assert stats.acceptance == want_stats.acceptance


def test_executor_slot_count_must_match(bundles):
    target, draft = bundles
    with pytest.raises(AssertionError, match="slot count"):
        SpecPipeDBEngine(target, draft, PCFG, max_len=MAX_LEN, max_slots=3,
                         executor=_sharded(bundles, 2))


def test_overlapped_bitmatches_flush_and_single(bundles):
    """Staggered arrivals + slot churn on the overlapped backend
    (1-stage mesh): per-uid outputs bit-match the flush sharded backend
    and the single-request engine (same ``PipeDecConfig`` so the traces
    are comparable)."""
    target, draft = bundles
    reqs = _mk_reqs(7, 4, arrivals=[0, 1, 4, 6], max_new=[4, 5, 3, 4])
    single = PipeDecEngine(target, draft, PCFG1, max_len=MAX_LEN)
    want = {r.uid: single.generate(r.prompt, r.max_new_tokens)[0]
            for r in reqs}

    outs = {}
    for name, ex in (("flush", _sharded(bundles, 2, pcfg=PCFG1)),
                     ("overlapped", _overlapped(bundles, 2))):
        eng = SpecPipeDBEngine(target, draft, PCFG1, max_len=MAX_LEN,
                               max_slots=2, executor=ex)
        for r in reqs:
            eng.submit(r)
        outs[name] = eng.run()
    for uid, tokens in want.items():
        np.testing.assert_array_equal(
            outs["flush"][uid].tokens, tokens,
            err_msg=f"flush vs single uid={uid}")
        np.testing.assert_array_equal(
            outs["overlapped"][uid].tokens, tokens,
            err_msg=f"overlapped vs single uid={uid}")


def test_overlapped_one_tick_per_timestep(bundles):
    """The steady-state dispatch hook: the overlapped executor issues
    exactly ONE ring tick per executed global timestep — entries pending
    or not — and never falls back to a flush or per-slot dispatch."""
    target, draft = bundles
    reqs = _mk_reqs(8, 3, arrivals=[0, 0, 2], max_new=[4, 3, 4])
    ex = _overlapped(bundles, 2)
    eng = SpecPipeDBEngine(target, draft, PCFG1, max_len=MAX_LEN,
                           max_slots=2, executor=ex)
    before = {b: dict(b.calls) for b in (target, draft)}
    for r in reqs:
        eng.submit(r)
    eng.run()

    assert eng.stats.tick_dispatches == [1] * eng.stats.timesteps
    assert ex.calls["pipeline_tick"] == eng.stats.timesteps
    assert ex.calls["drain_tick"] == 0, \
        "per-timestep ticks must resolve every live flight"
    assert ex.calls["pipeline_verify"] == 0, "no flush dispatches"
    # draft rides the entry cadence, replicated locally
    disp = eng.stats.verify_dispatches
    assert draft.calls["tree_verify_rows"] - \
        before[draft].get("tree_verify_rows", 0) == sum(disp)
    for b in (target, draft):
        assert b.calls["tree_verify"] == before[b].get("tree_verify", 0)
    assert target.calls["tree_verify_rows"] == \
        before[target].get("tree_verify_rows", 0)
    assert eng.stats.peak_occupancy == 2, "slots actually shared"


def test_overlapped_generate_b1_path(bundles):
    """The B=1 path through ``generate_with_executor`` on the overlapped
    backend bit-matches the direct single-request engine."""
    target, draft = bundles
    prompt = np.asarray([5, 3, 2, 7, 11], np.int32)
    single = PipeDecEngine(target, draft, PCFG1, max_len=MAX_LEN)
    want, want_stats = single.generate(prompt, 6)
    got, stats = generate_with_executor(target, draft, PCFG1, prompt, 6,
                                        executor=_overlapped(bundles, 1),
                                        max_len=MAX_LEN)
    np.testing.assert_array_equal(got, want)
    assert stats.commits == want_stats.commits
    assert stats.acceptance == want_stats.acceptance


def test_overlapped_requires_matching_stage_count(bundles):
    """The ring IS the flight bookkeeping: an overlapped executor whose
    mesh stage count differs from ``PipeDecConfig.n_stages`` must be
    rejected (the fill latencies would disagree)."""
    target, draft = bundles
    with pytest.raises(AssertionError, match="n_stages"):
        SpecPipeDBEngine(target, draft, PCFG, max_len=MAX_LEN,
                         max_slots=2, executor=_overlapped(bundles, 2))


def test_overlapped_stale_flight_cannot_commit(bundles):
    """A killed slot's outstanding futures are dead: resolving one raises
    instead of committing from a stale tree (the engine never does — this
    pins the guard rail itself)."""
    from repro.serving import DeferredLogits

    h = DeferredLogits(slot=0, version=3)
    with pytest.raises(RuntimeError, match="not yet|before its exit"):
        h.resolve()
    h.dead = True
    with pytest.raises(RuntimeError, match="stale"):
        h.resolve()


def test_async_bitmatches_lockstep_and_single(bundles):
    """The async free-running backend (3 stage actors + a draft actor on
    the 1-device test process): staggered arrivals + slot churn must
    bit-match the flush sharded backend and the single-request engine —
    same tree policy, radically different schedule."""
    target, draft = bundles
    reqs = _mk_reqs(11, 4, arrivals=[0, 1, 4, 6], max_new=[4, 5, 3, 4])
    single = PipeDecEngine(target, draft, PCFG, max_len=MAX_LEN)
    want = {r.uid: single.generate(r.prompt, r.max_new_tokens)[0]
            for r in reqs}

    outs = {}
    execs = {"flush": _sharded(bundles, 2), "async": _async(bundles, 2)}
    for name, ex in execs.items():
        eng = SpecPipeDBEngine(target, draft, PCFG, max_len=MAX_LEN,
                               max_slots=2, executor=ex)
        for r in reqs:
            eng.submit(r)
        outs[name] = eng.run()
    ex = execs["async"]
    try:
        for uid, tokens in want.items():
            np.testing.assert_array_equal(
                outs["flush"][uid].tokens, tokens,
                err_msg=f"flush vs single uid={uid}")
            np.testing.assert_array_equal(
                outs["async"][uid].tokens, tokens,
                err_msg=f"async vs single uid={uid}")
        # every entry message stepped every free-running stage exactly
        # once, and the drained pipe consumed all its messages
        assert ex.calls["stage_steps"] == \
            ex.calls["entry_msgs"] * PCFG.n_stages
        assert ex._consumed == ex._pushed
    finally:
        ex.shutdown()


def test_async_kill_short_circuits_in_flight_layer(bundles):
    """Kill latency: with the stage gate paused, a pushed layer whose
    slot is killed must die at stage 0 — before even ONE hop, where the
    lockstep ring invalidates one stage per tick and a stale layer rides
    ``n_stages - 1`` more hops before its exit drops."""
    ex = _async(bundles, 2)
    try:
        ex.pause()
        row_on = np.zeros(2, bool)
        row_on[0] = True
        _d, handles = ex.tick_rows(*ex.dead_entry, row_on)
        ex.kill(0)
        ex.resume()
        ex.drain()
        ctr = ex.counters()
        assert ctr["stages"][0]["stale_rows"] >= 1, \
            "kill must beat the paused layer to stage 0"
        assert all(s["stale_rows"] >= 1 for s in ctr["stages"])
        assert handles[0].dead
        assert ex.calls["stale_exits"] >= 1
    finally:
        ex.shutdown()


def test_async_actor_exception_propagates(bundles):
    """Fail loudly, never hang: a stage actor that raises must surface
    on the host thread as ``AsyncExecutorError`` carrying the original
    traceback (within the executor timeout)."""
    ex = _async(bundles, 2)
    ex.timeout_s = 60.0
    ex._apply_j = lambda *a, **k: (_ for _ in ()).throw(
        RuntimeError("injected stage fault"))
    row_on = np.zeros(2, bool)
    row_on[0] = True
    try:
        with pytest.raises(AsyncExecutorError,
                           match="injected stage fault"):
            ex.tick_rows(*ex.dead_entry, row_on)
            ex.drain()
    finally:
        ex.shutdown()


def test_async_shutdown_clean_and_deterministic(bundles):
    """Clean shutdown: every actor thread joins (none leaked), shutdown
    is idempotent, and a fresh executor re-running the workload is
    bit-deterministic."""
    import threading

    target, draft = bundles
    reqs = _mk_reqs(13, 3, arrivals=[0, 1, 3], max_new=[4, 3, 4])

    def run_once():
        ex = _async(bundles, 2)
        eng = SpecPipeDBEngine(target, draft, PCFG, max_len=MAX_LEN,
                               max_slots=2, executor=ex)
        for r in reqs:
            eng.submit(r)
        res = eng.run()
        ex.shutdown()
        ex.shutdown()   # idempotent
        return {u: res[u].tokens for u in res}

    a, b = run_once(), run_once()
    leaked = [t.name for t in threading.enumerate()
              if t.name.startswith("async-")]
    assert not leaked, f"leaked actor threads: {leaked}"
    for u in a:
        np.testing.assert_array_equal(a[u], b[u],
                                      err_msg=f"repeat run uid={u}")


def test_devices_not_polluted_by_sharded_check():
    assert len(jax.devices()) == 1, \
        "test process must never see the sharded check's fake devices"
