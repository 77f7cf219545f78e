"""Cheap steady-state ticks: the three overlapped-executor cost levers —
gated in-ring ctrl, donated ring/stage-cache buffers, and prefill-in-ring
— must each be free of semantic effect (committed tokens bit-identical
with every lever on or off) while actually engaging (no donation
warnings, no separate prefill dispatches, ctrl gated off on quiet ticks).

All tests run on a 1-stage mesh (the in-process device budget); the same
levers run on a REAL 8-device mesh via ``repro.launch.sharded_check
--overlap`` (see tests/test_sharded_check.py).
"""
import warnings

import jax
import numpy as np
import pytest

from repro.core.pipedec import PipeDecConfig, PipeDecEngine
from repro.core.speculative import ModelBundle
from repro.models import transformer as tf
from repro.serving import (OverlappedShardedExecutor, Request,
                           SpecPipeDBEngine)

PCFG1 = PipeDecConfig(n_stages=1, width=4, branch=2)
MAX_LEN = 128


@pytest.fixture(scope="module")
def bundles(tiny_dense, tiny_draft):
    tp = tf.init_model(jax.random.PRNGKey(0), tiny_dense)
    dp = tf.init_model(jax.random.PRNGKey(9), tiny_draft)
    return ModelBundle(tp, tiny_dense), ModelBundle(dp, tiny_draft)


def _overlapped(bundles, slots, **kw):
    target, draft = bundles
    return OverlappedShardedExecutor(
        target, draft, slots=slots, max_len=MAX_LEN,
        tree_capacity=PCFG1.tree_buffer_capacity, capacity=PCFG1.capacity,
        n_stages=1, **kw)


def _mk_reqs(seed, n, arrivals, max_new):
    rng = np.random.default_rng(seed)
    return [Request(i,
                    rng.integers(0, 100, size=int(rng.integers(3, 8)))
                    .astype(np.int32), int(max_new[i]),
                    arrival_t=int(arrivals[i]))
            for i in range(n)]


def _run(bundles, reqs, slots=2, **kw):
    target, draft = bundles
    ex = _overlapped(bundles, slots, **kw)
    eng = SpecPipeDBEngine(target, draft, PCFG1, max_len=MAX_LEN,
                           max_slots=slots, executor=ex)
    for r in reqs:
        eng.submit(r)
    return eng, ex, eng.run()


def test_donated_tick_compiles_without_donation_warnings(bundles):
    """The donated tick must actually alias: jax warns ("Some donated
    buffers were not usable") when a donated input cannot be aliased to
    an output — the pin is that no such warning fires across compile and
    steady-state dispatch."""
    reqs = _mk_reqs(11, 3, arrivals=[0, 1, 3], max_new=[4, 3, 4])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        _run(bundles, reqs, donate=True)
    donation = [w for w in caught if "donat" in str(w.message).lower()]
    assert not donation, [str(w.message) for w in donation]


def test_gating_and_donation_bit_identical_on_vs_off(bundles):
    """Committed tokens must be bit-identical with gated ctrl + donation
    + prefill-in-ring on vs all three off (the off configuration is the
    PR-4 semantics) and vs the single-request engine."""
    target, draft = bundles
    reqs = _mk_reqs(12, 4, arrivals=[0, 1, 4, 6], max_new=[4, 5, 3, 4])
    single = PipeDecEngine(target, draft, PCFG1, max_len=MAX_LEN)
    want = {r.uid: single.generate(r.prompt, r.max_new_tokens)[0]
            for r in reqs}
    _, _, on = _run(bundles, reqs, gate_ctrl=True, donate=True)
    _, ex_off, off = _run(bundles, reqs, gate_ctrl=False, donate=False,
                          prefill_cap=0)
    for uid, tokens in want.items():
        np.testing.assert_array_equal(on[uid].tokens, tokens,
                                      err_msg=f"levers-on uid={uid}")
        np.testing.assert_array_equal(off[uid].tokens, tokens,
                                      err_msg=f"levers-off uid={uid}")
    # ungated: every tick pays the ctrl application
    assert ex_off.calls["ctrl_active_ticks"] == ex_off.calls["pipeline_tick"]


def test_prefill_rides_the_tick_dispatch(bundles):
    """The dispatch-count pin: admission prefill no longer issues its own
    dispatch — ``calls["pipeline_tick"] == timesteps`` with admissions
    included, no ``prefill`` entry in either ``ModelBundle.calls``, and
    one ``prefill_in_ring`` per admitted request."""
    target, draft = bundles
    reqs = _mk_reqs(13, 4, arrivals=[0, 0, 2, 5], max_new=[4, 3, 4, 3])
    before = {b: dict(b.calls) for b in (target, draft)}
    eng, ex, _ = _run(bundles, reqs)
    assert ex.calls["pipeline_tick"] == eng.stats.timesteps
    assert eng.stats.tick_dispatches == [1] * eng.stats.timesteps
    assert ex.calls["prefill_in_ring"] == len(reqs)
    assert ex.calls["drain_tick"] == 0
    for b in (target, draft):
        assert b.calls["prefill"] == before[b].get("prefill", 0), \
            "prefill must ride the tick dispatch, not a ModelBundle call"
    # the ctrl gate actually closes on some ticks of a miss-heavy run
    assert ex.calls["ctrl_active_ticks"] <= ex.calls["pipeline_tick"]


def test_long_prompt_streams_through_ring_in_chunks(bundles):
    """A prompt longer than the ring's prefill lane no longer falls back
    to a separate dispatch: it streams through the lane in
    ``prefill_cap``-token chunks over consecutive ticks — tokens still
    bit-match the single-request engine, with zero ``ModelBundle``
    prefill calls and one tick per timestep throughout."""
    target, draft = bundles
    rng = np.random.default_rng(14)
    long_prompt = rng.integers(0, 100, size=12).astype(np.int32)
    reqs = [Request(0, long_prompt, 4, arrival_t=0),
            Request(1, rng.integers(0, 100, size=4).astype(np.int32), 3,
                    arrival_t=1)]
    single = PipeDecEngine(target, draft, PCFG1, max_len=MAX_LEN)
    want = {r.uid: single.generate(r.prompt, r.max_new_tokens)[0]
            for r in reqs}
    before = {b: dict(b.calls) for b in (target, draft)}
    eng, ex, res = _run(bundles, reqs, prefill_cap=8)
    for uid, tokens in want.items():
        np.testing.assert_array_equal(res[uid].tokens, tokens,
                                      err_msg=f"uid={uid}")
    assert ex.calls["prefill_in_ring"] == 2, "both prompts ride the ring"
    assert ex.calls["prefill_chunks"] == 3, \
        "12-token prompt = 2 chunks at cap 8, short prompt = 1"
    for b in (target, draft):
        assert b.calls["prefill"] == before[b].get("prefill", 0), \
            "no separate-dispatch prefill at any prompt length"
    assert eng.stats.separate_prefill_dispatches == 0
    assert ex.calls["pipeline_tick"] == eng.stats.timesteps


def test_sim_ctrl_and_prefill_cost_terms():
    """The ``flush=False`` pricing's steady-state cost terms: the gated
    ctrl term scales with the active rate (``ctrl_rate=0`` reproduces
    the old cost exactly), and the separate-prefill term is paid by the
    flush schedule only — the overlapped schedule rides admission in the
    hop."""
    from repro.core import sim

    hw = sim.StageHardware(n_stages=8, t_stage_one=1e-4,
                           t_stage_width=4e-4, t_comm=5e-5, t_draft=1e-4,
                           t_sync=1e-5)
    base = sim.specpipe_db_sharded_timestep(hw, 4)
    assert sim.specpipe_db_sharded_timestep(hw, 4, ctrl_rate=0.0,
                                            t_ctrl=1e-3) == base
    gated = sim.specpipe_db_sharded_timestep(hw, 4, ctrl_rate=0.2,
                                             t_ctrl=1e-3)
    ungated = sim.specpipe_db_sharded_timestep(hw, 4, ctrl_rate=1.0,
                                               t_ctrl=1e-3)
    assert base < gated < ungated
    assert abs((gated - base) - 0.2e-3) < 1e-12
    # prefill: flush pays per admission, overlapped rides the ring
    fl = sim.specpipe_db_sharded_timestep(hw, 4, flush=True)
    fl_adm = sim.specpipe_db_sharded_timestep(hw, 4, flush=True,
                                              prefill_rate=0.5,
                                              t_prefill=2e-3)
    assert abs(fl_adm - (fl + 0.5 * 2e-3)) < 1e-12
    over_adm = sim.specpipe_db_sharded_timestep(hw, 4, prefill_rate=0.5,
                                                t_prefill=2e-3)
    assert over_adm == base


def test_kill_cancels_in_flight_prefill(bundles):
    """A slot killed while its prompt is riding the prefill lane must
    leave the executor clean: the ``DeferredPrefill`` dies (resolve
    raises), ``drain()`` terminates, and the slot can admit a fresh
    prefill."""
    ex = _overlapped(bundles, 1)
    prompt = np.asarray([1, 2, 3], np.int32)
    h = ex.begin_prefill(0, prompt)
    assert h is not None
    ex.kill(0)
    with pytest.raises(RuntimeError, match="killed"):
        h.resolve()
    assert ex.drain() == 0, "no outstanding futures after the kill"
    h2 = ex.begin_prefill(0, prompt)
    assert h2 is not None and not h2.dead


def test_prefix_embeds_bundle_disables_prefill_in_ring(tiny_dense,
                                                       tiny_draft):
    """ModelBundle prefill semantics the raw-token lane cannot express
    (prefix_embeds / enc_out / window_override) must force the
    separate-dispatch fallback."""
    import jax.numpy as jnp

    tp = tf.init_model(jax.random.PRNGKey(0), tiny_dense)
    dp = tf.init_model(jax.random.PRNGKey(9), tiny_draft)
    target = ModelBundle(tp, tiny_dense,
                         prefix_embeds=jnp.zeros((1, 2, tiny_dense.d_model)))
    draft = ModelBundle(dp, tiny_draft)
    ex = OverlappedShardedExecutor(
        target, draft, slots=1, max_len=MAX_LEN,
        tree_capacity=PCFG1.tree_buffer_capacity, capacity=PCFG1.capacity,
        n_stages=1)
    assert ex.prefill_cap == 0
    assert ex.begin_prefill(0, np.asarray([1, 2, 3], np.int32)) is None
