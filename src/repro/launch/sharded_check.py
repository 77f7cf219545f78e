"""Sharded-deployment equivalence check (the PR's acceptance pin, as a
runnable): on an ``--stages``-device CPU mesh, ``SpecPipeDBEngine`` with
``ShardedPipelineExecutor`` must produce per-uid token outputs
bit-identical to ``LocalFusedExecutor`` AND to the single-request
``PipeDecEngine`` under greedy decoding (staggered arrivals included),
and the dispatch-count hook must show exactly one batched sharded tick
per timestep with pending entries.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.sharded_check --stages 8

``--overlap`` additionally checks the steady-state overlapped executor
(``OverlappedShardedExecutor``: persistent always-full ring, ONE tick per
global timestep, deferred exit logits, in-ring pruning propagation):

  * per-uid outputs bit-identical to flush / local / single-request on
    TWO workloads — an independent draft (misses dominate: kills with
    layers in flight) and a self-draft (perfect acceptance: every commit
    is a hit, so prune index_maps ride the ring through a full pipeline);
  * exactly ONE ring tick per executed timestep
    (``calls["pipeline_tick"]`` == engine timesteps) — admission
    timesteps included: prefill rides the tick's prefill lane
    (prefill-in-ring), so NEITHER model ever logs a separate ``prefill``
    dispatch on the overlapped backend;
  * the gated ctrl channel actually gates: the measured ctrl-active rate
    (``calls["ctrl_active_ticks"] / calls["pipeline_tick"]``) is < 1;
  * a tick-level pruning-propagation scenario on the real S-stage mesh: a
    slot killed with layers still in flight writes nothing further into
    its stage tree caches (rows bit-untouched), its stale exits come out
    dead, and the other slot's rows/exits are bit-identical to a run
    without the kill.

``--paged`` reruns everything on block-paged KV arenas (``--page-size``
rows per block): the local backend's ``PagedKVArena`` pools plus the
sharded/overlapped stage arenas behind identity block tables.  The pin is
unchanged — paged outputs must stay bit-identical to the single-request
engine (the dense reference), with the same dispatch counts.  With
``--overlap`` the workload set grows a *long-prompt* leg whose prompts all
exceed the ring's ``--prefill-cap``, pinning chunked prefill-in-ring:
every admission streams through the lane over several ticks
(``prefill_chunks`` > requests) with exactly ONE tick per timestep and
``separate_prefill_dispatches == 0`` at any prompt length, and the
slot-recycle scenario reuses a slot under paging with a chunked prompt.

``--async`` runs every workload on the ``AsyncPipelineExecutor`` as well
(free-running per-stage actor threads + a disaggregated draft actor — no
host lockstep), pinning it bit-identical to the same single-request
reference, and adds three async-only scenarios:

  * *kill latency*: with the stage gate paused, an entry is pushed and
    its slot killed before the actors resume — the stale layer must die
    at stage 0 (``stage_counters[0]["stale_rows"]`` > 0), i.e. before
    even ONE hop, let alone a full ring revolution;
  * *fail loudly*: a stage actor forced to raise must surface on the
    main thread as ``AsyncExecutorError`` (original traceback attached)
    within the executor timeout — the check prints ``SHARDED_CHECK
    fail`` instead of hanging;
  * *clean shutdown*: ``shutdown()`` joins every actor thread (none
    leaked), twice (idempotent), and a repeat run is bit-deterministic.

``--async`` composes with ``--overlap`` and ``--quant`` but not
``--paged`` (the async backend has no paged path yet — it rejects the
combination loudly).

``--quant`` additionally runs the whole workload on int8 bundles
(``ModelBundle.quantize()``: per-out-channel int8 weights + int8 KV
arena).  The strong pin is the same as fp32's, *within* the quantized
path: quantized DB outputs across every executor are bit-identical to
the quantized single-request engine, with the identical dispatch-count
assertions (one tick per timestep, prefill-in-ring, no separate prefill
dispatch).  Against fp32 the gates are statistical, not bitwise — the
acceptance-rate delta stays within ``QUANT_ACCEPTANCE_TOL``, the
self-draft workload keeps ~perfect acceptance, and the int8 arena costs
at most ``QUANT_BYTES_RATIO_MAX`` of the fp32 bytes per slot (so an
equal byte budget admits >= ``QUANT_SLOTS_MULT_MIN`` x the slots).

Prints one JSON summary line plus one machine-greppable status line —
``SHARDED_CHECK ok stages=8 ...`` on success, ``SHARDED_CHECK fail ...``
(and a non-zero exit code, no traceback spelunking needed) on any
mismatch.  Run in its own process: the forced host-device count must not
leak into other jax users (tests spawn it via subprocess, CI runs it as a
dedicated leg and greps the status line).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

# int8 regression thresholds (committed gates; see module docstring)
QUANT_ACCEPTANCE_TOL = 0.15     # |acc(int8) - acc(fp32)| on the workload
QUANT_BYTES_RATIO_MAX = 0.55    # int8 arena bytes / fp32 arena bytes
QUANT_SLOTS_MULT_MIN = 1.9      # slots admitted at an equal byte budget


def _pruning_propagation_scenario(stages: int):
    """Tick-level pin of the in-ring kill on a real S-stage mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import pipeline as pl
    from repro.models import transformer as tf
    from repro.models.config import ModelConfig

    cfg = ModelConfig(name="pp-chk", family="dense", num_layers=stages,
                      d_model=32, num_heads=2, num_kv_heads=1, d_ff=64,
                      vocab_size=64)
    params = tf.init_model(jax.random.PRNGKey(3), cfg)
    mesh = pl.make_stage_mesh(stages)
    w = 4
    ticks = stages + 2
    cap = 1 + w * (ticks + 1)
    pcfg = pl.PipelineConfig(n_stages=stages, width=w, tree_capacity=cap,
                             max_len=32)
    sp, valid = pl.stage_params(cfg, params, stages)
    kill_at = 2

    def entry(t, slot0_on):
        key = jax.random.PRNGKey(100 + t)
        wi = 1 + t * w
        mask = jax.nn.one_hot(wi + jnp.arange(w), cap + w, dtype=bool)
        return {
            "act": jax.random.normal(key, (2, w, cfg.d_model)),
            "positions": jnp.broadcast_to(jnp.arange(w)[None], (2, w))
            .astype(jnp.int32),
            "mask": jnp.broadcast_to(mask[None], (2, w, cap + w)),
            "write_idx": jnp.full((2,), wi, jnp.int32),
            "model_len": jnp.zeros((2,), jnp.int32),
            "valid": jnp.asarray([slot0_on, True]),
            "version": jnp.zeros((2,), jnp.int32),
        }

    jtick = jax.jit(pl.make_pipedec_tick(cfg, pcfg, mesh))

    def run(with_kill: bool):
        model_kv, tree_kv = pl.init_stage_caches(cfg, pcfg, batch=2)
        ring = pl.init_ring(cfg, pcfg, batch=2)
        states, exits = [], []
        with mesh:
            for t in range(ticks):
                killed = with_kill and t >= kill_at
                kill = jnp.asarray([with_kill and t == kill_at, False])
                model_kv, tree_kv, ring, ex = jtick(
                    sp, valid, model_kv, tree_kv, ring,
                    entry(t, not killed), kill)
                states.append(jax.tree.map(np.asarray, tree_kv))
                exits.append((np.asarray(ex["valid"]),
                              np.asarray(ex["act"])))
        return states, exits

    states_a, exits_a = run(False)
    states_b, exits_b = run(True)

    def slot(tree, b):
        return jax.tree.map(lambda x: x[:, b], tree)

    eq = lambda x, y: jax.tree.map(np.testing.assert_array_equal, x, y)
    # (1) killed slot: no write after the kill tick — stale in-flight
    # layers stopped touching the stage tree caches
    for t in range(kill_at, ticks):
        eq(slot(states_b[t], 0), slot(states_b[kill_at - 1], 0))
    # ...whereas without the kill the same layers DID keep writing
    changed = any(
        bool(np.any(x != y))
        for x, y in zip(jax.tree.leaves(slot(states_a[ticks - 1], 0)),
                        jax.tree.leaves(slot(states_b[ticks - 1], 0))))
    assert changed, "control run must show the writes the kill suppressed"
    # (2) the other slot is bit-unaffected by the kill, every tick
    for t in range(ticks):
        eq(slot(states_b[t], 1), slot(states_a[t], 1))
    # (3) exits: stale slot-0 exits come out dead; slot 1 identical
    saw_dead = saw_live = False
    for t in range(ticks):
        va, aa = exits_a[t]
        vb, ab = exits_b[t]
        assert bool(va[1]) == bool(vb[1])
        if va[1]:
            np.testing.assert_array_equal(ab[1], aa[1])
            saw_live = True
        if t >= stages - 1:
            assert bool(va[0]), "control run: slot-0 layers must exit live"
        if t >= max(stages - 1, kill_at):
            # from here every slot-0 exit was either in flight at the
            # kill tick or an invalidated entry (at stages <= kill_at a
            # layer entered early enough exits live BEFORE the kill —
            # that exit is legitimately identical in both runs)
            assert not bool(vb[0]), "stale slot-0 exit must be dead"
            saw_dead = True
    assert saw_dead and saw_live
    return {"killed_rows_untouched": True, "other_slot_unaffected": True,
            "stale_exits_dropped": True, "live_exits_match": True,
            "ticks": ticks, "kill_at": kill_at}


def main(argv=None):
    """Run every workload x executor combination plus the async
    scenarios; print one machine-readable SHARDED_CHECK ok/fail
    line (CI greps it).
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=8)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--layers", type=int, default=0,
                    help="target layers (default: one per stage)")
    ap.add_argument("--overlap", action="store_true",
                    help="also check the overlapped executor (one ring "
                         "tick per timestep; PipeDecConfig.n_stages is "
                         "then --stages so the ring IS the flight "
                         "bookkeeping)")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="also check the async free-running executor "
                         "(per-stage actor threads + disaggregated draft; "
                         "PipeDecConfig.n_stages is then --stages), plus "
                         "its kill-latency, fail-loudly and "
                         "clean-shutdown scenarios")
    ap.add_argument("--quant", action="store_true",
                    help="also run the workload on int8 bundles "
                         "(ModelBundle.quantize()): same bit-identity pin "
                         "within the quantized path, acceptance-delta and "
                         "arena-bytes gates against fp32")
    ap.add_argument("--paged", action="store_true",
                    help="run every executor on block-paged KV arenas "
                         "(models.paging pools + block tables); outputs "
                         "must stay bit-identical to the dense reference")
    ap.add_argument("--page-size", type=int, default=16,
                    help="rows per KV block under --paged (power of two)")
    ap.add_argument("--prefill-cap", type=int, default=16,
                    help="overlapped ring prefill-lane chunk size; prompts "
                         "longer than this stream through the lane over "
                         "several ticks (chunked prefill)")
    args = ap.parse_args(argv)

    if "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.stages}")

    import jax
    import numpy as np

    from repro.core.pipedec import PipeDecConfig, PipeDecEngine
    from repro.core.speculative import ModelBundle
    from repro.models import transformer as tf
    from repro.models.config import ModelConfig
    from repro.serving import (AsyncExecutorError, AsyncPipelineExecutor,
                               LocalFusedExecutor,
                               OverlappedShardedExecutor, Request,
                               ShardedPipelineExecutor, SpecPipeDBEngine)

    assert len(jax.devices()) >= args.stages, \
        f"need {args.stages} devices, have {len(jax.devices())}"
    assert not (args.use_async and args.paged), \
        "--async has no paged path yet; drop one of --async/--paged"

    layers = args.layers or args.stages
    target_cfg = ModelConfig(name="chk-target", family="dense",
                             num_layers=layers, d_model=64, num_heads=4,
                             num_kv_heads=2, d_ff=128, vocab_size=128)
    draft_cfg = ModelConfig(name="chk-draft", family="dense", num_layers=1,
                            d_model=32, num_heads=2, num_kv_heads=1,
                            d_ff=64, vocab_size=128, tie_embeddings=True)
    target = ModelBundle(tf.init_model(jax.random.PRNGKey(0), target_cfg),
                         target_cfg)
    draft = ModelBundle(tf.init_model(jax.random.PRNGKey(9), draft_cfg),
                        draft_cfg)
    # the overlapped ring length is pcfg.n_stages, so it must equal the
    # mesh's stage count; the flush/local backends accept any pcfg (and
    # the async actor chain is likewise pcfg.n_stages long)
    n_stages = args.stages if (args.overlap or args.use_async) else 4
    pcfg = PipeDecConfig(n_stages=n_stages, width=4, branch=2)
    max_len = 160

    rng = np.random.default_rng(0)

    def mk_reqs(lo_new, hi_new):
        return [Request(i,
                        rng.integers(0, 100, size=int(rng.integers(3, 8)))
                        .astype(np.int32),
                        int(rng.integers(lo_new, hi_new)),
                        arrival_t=int(rng.integers(0, 3 * args.requests)))
                for i in range(args.requests)]

    mk = {
        "local": lambda t, d: LocalFusedExecutor(
            t, d, slots=args.slots, max_len=max_len,
            tree_capacity=pcfg.tree_buffer_capacity,
            capacity=pcfg.capacity, paged=args.paged,
            page=args.page_size),
        "sharded": lambda t, d: ShardedPipelineExecutor(
            t, d, slots=args.slots, max_len=max_len,
            tree_capacity=pcfg.tree_buffer_capacity,
            capacity=pcfg.capacity, n_stages=args.stages,
            paged=args.paged, page=args.page_size),
    }
    if args.overlap:
        mk["sharded_overlapped"] = lambda t, d: OverlappedShardedExecutor(
            t, d, slots=args.slots, max_len=max_len,
            tree_capacity=pcfg.tree_buffer_capacity,
            capacity=pcfg.capacity, n_stages=args.stages,
            prefill_cap=args.prefill_cap, paged=args.paged,
            page=args.page_size)
    if args.use_async:
        mk["sharded_async"] = lambda t, d: AsyncPipelineExecutor(
            t, d, slots=args.slots, max_len=max_len,
            tree_capacity=pcfg.tree_buffer_capacity,
            capacity=pcfg.capacity, n_stages=args.stages)

    def check_workload(tgt, drf, reqs):
        single = PipeDecEngine(tgt, drf, pcfg, max_len=max_len)
        want, acc = {}, {}
        for r in reqs:
            want[r.uid], st = single.generate(r.prompt, r.max_new_tokens)
            acc[r.uid] = st.acceptance
        part = {"acceptance_mean": round(float(np.mean(list(acc.values()))),
                                         4)}
        for name, make in mk.items():
            ex = make(tgt, drf)
            eng = SpecPipeDBEngine(tgt, drf, pcfg, max_len=max_len,
                                   max_slots=args.slots, executor=ex)
            before = {m: dict(m.calls) for m in (tgt, drf)}
            for r in reqs:
                eng.submit(r)
            res = eng.run()
            for uid, tokens in want.items():
                np.testing.assert_array_equal(
                    res[uid].tokens, tokens,
                    err_msg=f"{name} executor vs single-request uid={uid}")
            disp = eng.stats.verify_dispatches
            assert max(disp) == 1, f"{name}: >1 dispatch in one timestep"
            assert ex.calls["verify_rows"] == sum(disp), \
                f"{name}: one batched dispatch per pending timestep"
            # per-request acceptance counters (DBStats.accepted/proposed)
            # must agree with the single-request trace — the runs are
            # bit-identical, so the verify decisions are too
            for r in reqs:
                st = res[r.uid].stats
                assert eng.stats.accepted[r.uid] == st.hits, \
                    f"{name}: DBStats.accepted mismatch uid={r.uid}"
                assert eng.stats.proposed[r.uid] == st.hits + st.misses, \
                    f"{name}: DBStats.proposed mismatch uid={r.uid}"
            part[name] = {
                "timesteps": eng.stats.timesteps,
                "tokens_per_timestep": round(eng.stats.tokens_per_timestep,
                                             4),
                "peak_occupancy": eng.stats.peak_occupancy,
                "acceptance_rate": round(eng.stats.acceptance_rate, 4),
                "dispatches": dict(ex.calls),
            }
            if name == "sharded":
                assert ex.calls["pipeline_verify"] == sum(disp), \
                    "one batched sharded flush per pending timestep"
            if name == "sharded_overlapped":
                # the steady-state pin: ONE ring tick per executed global
                # timestep — admission timesteps included (prefill rides
                # the tick's prefill lane, never its own dispatch)
                assert ex.calls["pipeline_tick"] == eng.stats.timesteps, \
                    "overlapped: one ring tick per executed timestep"
                assert eng.stats.tick_dispatches == \
                    [1] * eng.stats.timesteps
                assert ex.calls["drain_tick"] == 0, \
                    "per-timestep ticks must resolve every live flight"
                assert ex.calls["prefill_in_ring"] == len(reqs), \
                    "every admission must prefill in-ring"
                assert eng.stats.separate_prefill_dispatches == 0, \
                    "overlapped: no standalone executor.prefill at ANY " \
                    "prompt length (chunked prefill streams long prompts)"
                for m in (tgt, drf):
                    assert m.calls["prefill"] == \
                        before[m].get("prefill", 0), \
                        "overlapped: no separate ModelBundle prefill " \
                        "dispatch"
                rate = ex.calls["ctrl_active_ticks"] / \
                    max(ex.calls["pipeline_tick"], 1)
                assert rate < 1.0, \
                    "gated ctrl must close on some ticks"
                part[name]["ctrl_active_rate"] = round(rate, 4)
            if name == "sharded_async":
                # every entering layer steps every free-running stage
                # actor exactly once, and the drained pipe consumed every
                # message it was fed
                assert ex.calls["stage_steps"] == \
                    ex.calls["entry_msgs"] * args.stages, \
                    "async: one stage step per entry per stage"
                assert ex._consumed == ex._pushed, \
                    "async: drained pipe must consume every message"
                # admission on the async backend is separate-dispatch:
                # the target's prompt rides the stage actors (never a
                # whole-model ModelBundle.prefill) and the draft runs
                # one ModelBundle.prefill per request (the self-draft
                # workload shares ONE bundle for both roles)
                assert drf.calls["prefill"] - \
                    before[drf].get("prefill", 0) == len(reqs), \
                    "async: one draft prefill per admission"
                if tgt is not drf:
                    assert tgt.calls["prefill"] == \
                        before[tgt].get("prefill", 0), \
                        "async: the target prefills stage by stage"
                ctr = ex.counters()
                part[name]["max_draft_lead"] = ctr["max_draft_lead"]
                part[name]["max_inbox_depth"] = max(
                    s["max_depth"] for s in ctr["stages"])
                part[name]["stale_rows"] = sum(
                    s["stale_rows"] for s in ctr["stages"])
                ex.shutdown()
                import threading
                assert not [t for t in threading.enumerate()
                            if t.name.startswith("async-")], \
                    "async: shutdown must join every actor thread"
        return part

    summary = {"stages": args.stages, "slots": args.slots,
               "requests": args.requests, "layers": layers,
               "overlap": args.overlap, "paged": args.paged,
               "page_size": args.page_size,
               "prefill_cap": args.prefill_cap}
    def check_recycle():
        """Regression: a retired occupant's in-ring ctrl must not leak
        into the recycled slot's next occupant.  Short request A (tiny
        prompt, back-to-back commits) retires while its final commits'
        ctrl messages still trail its killed layers in the ring; B joins
        the same slot the next timestep with a LONGER prompt whose low
        KV positions those stale commits would overwrite."""
        a = Request(0, np.arange(1, 4, dtype=np.int32), 2, arrival_t=0)
        b = Request(1, (np.arange(5, 45, dtype=np.int32) % 100), 4,
                    arrival_t=1)
        single = PipeDecEngine(target, target, pcfg, max_len=max_len)
        want = {r.uid: single.generate(r.prompt, r.max_new_tokens)[0]
                for r in (a, b)}
        ex = OverlappedShardedExecutor(
            target, target, slots=1, max_len=max_len,
            tree_capacity=pcfg.tree_buffer_capacity,
            capacity=pcfg.capacity, n_stages=args.stages,
            prefill_cap=args.prefill_cap, paged=args.paged,
            page=args.page_size)
        eng = SpecPipeDBEngine(target, target, pcfg, max_len=max_len,
                               max_slots=1, executor=ex)
        eng.submit(a)
        eng.submit(b)
        res = eng.run()
        for uid, tokens in want.items():
            np.testing.assert_array_equal(
                res[uid].tokens, tokens,
                err_msg=f"slot-recycle ctrl leak uid={uid}")
        assert ex.calls["kill"] >= 2, "both retires must kill in-ring"
        return {"bit_identical": True, "kills": int(ex.calls["kill"])}

    def check_recycle_async():
        """The slot-recycle leg on the async backend: same A-retires/
        B-reuses-the-slot workload as ``check_recycle``, with the retire's
        ctrl-version bump neutralising A's in-flight ctrl messages at
        whatever stage they sit."""
        a = Request(0, np.arange(1, 4, dtype=np.int32), 2, arrival_t=0)
        b = Request(1, (np.arange(5, 45, dtype=np.int32) % 100), 4,
                    arrival_t=1)
        single = PipeDecEngine(target, target, pcfg, max_len=max_len)
        want = {r.uid: single.generate(r.prompt, r.max_new_tokens)[0]
                for r in (a, b)}
        ex = AsyncPipelineExecutor(
            target, target, slots=1, max_len=max_len,
            tree_capacity=pcfg.tree_buffer_capacity,
            capacity=pcfg.capacity, n_stages=args.stages)
        eng = SpecPipeDBEngine(target, target, pcfg, max_len=max_len,
                               max_slots=1, executor=ex)
        eng.submit(a)
        eng.submit(b)
        res = eng.run()
        for uid, tokens in want.items():
            np.testing.assert_array_equal(
                res[uid].tokens, tokens,
                err_msg=f"async slot-recycle ctrl leak uid={uid}")
        kills = int(ex.calls["kill"])
        assert kills >= 2, "both retires must kill in-flight state"
        ex.shutdown()
        return {"bit_identical": True, "kills": kills}

    def check_async_kill_latency():
        """The short-circuit pin: with the stage gate paused, an entry is
        pushed and its slot killed before any actor touches it.  The
        layer must then die at stage 0 — suppressed before even ONE hop,
        where the lockstep ring can only invalidate one stage per tick
        and a stale layer rides ``n_stages - 1`` further hops before its
        exit is dropped."""
        ex = mk["sharded_async"](target, draft)
        try:
            ex.pause()
            row_on = np.zeros(args.slots, bool)
            row_on[0] = True
            _d, handles = ex.tick_rows(*ex.dead_entry, row_on)
            ex.kill(0)
            ex.resume()
            ex.drain()
            ctr = ex.counters()
            stale0 = ctr["stages"][0]["stale_rows"]
            assert stale0 >= 1, \
                "kill must beat the paused layer to stage 0"
            # ...and since rows go stale at processing time, every later
            # stage suppressed it too — never a live write after the kill
            assert all(s["stale_rows"] >= 1 for s in ctr["stages"])
            assert handles[0].dead, "the flight's future must be dead"
            assert ex.calls["stale_exits"] >= 1, \
                "the stale exit must be dropped, not delivered"
        finally:
            ex.shutdown()
        return {"stale_at_stage0": int(stale0),
                "revolution_hops_saved": args.stages - 1}

    def check_async_failfast():
        """The fail-loudly pin: a stage actor forced to raise must
        surface on the main thread as ``AsyncExecutorError`` carrying the
        original traceback, well inside the executor timeout — never a
        hang.  (The workload ``try`` below turns any such error into the
        ``SHARDED_CHECK fail`` status line.)"""
        import time as _time

        ex = mk["sharded_async"](target, draft)
        ex.timeout_s = 60.0

        def boom(*a, **k):
            raise RuntimeError("injected stage fault")

        ex._apply_j = boom
        row_on = np.zeros(args.slots, bool)
        row_on[0] = True
        t0 = _time.monotonic()
        try:
            ex.tick_rows(*ex.dead_entry, row_on)
            ex.drain()
        except AsyncExecutorError as e:
            elapsed = _time.monotonic() - t0
            assert "injected stage fault" in str(e), \
                "original traceback must ride the host-side error"
            assert elapsed < ex.timeout_s, "must fail fast, not time out"
        else:
            raise AssertionError(
                "stage fault must surface as AsyncExecutorError")
        finally:
            ex.shutdown()
        return {"propagates": True, "seconds": round(elapsed, 3)}

    def check_async_shutdown(reqs):
        """Clean-shutdown pin: ``shutdown()`` joins every actor thread
        (none leaked), is idempotent, and a fresh executor re-running the
        workload is bit-deterministic."""
        import threading

        def run_once():
            ex = mk["sharded_async"](target, draft)
            eng = SpecPipeDBEngine(target, draft, pcfg, max_len=max_len,
                                   max_slots=args.slots, executor=ex)
            for r in reqs:
                eng.submit(r)
            res = eng.run()
            ex.shutdown()
            ex.shutdown()    # idempotent
            return {u: res[u].tokens for u in res}

        a, b = run_once(), run_once()
        leaked = [t.name for t in threading.enumerate()
                  if t.name.startswith("async-")]
        assert not leaked, f"leaked actor threads: {leaked}"
        for u in a:
            np.testing.assert_array_equal(
                a[u], b[u], err_msg=f"async repeat-run uid={u}")
        return {"deterministic": True, "no_leaked_threads": True}

    def check_quant_arena():
        """Byte-budget gate: the int8 arena must cost at most
        ``QUANT_BYTES_RATIO_MAX`` of the fp32 bytes per slot, so an equal
        memory budget admits >= ``QUANT_SLOTS_MULT_MIN`` x the slots.
        Shapes only (``jax.eval_shape``) — nothing is allocated."""
        from repro.serving.scheduler import KVArena

        def bps(t, d):
            return KVArena(t, d, slots=1, max_len=max_len,
                           tree_capacity=pcfg.tree_buffer_capacity
                           ).bytes_per_slot()

        fp32_b = bps(target, draft)
        int8_b = bps(target.quantize(), draft.quantize())
        ratio = int8_b / fp32_b
        mult = fp32_b // int8_b if int8_b else 0
        assert ratio <= QUANT_BYTES_RATIO_MAX, \
            f"int8 arena ratio {ratio:.3f} > {QUANT_BYTES_RATIO_MAX}"
        assert mult >= QUANT_SLOTS_MULT_MIN, \
            f"int8 slots multiplier {mult} < {QUANT_SLOTS_MULT_MIN}"
        return {"fp32": fp32_b, "int8": int8_b,
                "ratio": round(ratio, 4), "slots_multiplier": int(mult)}

    try:
        reqs_main = mk_reqs(3, 7)
        summary["independent_draft"] = check_workload(target, draft,
                                                      reqs_main)
        if args.quant:
            # same requests, int8 bundles: bit-identity within the
            # quantized path (DB executors vs quant single-request) with
            # the identical dispatch-count assertions, then the
            # statistical gates against fp32
            q_target, q_draft = target.quantize(), draft.quantize()
            summary["quant_int8"] = check_workload(q_target, q_draft,
                                                   reqs_main)
            delta = abs(summary["quant_int8"]["acceptance_mean"]
                        - summary["independent_draft"]["acceptance_mean"])
            assert delta <= QUANT_ACCEPTANCE_TOL, \
                f"int8 acceptance delta {delta:.4f} > {QUANT_ACCEPTANCE_TOL}"
            summary["quant_int8"]["acceptance_delta_vs_fp32"] = \
                round(delta, 4)
            summary["quant_int8"]["arena_bytes_per_slot"] = \
                check_quant_arena()
            if args.overlap:
                # quantized self-draft: draft == target, so acceptance
                # must stay ~perfect (quant noise hits both identically)
                qsd = check_workload(q_target, q_target, mk_reqs(8, 14))
                assert qsd["acceptance_mean"] > 0.99, \
                    "int8 self-draft must keep ~perfect acceptance"
                summary["quant_self_draft"] = qsd
        if args.overlap:
            # self-draft: perfect acceptance — every commit is a hit, so
            # the prune index_maps ride the ring with n_stages-1 layers
            # in flight
            summary["self_draft"] = check_workload(target, target,
                                                   mk_reqs(8, 14))
            # long prompts: every prompt exceeds the ring's prefill lane,
            # so admission MUST stream chunk by chunk over several ticks
            # (one tick per timestep throughout, zero separate prefill
            # dispatches) and still bit-match the single-request engine
            cap = args.prefill_cap
            long_reqs = [
                Request(i,
                        rng.integers(0, 100,
                                     size=int(rng.integers(cap + 4,
                                                           2 * cap + 9)))
                        .astype(np.int32),
                        int(rng.integers(3, 6)),
                        arrival_t=int(rng.integers(0, args.requests)))
                for i in range(args.requests)]
            summary["long_prompt"] = check_workload(target, draft,
                                                    long_reqs)
            lp_disp = summary["long_prompt"]["sharded_overlapped"][
                "dispatches"]
            assert lp_disp["prefill_chunks"] > args.requests, \
                "long-prompt workload must actually chunk its prefills"
            summary["slot_recycle"] = check_recycle()
            assert summary["self_draft"]["acceptance_mean"] > 0.99
            assert summary["self_draft"]["sharded_overlapped"][
                "dispatches"].get("remap_rows", 0) > 0, \
                "self-draft workload must exercise in-ring prune " \
                "propagation"
            summary["pruning_propagation"] = \
                _pruning_propagation_scenario(args.stages)
        if args.use_async:
            asy = summary["independent_draft"]["sharded_async"]
            assert asy["dispatches"].get("kill", 0) > 0, \
                "miss-heavy workload must kill in-flight async layers"
            summary["async_kill_latency"] = check_async_kill_latency()
            summary["async_failfast"] = check_async_failfast()
            summary["async_shutdown"] = check_async_shutdown(reqs_main)
            summary["async_slot_recycle"] = check_recycle_async()
    except Exception as e:  # single loud line, non-zero exit — the CI
        # legs grep this instead of fishing assertion tracebacks
        import traceback
        traceback.print_exc(file=sys.stderr)
        reason = str(e).splitlines()[0][:200] if str(e) else ""
        print(f"SHARDED_CHECK fail stages={args.stages} "
              f"slots={args.slots} requests={args.requests} "
              f"overlap={int(args.overlap)} quant={int(args.quant)} "
              f"paged={int(args.paged)} async={int(args.use_async)} "
              f"error={type(e).__name__}: {reason}")
        return 1
    summary["bit_identical"] = True
    print(json.dumps(summary))
    parts = [f"SHARDED_CHECK ok stages={args.stages}",
             f"slots={args.slots}", f"requests={args.requests}",
             f"overlap={int(args.overlap)}", f"quant={int(args.quant)}",
             f"paged={int(args.paged)}", f"async={int(args.use_async)}",
             "bit_identical=1"]
    if args.paged:
        parts += [f"page_size={args.page_size}"]
    if args.use_async:
        asy = summary["independent_draft"]["sharded_async"]
        parts += [
            f"async_kills={asy['dispatches']['kill']}",
            f"async_stale_at_stage0="
            f"{summary['async_kill_latency']['stale_at_stage0']}",
            f"async_max_draft_lead={asy['max_draft_lead']}",
        ]
    if args.overlap:
        over = summary["independent_draft"]["sharded_overlapped"]
        lp = summary["long_prompt"]["sharded_overlapped"]
        parts += [
            f"ticks_per_timestep="
            f"{over['dispatches']['pipeline_tick'] / over['timesteps']:.2f}",
            f"ctrl_active_rate={over['ctrl_active_rate']:.4f}",
            f"prefill_in_ring={over['dispatches']['prefill_in_ring']}",
            f"prefill_chunks_long={lp['dispatches']['prefill_chunks']}",
            f"long_ticks_per_timestep="
            f"{lp['dispatches']['pipeline_tick'] / lp['timesteps']:.2f}",
        ]
    if args.quant:
        q = summary["quant_int8"]
        arena = q["arena_bytes_per_slot"]
        parts += [
            f"quant_acceptance_delta={q['acceptance_delta_vs_fp32']:.4f}",
            f"quant_arena_ratio={arena['ratio']:.4f}",
            f"quant_slots_multiplier={arena['slots_multiplier']}",
        ]
        if args.overlap:
            qo = q["sharded_overlapped"]
            parts += [
                f"quant_ticks_per_timestep="
                f"{qo['dispatches']['pipeline_tick'] / qo['timesteps']:.2f}",
            ]
    print(" ".join(parts))
    return 0


if __name__ == "__main__":
    sys.exit(main())
