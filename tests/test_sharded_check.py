"""The 8-stage acceptance pin of the executor layer, in a file of its own.

It runs ``repro.launch.sharded_check`` on an 8-device simulated mesh in a
subprocess (several minutes on the CPU); alone in its file, pytest-xdist's
``--dist loadfile`` runs it beside ``test_executor_sharded.py`` instead of
after it.
"""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_sharded_8stage_acceptance_pin_subprocess():
    """The PR's acceptance pin on a REAL 8-device simulated mesh: flush
    AND overlapped sharded backends == local == single per uid, one
    batched flush dispatch per pending timestep, one ring tick per
    executed timestep, and the tick-level pruning-propagation scenario (a
    slot killed with layers in flight writes nothing further, its stale
    exits come out dead, other slots bit-untouched).  Runs
    ``repro.launch.sharded_check --overlap`` in a subprocess so the
    forced host-device count cannot leak into this test process (same
    pattern as test_dryrun)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.sharded_check", "--stages",
         "8", "--requests", "4", "--overlap", "--async"],
        capture_output=True, text=True, timeout=1800, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    # the machine-greppable status line the CI legs key on
    assert lines[-1].startswith("SHARDED_CHECK ok stages=8"), lines[-1]
    summary = json.loads(
        [ln for ln in lines if ln.startswith("{")][-1])
    assert summary["bit_identical"]
    assert summary["stages"] == 8
    indep = summary["independent_draft"]
    assert indep["sharded"]["dispatches"]["pipeline_verify"] > 0
    assert (indep["sharded"]["tokens_per_timestep"]
            == indep["local"]["tokens_per_timestep"])
    # the steady-state executor: ONE ring tick per executed timestep, on
    # both the miss-heavy and the perfect-acceptance workloads —
    # admission timesteps included (prefill-in-ring: zero separate
    # prefill dispatches), with the ctrl gate closed on quiet ticks
    for wl in ("independent_draft", "self_draft"):
        over = summary[wl]["sharded_overlapped"]
        assert (over["dispatches"]["pipeline_tick"] == over["timesteps"])
        assert over["dispatches"]["prefill_in_ring"] == 4
        assert 0.0 < over["ctrl_active_rate"] < 1.0
    # hits with a full ring: prune index_maps rode the ring
    assert summary["self_draft"]["acceptance_mean"] > 0.99
    assert summary["self_draft"]["sharded_overlapped"]["dispatches"][
        "remap_rows"] > 0
    # misses with a full ring: in-flight layers were killed
    assert summary["independent_draft"]["sharded_overlapped"][
        "dispatches"]["kill"] > 0
    pp = summary["pruning_propagation"]
    assert pp["killed_rows_untouched"] and pp["other_slot_unaffected"]
    assert pp["stale_exits_dropped"] and pp["live_exits_match"]
    # retire-clear regression: a retired occupant's in-ring ctrl must not
    # leak into the recycled slot's next occupant
    assert summary["slot_recycle"]["bit_identical"]
    assert summary["slot_recycle"]["kills"] >= 2
    # async free-running backend: bit-identical on the same workloads
    # (miss-heavy, self-draft, long-prompt, slot-recycle), with a kill
    # observed to cancel an in-flight layer at stage 0 — before a full
    # ring revolution — plus fail-loudly and clean-shutdown pins
    for wl in ("independent_draft", "self_draft", "long_prompt"):
        asy = summary[wl]["sharded_async"]
        assert asy["dispatches"]["stage_steps"] == \
            asy["dispatches"]["entry_msgs"] * 8
    assert summary["independent_draft"]["sharded_async"][
        "dispatches"]["kill"] > 0
    assert summary["async_kill_latency"]["stale_at_stage0"] >= 1
    assert summary["async_kill_latency"]["revolution_hops_saved"] == 7
    assert summary["async_failfast"]["propagates"]
    assert summary["async_shutdown"]["deterministic"]
    assert summary["async_shutdown"]["no_leaked_threads"]
    assert summary["async_slot_recycle"]["bit_identical"]
