"""The phase reduction (``phases.py``) against a trace recorded on a TPU
v5e with the program's spans (``phase_run.py q25-7b.chat-batch OUT
3141592653 45``, the Python tracer off; ``phases.py OUT/trace --save
data/phases_q25-7b.json.gz --steps 3`` kept its first three traced
timesteps as ``load``'s tuples), and against made-up ones.  ``test_trace.py`` checks the older
reduction on its own recorded trace, which stays as it was."""
import os

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import phases
from phases import Recording, reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6          # the trace's clock counts nanoseconds


@pytest.fixture(scope="module")
def recorded():
    return phases.restore(os.path.join(DATA, "phases_q25-7b.json.gz"))


def test_offset_on_the_chip(recorded):
    """The v5e's device clock reads about 1.5 ms before the host's."""
    got = reduce(recorded)
    assert got["offset_ms"] == pytest.approx(1.5287, abs=1e-3)
    assert got["runs_matched"] == 9078


def test_chip_runs_land_in_the_span_that_launched_them(recorded):
    """Both verifies in the executor's verify call (in the entry), both
    commits in its commit call (in the exit), the draft's candidate
    picks (top-k of a log-softmax) in the expansion, though a runtime
    worker thread enqueues some of them after the call returns."""
    cover = phases.Cover(recorded.spans)
    where = {}
    for name, _, _, rid in recorded.runs["/device:TPU:0"]:
        if rid in recorded.enqueues:
            chain = cover.chain(recorded.enqueues[rid])
            where.setdefault(name[:name.rfind("(")], set()).add(
                (phases.phase(chain), phases.innermost(chain)))
    assert where["jit_tree_verify_rows"] == {("entry",
                                              "executor.verify_rows")}
    assert where["jit_target_commit_rows"] == \
        where["jit_draft_commit_rows"] == {("exit", "executor.commit_rows")}
    assert where["jit_top_k"] == where["jit_log_softmax"] == {
        ("expand", "expand")}
    got = reduce(recorded)
    assert got["programs_per_timestep"] == 3026.0
    assert got["programs_by_phase"] == {"expand": 5424, "exit": 2172,
                                        "entry": 1482}


def test_chip_idle_time_is_all_labelled(recorded):
    got = reduce(recorded)
    assert got["timesteps"] == 3
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"][0])
    assert got["idle_outside_share"] < 0.1
    top = [n.split(" (")[0] for n, _ in got["idle_gaps"][:3]]
    assert top == ["expand", "exit", "entry"]


def test_chip_phases_split_the_timestep(recorded):
    got = reduce(recorded)
    m = phases.metrics(got)
    assert m["entry_host_ms"] == pytest.approx(269.221, abs=1e-3)
    assert m["expand_host_ms"] == pytest.approx(672.331, abs=1e-3)
    assert m["exit_host_ms"] == pytest.approx(281.222, abs=1e-3)
    assert 0.9 * got["timestep_ms"] < m["entry_host_ms"] + \
        m["expand_host_ms"] + m["exit_host_ms"] < got["timestep_ms"]


def _made_up(lead=2 * MS):
    """Two timesteps of 100 ms; the device clock reads ``lead`` early."""
    spans = [("timestep", 0, 100 * MS, {"step": 1, "active": 1}),
             ("admit", 1 * MS, 4 * MS, {}),
             ("entry", 10 * MS, 30 * MS, {}),
             ("executor.verify_rows", 20 * MS, 10 * MS, {}),
             ("expand", 45 * MS, 30 * MS, {}),
             ("expand.slot", 46 * MS, 10 * MS, {"uid": 3, "slot": 0}),
             ("exit", 80 * MS, 15 * MS, {}),
             ("executor.commit_rows", 85 * MS, 5 * MS, {}),
             ("timestep", 110 * MS, 50 * MS, {"step": 2, "active": 1}),
             ("entry", 112 * MS, 10 * MS, {}),
             ("executor.verify_rows", 114 * MS, 4 * MS, {}),
             ("expand", 125 * MS, 20 * MS, {}),
             ("exit", 146 * MS, 10 * MS, {})]
    enqueues = {1: 22 * MS, 2: 47 * MS, 3: 86 * MS, 4: 116 * MS,
                5: 130 * MS}
    runs = [("jit_tree_verify_rows(7)", 22 * MS, 5 * MS, 1),
            ("jit_top_k(8)", 47.1 * MS, 2 * MS, 2),
            ("jit_target_commit_rows(9)", 86 * MS, 3 * MS, 3),
            ("jit_tree_verify_rows(7)", 116.2 * MS, 3 * MS, 4),
            ("jit_top_k(8)", 130 * MS, 1 * MS, 5)]
    runs = [(n, s - lead, d, r) for n, s, d, r in runs]
    return Recording(spans, enqueues, {"/device:TPU:0": runs})


def test_offset_is_the_device_clocks_lead():
    got = reduce(_made_up())
    assert got["offset_ms"] == pytest.approx(2.0, abs=0.01)
    assert got["runs_matched"] == 5


def test_runs_go_to_the_phase_that_enqueued_them():
    """By the enqueue on the host clock, not by where the run falls."""
    got = reduce(_made_up(lead=30 * MS))
    assert got["programs_by_phase"] == {"entry": 2, "expand": 2, "exit": 1}
    assert got["programs_by_span"] == {"executor.verify_rows": 2,
                                       "expand": 2, "executor.commit_rows": 1}
    assert got["programs_per_timestep"] == 2.5


def test_self_time_leaves_out_executor_calls():
    m = phases.metrics(reduce(_made_up()))
    assert m["entry_host_ms"] == pytest.approx((20 + 6) / 2)
    assert m["expand_host_ms"] == pytest.approx((30 + 20) / 2)
    assert m["exit_host_ms"] == pytest.approx((10 + 10) / 2)


def test_idle_labels_fill_the_idle_time():
    got = reduce(_made_up())
    busy = got["busy_s"][0]
    assert busy == pytest.approx((5 + 2 + 3 + 3 + 1) * 1e-3)
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - busy)
    labels = {n.split(" (")[0]: s for n, s in got["idle_gaps"]}
    # the gap between the timesteps is outside every span; the expand
    # spans' gaps (less the top_k runs) include their request's own span
    assert labels[phases.OUTSIDE] == pytest.approx(10e-3)
    assert labels["expand"] == pytest.approx((30 - 2 + 20 - 1) * 1e-3)
    assert got["idle_outside_share"] == pytest.approx(
        10e-3 / (got["window_s"] - busy))


@pytest.mark.parametrize("verify", ["tree_verify_rows", None],
                         ids=["one_verify_name", "four_names"])
def test_program_names_pair_on_the_recorded_v5e_trace(verify):
    """``trace.pair_launches`` on the older recorded trace (its programs
    all ``jit__unknown``), each program renamed as the program names it
    now: every launch pairs where both models' verify share one name,
    none where all four per-timestep programs differ (three names at
    most), so ``verify_roofline`` depends on the shared name."""
    from trace import Plane, reduce as reduce_old, restore as restore_old
    planes = restore_old(os.path.join(DATA, "trace_q25-7b.json.gz"))
    # the four compiled programs' fingerprints, by the label they paired
    # with on the chip (``test_trace.py``)
    label_of = {"8017494868153325652": "target_tree_verify_rows",
                "11544475575600174432": "draft_tree_verify_rows",
                "9000250889963096953": "target_commit_rows",
                "9558341516056190968": "draft_commit_rows"}

    def renamed(name):
        fp = name[name.find("(") + 1:-1]
        if not name.startswith("jit__unknown(") or fp not in label_of:
            return name
        label = label_of[fp]
        if verify and label.endswith("tree_verify_rows"):
            label = verify
        return f"jit_{label}({fp})"

    planes = [Plane(p.name, {k: [(renamed(n), s, d) for n, s, d in v]
                             for k, v in p.lines.items()})
              if p.name.startswith("/device") else p for p in planes]
    got = reduce_old(planes)
    want = {"target_tree_verify_rows": 5, "draft_tree_verify_rows": 5,
            "target_commit_rows": 5, "draft_commit_rows": 5}
    assert got.launched_calls == (want if verify else {})


def test_load_keeps_spans_and_enqueues_of_a_cpu_trace(tmp_path):
    """``load`` on a trace of the engine on the CPU: every program span
    with its metadata, and an enqueue instant for the runs it made (the
    CPU has no TPU plane, so no device runs)."""
    import jax
    import numpy as np

    from repro.core.pipedec import PipeDecConfig
    from repro.core.speculative import ModelBundle
    from repro.models import transformer as tf
    from repro.models.config import ModelConfig
    from repro.serving import Request, SpecPipeDBEngine
    from trace import find

    def bundle(name, d, seed):
        cfg = ModelConfig(name=name, family="dense", num_layers=1,
                          d_model=d, num_heads=2, num_kv_heads=1,
                          d_ff=2 * d, vocab_size=64)
        return ModelBundle(tf.init_model(jax.random.PRNGKey(seed), cfg),
                           cfg)

    def serve():
        eng = SpecPipeDBEngine(bundle("target", 32, 0), bundle("draft", 16, 1),
                               PipeDecConfig(n_stages=2, width=2, branch=2),
                               max_len=32, max_slots=2)
        for uid in range(3):
            eng.submit(Request(uid, np.arange(3 + uid, dtype=np.int32), 3))
        eng.run()
        return eng

    serve()                                   # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng = serve()
    finally:
        jax.profiler.stop_trace()
    rec = phases.load(find(str(tmp_path)))
    steps = [m for n, _, _, m in rec.spans if n == "timestep"]
    assert [m["step"] for m in steps] == list(range(1, eng.stats.timesteps
                                                   + 1))
    assert {m["uid"] for n, _, _, m in rec.spans
            if n == "expand.slot"} == {0, 1, 2}
    assert rec.runs == {} and len(rec.enqueues) > eng.stats.timesteps
