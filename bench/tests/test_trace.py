"""The trace reduction against a trace recorded on a TPU v5e
(``record_trace.py q25-7b.chat-batch OUT 667099219 12``, kept as
``load``'s tuples): the numbers the reduction gave on the chip, every
launch span of the recorded window paired with a program, and the
window split exactly into busy and idle time."""
import json
import os

import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
from trace import program_name, reduce, restore

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def recorded():
    planes = restore(os.path.join(DATA, "trace_q25-7b.json.gz"))
    with open(os.path.join(DATA, "trace_q25-7b.result.json")) as f:
        return planes, json.load(f)


def test_reduction_matches_the_chip(recorded):
    planes, chip = recorded
    got = reduce(planes)
    assert got.window_s == pytest.approx(chip["device"]["window_s"])
    assert got.busy_s == [pytest.approx(chip["device"]["busy_s"])]
    for key, rows in chip["breakdown"].items():
        mine = got.breakdown()[key]
        assert [n for n, _ in mine] == [n for n, _ in rows]
        assert [s for _, s in mine] == pytest.approx([s for _, s in rows])


def test_every_launch_pairs_on_the_chip_trace(recorded):
    """Five timesteps, each launching both models' verify and commit;
    the target's verify runs longer than the draft's."""
    got = reduce(recorded[0])
    assert got.launched_calls == {
        "target_tree_verify_rows": 5, "draft_tree_verify_rows": 5,
        "target_commit_rows": 5, "draft_commit_rows": 5}
    assert got.launched_s["target_tree_verify_rows"] > \
        got.launched_s["draft_tree_verify_rows"]


def test_busy_and_idle_fill_the_window(recorded):
    got = reduce(recorded[0])
    assert 0 < got.busy_s[0] < got.window_s
    assert got.busy_s[0] + sum(got.idle_s.values()) == pytest.approx(
        got.window_s)


def test_overlapping_programs_count_once():
    from trace import Plane
    host = Plane("/host:CPU", {"t": [("bench.timestep", 0.0, 100.0),
                                     ("bench.verify_rows", 10.0, 5.0)]})
    dev = Plane("/device:TPU:0", {"XLA Modules": [
        ("jit_a(1)", 20.0, 30.0), ("jit_b(2)", 40.0, 20.0)]})
    got = reduce([host, dev])
    assert got.busy_s == [pytest.approx(40e-9)]
    assert got.program_s == {"jit_a": pytest.approx(30e-9),
                             "jit_b": pytest.approx(20e-9)}
    # a gap goes to the innermost span at its midpoint: [0, 20) to the
    # verify span around 10, [60, 100) to the timestep's own host work
    assert got.idle_s["verify_rows"] == pytest.approx(20e-9)
    assert got.idle_s["engine host work"] == pytest.approx(40e-9)


def test_program_name():
    assert program_name("jit_target_tree_verify_rows(123)") == \
        "jit_target_tree_verify_rows"


def test_launches_pair_with_their_programs_in_order():
    """Target and draft launch programs of one name; eager slices run in
    between; each launch span gets the program it launched."""
    from trace import Plane
    host = Plane("/host:CPU", {"t": [
        ("bench.timestep", 0.0, 200.0),
        ("bench.launch.target_verify", 10.0, 1.0),
        ("bench.launch.draft_verify", 12.0, 1.0),
        ("bench.launch.target_verify", 100.0, 1.0),
        ("bench.launch.draft_verify", 102.0, 1.0)]})
    dev = Plane("/device:TPU:0", {"XLA Modules": [
        ("jit_slice(1)", 11.0, 1.0), ("jit__unknown(5)", 13.0, 20.0),
        ("jit_slice(1)", 33.0, 1.0), ("jit__unknown(6)", 34.0, 10.0),
        ("jit__unknown(5)", 103.0, 22.0), ("jit__unknown(6)", 126.0, 9.0)]})
    got = reduce([host, dev])
    assert got.launched_s == {"target_verify": pytest.approx(42e-9),
                              "draft_verify": pytest.approx(19e-9)}
    assert got.launched_calls == {"target_verify": 2, "draft_verify": 2}
    assert got.program_s["jit__unknown [target_verify]"] == \
        pytest.approx(42e-9)


MS = 1e6          # the trace's clock counts nanoseconds


def _alternating(n, runs):
    """Launch spans 100 ms apart, target's and draft's verify in turn."""
    from trace import Plane
    host = Plane("/host:CPU", {"t": [("bench.timestep", 0.0, 100 * n * MS)]
                               + [(f"bench.launch.{'target' if k % 2 == 0
                                   else 'draft'}_verify",
                                   100 * k * MS, 1 * MS) for k in range(n)]})
    return reduce([host, Plane("/device:TPU:0", {"XLA Modules": runs})])


def test_no_pairing_without_a_matching_program():
    """One compiled program cannot be both roles' verify."""
    got = _alternating(12, [("jit_b(2)", (100 * k + 5) * MS, 1 * MS)
                            for k in range(12)])
    assert got.launched_s == {}


def test_pairing_survives_an_early_device_clock_and_the_trace_ends():
    """The device clock reads 2 ms early (each run starts before its span
    on it), two runs launched before the first span lead, and the last
    span's program ran after the trace."""
    runs = [("jit__unknown(9)", -90 * MS, 3 * MS),
            ("jit__unknown(8)", -80 * MS, 3 * MS)]
    runs += [(f"jit__unknown({5 + k % 2})", (100 * k - 2) * MS,
              (10 + k % 2) * MS) for k in range(11)]
    got = _alternating(12, runs)
    assert got.launched_calls == {"target_verify": 6, "draft_verify": 5}
    assert got.launched_s == {"target_verify": pytest.approx(60e-3 - 2e-3),
                              "draft_verify": pytest.approx(55e-3)}


def test_programs_of_several_names_pair_with_their_launches():
    """Each role's program under a name of its own, eager ops between."""
    runs = []
    for k in range(12):
        name = "jit_target_verify(5)" if k % 2 == 0 else "jit_dv(6)"
        runs += [(name, (100 * k + 10) * MS, 20 * MS),
                 ("jit_slice(1)", (100 * k + 40) * MS, 1 * MS)]
    got = _alternating(12, runs)
    assert got.launched_calls == {"target_verify": 6, "draft_verify": 6}
