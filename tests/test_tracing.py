"""The engine's own spans, program names and live counters.

A tiny two-slot ``SpecPipeDBEngine`` runs under the JAX profiler on the
CPU; the ``.xplane.pb`` it writes is read back with
``jax.profiler.ProfileData``.  Every executed timestep must hold one
entry, expand and exit phase, each request's own spans must name a live
request, the executor's calls must sit inside the phase that makes them,
and every program the run compiles must carry its name.
"""
import collections
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pipedec import PipeDecConfig
from repro.core.speculative import ModelBundle, named_jit
from repro.models import transformer as tf
from repro.serving import Request, SpecPipeDBEngine
from repro.serving.executor import (AsyncPipelineExecutor, LocalFusedExecutor,
                                    OverlappedShardedExecutor,
                                    ShardedPipelineExecutor)

PCFG = PipeDecConfig(n_stages=3, width=4, branch=2)
MAX_LEN = 64
PHASES = ("entry", "expand", "exit")


@pytest.fixture(scope="module")
def bundles(tiny_dense, tiny_draft):
    target = dataclasses.replace(tiny_dense, name="target")
    draft = dataclasses.replace(tiny_draft, name="draft")
    return (ModelBundle(tf.init_model(jax.random.PRNGKey(0), target), target),
            ModelBundle(tf.init_model(jax.random.PRNGKey(9), draft), draft))


def _requests(seed, n=4, first_uid=0):
    rng = np.random.default_rng(seed)
    return [Request(first_uid + i,
                    rng.integers(0, 100, size=int(rng.integers(3, 8)))
                    .astype(np.int32), int(rng.integers(3, 7)))
            for i in range(n)]


def _serve(bundles, reqs, fused=True):
    eng = SpecPipeDBEngine(*bundles, PCFG, max_len=MAX_LEN, max_slots=2,
                           fused=fused)
    for r in reqs:
        eng.submit(r)
    streamed = []
    eng.run(on_token=lambda uid, tok, t: streamed.append(uid))
    return eng, streamed


@pytest.fixture(scope="module")
def traced(bundles, tmp_path_factory):
    """The program's spans (``(name, start, end, metadata)``) and the
    names of the programs that ran, from one traced run (after an
    untraced one compiled every program)."""
    _serve(bundles, _requests(1))
    out = str(tmp_path_factory.mktemp("trace"))
    reqs = _requests(2, first_uid=100)
    jax.profiler.start_trace(out)
    try:
        _serve(bundles, reqs)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans, modules = [], set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("$"):      # the Python tracer's
                    continue
                stats = dict(e.stats)
                if e.name.startswith("specpipe."):
                    spans.append((e.name[len("specpipe."):], e.start_ns,
                                  e.start_ns + e.duration_ns, stats))
                elif "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
    return sorted(spans, key=lambda s: s[1]), modules, reqs


def _inside(spans, outer):
    return [s for s in spans if outer[1] <= s[1] and s[2] <= outer[2]]


def test_every_timestep_holds_one_of_each_phase(traced):
    spans, _, _ = traced
    steps = [s for s in spans if s[0] == "timestep"]
    assert steps
    assert [s[3]["step"] for s in steps] == list(range(1, len(steps) + 1))
    for step in steps:
        inner = collections.Counter(s[0] for s in _inside(spans, step))
        assert [inner[p] for p in PHASES] == [1, 1, 1], inner
        assert inner["admit"] == 1 and inner["retire"] == 1


def test_request_spans_name_a_live_request(traced):
    """A request's own span names the request its slot holds: the last
    one admitted there."""
    spans, _, reqs = traced
    uids = {r.uid for r in reqs}
    holder, admitted = {}, set()
    seen = collections.Counter()
    for name, _, _, meta in spans:
        if name == "admit.slot":
            holder[meta["slot"]] = meta["uid"]
            admitted.add(meta["uid"])
        elif name in ("expand.slot", "exit.slot"):
            assert holder[meta["slot"]] == meta["uid"]
            seen[name] += 1
    assert admitted == uids
    assert seen["expand.slot"] and seen["exit.slot"]


def test_executor_calls_sit_inside_their_phase(traced):
    spans, _, _ = traced
    where = {"verify_rows": "entry", "commit_rows": "exit",
             "prefill": "admit"}
    for call, phase in where.items():
        calls = [s for s in spans if s[0] == f"executor.{call}"]
        assert calls, call
        outer = [s for s in spans if s[0] == phase]
        for c in calls:
            assert any(c in _inside(spans, o) for o in outer), (call, c)


def test_programs_carry_their_names(traced):
    _, modules, _ = traced
    assert {"jit_target_prefill", "jit_draft_prefill",
            "jit_tree_verify_rows", "jit_target_commit_rows",
            "jit_draft_commit_rows"} <= modules
    assert not any("_unknown" in m for m in modules), sorted(modules)


def test_program_names_are_identifiers():
    f = named_jit("llama-3.1-70b_prefill", lambda x: x + 1)
    text = f.lower(jnp.ones(3)).as_text()
    assert "module @jit_llama_3_1_70b_prefill " in text


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "looped"])
def test_live_counters_match_the_requests(bundles, fused):
    reqs = _requests(3, n=5)
    eng, streamed = _serve(bundles, reqs, fused=fused)
    st = eng.stats
    per_req = st.per_request.values()
    assert st.hits + st.misses == sum(g.hits + g.misses for g in per_req)
    assert st.hits == sum(g.hits for g in per_req)
    assert st.tokens_committed == len(streamed) == \
        sum(r.max_new_tokens + 1 for r in reqs)
    assert st.timesteps == len(st.occupancy)


def test_dispatch_counters_count_dispatches_only(bundles):
    """The counters a run sums into dispatches per timestep hold the
    executor's and the bundles' calls and nothing else."""
    target, draft = bundles
    for b in bundles:
        b.calls.clear()
    eng, _ = _serve(bundles, _requests(4))
    ex = eng.executor
    assert set(ex.calls) <= {"verify_rows", "commit_rows", "remap_rows"}
    for b in bundles:
        assert set(b.calls) <= {"prefill", "tree_verify_rows", "commit_rows"}
    verifies = sum(eng.stats.verify_dispatches)
    assert target.calls["tree_verify_rows"] == verifies
    assert ex.calls["verify_rows"] == verifies
    assert target.calls["prefill"] == len(eng.stats.per_request)


@pytest.mark.parametrize("cls, method", [
    (LocalFusedExecutor, "prefill"), (LocalFusedExecutor, "verify_rows"),
    (LocalFusedExecutor, "commit_rows"), (LocalFusedExecutor, "remap_rows"),
    (ShardedPipelineExecutor, "prefill"),
    (ShardedPipelineExecutor, "verify_rows"),
    (ShardedPipelineExecutor, "commit_rows"),
    (ShardedPipelineExecutor, "remap_rows"),
    (OverlappedShardedExecutor, "begin_prefill"),
    (OverlappedShardedExecutor, "tick_rows"),
    (OverlappedShardedExecutor, "verify_rows"),
    (OverlappedShardedExecutor, "commit_rows"),
    (OverlappedShardedExecutor, "remap_rows"),
    (AsyncPipelineExecutor, "prefill"), (AsyncPipelineExecutor, "tick_rows"),
    (AsyncPipelineExecutor, "verify_rows"),
    (AsyncPipelineExecutor, "commit_rows"),
    (AsyncPipelineExecutor, "remap_rows")],
    ids=lambda x: getattr(x, "__name__", x))
def test_every_backend_call_opens_its_span(cls, method):
    """Each backend's own public call opens ``specpipe.executor.<m>``."""
    fn = cls.__dict__[method]
    assert fn.__wrapped__.__name__ == method
    assert f"specpipe.executor.{method}" in [
        c.cell_contents for c in fn.__closure__]
