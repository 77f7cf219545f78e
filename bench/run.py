"""Run one benchmark cell once on the TPU and print one JSON result line.

  python3 bench/run.py --workload q25-7b.chat-batch --seed 7 --seconds 45 \
      --trace 0

Everything a cell is comes from data: ``BENCHMARK.json`` names its
configuration (``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``), and every metric is a reader of its
own (``bench/metrics/<metric>.py``).  ``--trace 0`` prints the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a run with
the profiler on.

The run: weights made on the device from the seed, the serving stack
built from the configuration, every program the traffic needs warmed
(set-up), then ``--seconds`` of traffic through ``SpecPipeDBEngine``,
then, for a seeded sample of requests, the logit rows that the target
and the draft picked their tokens from checked against the plain fp32
reference (``reference.py``).  It refuses to run on
anything but a TPU with the cell's chip count.  The last line of
standard output is the result; the numbers compared for ``correct``
close it, and standard error ends with them too.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import List, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

# requests checked against the reference: the longest served one, then
# seeded others until this many served tokens (or MAX_CHECKED requests)
CHECK_TOKENS = 256
MAX_CHECKED = 16
WARM_UID = 1 << 30          # uids of the throwaway warm-up requests
# a --trace 1 run traces the first TRACE_S seconds of its window (a trace
# grows by megabytes a second); its host-clock readings come from the rest
TRACE_S = 8.0


def say(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    """A cell of ``BENCHMARK.json`` by name; the chip scripts also take a
    cell that is not (yet) in it, named ``<config>:<traffic>`` (one
    chip)."""
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    config, _, mix = name.partition(":")
    if not mix:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    return {"name": name, "config": config, "traffic": mix, "chips": 1}


def reader(name: str):
    """``bench/metrics/<name>.py``'s ``read``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metric entries this cell reports in this kind of run."""
    entries = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def tpu_devices(chips: int):
    """The first ``chips`` TPU devices; anything else is an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RuntimeError(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX found "
                           f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> str:
    """The program's compile-cache rule (``JAX_COMPILATION_CACHE_DIR``,
    else ``<checkout>/.jax_cache``), with every program cached however
    quick its compile, so that a second run compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def compile_clock():
    """``chip_smoke.CompileClock``: backend-compile seconds and compile
    cache hits and misses, from JAX's own monitoring events."""
    sys.path.insert(0, ROOT)
    try:
        from chip_smoke import CompileClock
    finally:
        sys.path.remove(ROOT)
    return CompileClock()


def programs(clock) -> int:
    """Programs compiled or loaded so far (every compile looks in the
    cache, which counts a hit or a miss)."""
    return clock.events["cache_hits"] + clock.events["cache_misses"]


def make_weights(conf: dict, seed: int, devices):
    """(target, draft) ``model.Weights`` from the seed, each made by its
    family on the first device."""
    import jax

    import model
    key = model.seed_key(seed)
    target, draft = (
        fam.make_local(jax.random.fold_in(key, i), s, devices[0])
        for i, fam, s in zip((1, 2), model.families(conf),
                             model.shapes(conf)))
    jax.block_until_ready((target.params, draft.params))
    return target, draft


def warm(executor, engine, conf: dict, mix: dict) -> None:
    """Run every program shape the cell's traffic can reach: each prompt
    length bucket's prefill, each slot index's admission, each
    power-of-two slot bucket of the fused verify, commit and remap, and a
    short throwaway run of the engine through every slot (admission,
    entry, expand, exit, hit, retire)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import tree as tree_lib
    from repro.serving.engine import Request
    from traffic import prompt_buckets
    sv = conf["serving"]
    slots, w = sv["slots"], sv["width"]
    pc = engine.pcfg
    tcap = pc.tree_buffer_capacity
    buckets = prompt_buckets(mix)
    arena = executor.arena
    held = [arena.alloc() for _ in range(slots)]
    for n in buckets:
        executor.prefill(held[0], jnp.zeros((1, n), jnp.int32))
    for slot in held[1:]:
        executor.prefill(slot, jnp.zeros((1, buckets[0]), jnp.int32))
    for slot in held:
        arena.free(slot)
    z = lambda *s: jnp.asarray(np.zeros(s, np.int32))   # noqa: E731
    nb = 1
    while nb <= slots:
        row_on = np.arange(slots) < nb
        executor.verify_rows(z(slots, w), z(slots, w),
                             jnp.zeros((slots, w, tcap), bool), z(slots),
                             jnp.full((slots,), pc.capacity, jnp.int32),
                             row_on)
        nb *= 2
    executor.commit_rows(z(slots), jnp.zeros((slots,), bool))
    imaps = np.tile(np.arange(pc.capacity, dtype=np.int32), (slots, 1))
    executor.remap_rows(imaps, np.ones((slots,), bool))
    rng = np.random.default_rng(0)
    vocab = engine.inner.target.cfg.vocab_size
    for uid in range(slots):
        engine.submit(Request(WARM_UID + uid, rng.integers(
            0, vocab, size=buckets[0]).astype(np.int32), 4))
    # at zero acceptance a hit is rare, and the first would compile the
    # tree's hit path (prune, remap, the pruned tree's expansion) inside
    # the window: the throwaway requests take the root's first child as
    # a hit at every commit
    real = tree_lib.find_child_with_token
    tree_lib.find_child_with_token = lambda tree, _x: real(tree,
                                                           tree.tokens[1])
    try:
        engine.run()
    finally:
        tree_lib.find_child_with_token = real


@dataclasses.dataclass
class Reading:
    """What the metric readers read (see ``bench/metrics``)."""

    setup_s: float
    window_s: float
    timesteps: int
    tokens: int
    tbt_ms: list
    dispatches: int
    useful_flops: float
    least_s: dict
    least_calls: dict
    chips: int
    peak: dict
    trace: Optional[object] = None


def counters(executor, engine) -> collections.Counter:
    c = collections.Counter()
    for src in (executor.calls, engine.inner.target.calls,
                engine.inner.draft.calls):
        c.update(src)
    return c


def sample(feed, seed: int) -> List[int]:
    """A seeded sample of the requests that served tokens: the longest,
    then others until ``CHECK_TOKENS`` served tokens or ``MAX_CHECKED``
    requests."""
    import numpy as np
    served = {u: ids for u, ids in feed.token_id.items() if ids}
    if not served:
        return []
    order = sorted(served, key=lambda u: (-len(served[u]), u))
    rng = np.random.default_rng(int(seed) + 1)
    rest = list(rng.permutation(order[1:]))
    pick, n_tok = [order[0]], len(served[order[0]])
    while rest and n_tok < CHECK_TOKENS and len(pick) < MAX_CHECKED:
        u = int(rest.pop(0))
        pick.append(u)
        n_tok += len(served[u])
    return pick


def check_served(served, prompts, rows, weights, control: bool = False):
    """Every kept row of the sampled requests against the reference, per
    role: ``{role: {stat: values over rows}}`` (``reference.STATS``,
    ``tokens_ok`` and, with ``control``, the bfloat16 pass's stats)."""
    import numpy as np

    import reference
    out = {}
    for role, w in weights.items():
        parts = []
        for u in sorted(served):
            mine = {k: v for (uid, k), v in rows[role].items() if uid == u}
            got = reference.check_rows(w, prompts[u], served[u], mine,
                                       control=control)
            if got is not None:
                parts.append(got)
        out[role] = {k: np.concatenate([p[k] for p in parts])
                     for k in (parts[0] if parts else {})}
    return out


def judge(found: dict, limits: dict) -> dict:
    """The numbers compared for ``correct``, each beside its limit: the
    largest reading of each ``<role>_<stat>`` in ``limits``, the served
    tokens that are not their own row's largest logit (limit 0), and the
    rows checked (at least one per role)."""
    checks = {}
    for name, limit in limits.items():
        role, stat = name.split("_", 1)
        vals = found.get(role, {}).get(stat)
        value = float(vals.max()) if vals is not None and vals.size \
            else float("inf")
        checks[name] = {"value": value, "limit": float(limit),
                        "ok": value <= float(limit)}
    target = found.get("target", {})
    bad = int((~target["tokens_ok"]).sum()) if "tokens_ok" in target else 0
    checks["tokens_mismatched"] = {"value": bad, "limit": 0, "ok": bad == 0}
    for role in ("target", "draft"):
        n = int(found.get(role, {}).get("tokens_ok", ()).__len__())
        checks[f"{role}_rows_checked"] = {"value": n, "limit": 1,
                                          "ok": n >= 1}
    return checks


def reading_stats(found: dict) -> dict:
    """Each role's stats, the program's and the control's, as their
    largest and median readings (the control test's record)."""
    import numpy as np
    return {role: {k: {"max": float(np.max(v)), "median": float(np.median(v)),
                       "rows": int(np.size(v))}
                   for k, v in stats.items() if k != "tokens_ok"}
            for role, stats in found.items()}


def run_cell(bench: dict, cell: dict, conf: dict, mix: dict, seed: int,
             seconds: float, trace: bool, devices,
             keep_trace: Optional[str] = None, control: bool = False,
             check: bool = True) -> dict:
    """One run of ``cell`` (its configuration ``conf`` and traffic mix
    ``mix`` already read); returns the result object.  ``keep_trace``
    names a directory to leave the profiler's trace in; ``control`` also
    reads the bfloat16 control on the same positions (the control test's
    reading, never part of ``correct``); ``check`` False skips the check
    against the reference (the rate sweep)."""
    import jax

    import flops as fl
    import model
    import serve
    import traffic
    from trace import find, load, reduce

    say(f"compile cache: {enable_compile_cache()}")
    clock = compile_clock()
    sv = conf["serving"]
    target_w, draft_w = make_weights(conf, seed, devices)
    say(f"weights: target {model.nbytes(target_w.params) / 2**30:.3f} GiB, "
        f"draft {model.nbytes(draft_w.params) / 2**30:.3f} GiB")
    executor, engine = serve.build(conf, target_w, draft_w)
    plan = traffic.schedule(mix, seed, target_w.shape.vocab, sv["slots"],
                            seconds)
    engine.schedule_budget = serve.guard_budget(
        plan, sv["n_stages"], getattr(executor, "prefill_cap", 0))
    warm(executor, engine, conf, mix)
    say(f"warm-up done at {time.perf_counter() - T_START:.2f} s; "
        f"{programs(clock)} programs compiled or loaded")

    trace_dir = keep_trace or (tempfile.mkdtemp(prefix="bench_trace_")
                               if trace else None)
    state = {}

    def on_open():
        state["setup_s"] = time.perf_counter() - T_START
        state["compiles"] = programs(clock)
        state["compile_s"] = clock.seconds
        state["calls"] = counters(executor, engine)
        if trace:
            jax.profiler.start_trace(trace_dir)

    def on_trace_end():
        state["traced_to"] = time.perf_counter()
        jax.profiler.stop_trace()
        state["calls"] = counters(executor, engine)

    feed = serve.Feeder(engine, executor, plan, mix["loop"], seconds,
                        on_open=on_open,
                        trace_s=min(TRACE_S, seconds) if trace else None,
                        on_trace_end=on_trace_end,
                        stagger=engine.pcfg.n_stages)
    pauses = []                             # the collector's, in the run

    def on_gc(phase, info):
        if phase == "start":
            pauses.append([time.perf_counter(), 0.0, info["generation"]])
        elif pauses:
            pauses[-1][1] = time.perf_counter() - pauses[-1][0]

    gc.callbacks.append(on_gc)
    try:
        feed.run(jax.random.fold_in(model.seed_key(seed), 3))
    finally:
        gc.callbacks.remove(on_gc)
    if feed.mark is None:                   # the trace ran to the close
        state["traced_to"] = feed.t_close
        jax.profiler.stop_trace()
        feed.mark, feed.steps_since_mark = feed.t_open, feed.timesteps
    in_window = programs(clock) - state["compiles"]
    compile_s = clock.seconds - state["compile_s"]
    calls = counters(executor, engine)
    calls.subtract(state["calls"])
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices)
    kind = devices[0].device_kind
    peak = fl.peaks(kind)
    models = (target_w, draft_w, peak)
    useful, _, _ = serve.verify_work(feed.calls, *models, feed.mark,
                                     feed.t_close)
    _, least, least_calls = serve.verify_work(
        feed.calls, *models, feed.t_open, state.get("traced_to", feed.t_open))
    late = feed.lateness_ms()
    step_ms, step_at = feed.longest_timestep()
    in_gc = [(d, g) for t, d, g in pauses if t >= feed.t_open]
    say(f"window: {feed.t_close - feed.t_open:.3f} s, "
        f"{feed.timesteps} timesteps, {feed.window_tokens()} tokens, "
        f"{feed.attempted()} requests attempted, "
        f"{len(feed.completed)} completed, {feed.waiting()} waiting "
        f"at the close")
    say(f"programs compiled or loaded inside the window: {in_window}, "
        f"{compile_s:.3f} s of backend compile (must be 0)")
    say(f"timesteps: median {serve.percentile(
        [(b - a) * 1e3 for a, b in zip(feed.step_t, feed.step_t[1:])], 50)
        or 0.0:.1f} ms, longest {step_ms:.1f} ms at {step_at:.1f} s; "
        f"garbage collections in the window: {len(in_gc)}, longest "
        f"{max(in_gc, default=(0.0, 0))[0] * 1e3:.1f} ms (generation "
        f"{max(in_gc, default=(0.0, 0))[1]}), "
        f"{sum(d for d, _ in in_gc) * 1e3:.1f} ms in all")
    say(f"generator lateness: {len(late)} requests, max "
        f"{max(late, default=0.0):.3f} ms, p50 "
        f"{serve.percentile(late, 50) or 0.0:.3f} ms")

    summary = None
    if trace:
        summary = reduce(load(find(trace_dir)))
        say(f"launch spans paired with device runs: "
            f"{summary.launched_calls or 'none'}; verify calls logged: "
            f"{least_calls}")
        if not keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    # host-clock readings of a traced run leave the traced part out
    reading = Reading(
        setup_s=state["setup_s"], window_s=feed.t_close - feed.mark,
        timesteps=feed.steps_since_mark,
        tokens=feed.window_tokens(feed.mark),
        tbt_ms=feed.token_gaps_ms(feed.mark),
        dispatches=sum(v for v in calls.values() if v > 0),
        useful_flops=useful, least_s=least, least_calls=least_calls,
        chips=len(devices), peak=peak,
        trace=summary)
    metrics = {}
    for m in cell_metrics(bench, cell["name"], trace):
        value = reader(m["name"])(reading)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    attempted, waiting = feed.attempted(), feed.waiting()

    # the sampled requests' rows leave the device, then the program's
    # state goes before the reference runs beside the weights
    pick = sample(feed, seed) if check else []
    served = {u: feed.token_id[u] for u in pick}
    rows = feed.rows.fetch(pick)
    feed.engine = feed.executor = feed.rows = None
    del executor, engine
    gc.collect()
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(peak_mem)}
    out = {"correct": False, "attempted": attempted, "failed": 0,
           "metrics": metrics, "device": device, "waiting": waiting,
           "queue_wait_ms": feed.queue_wait_ms()}
    if summary is not None:
        device["busy_s"] = sum(summary.busy_s) / summary.chips
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
    if not check:
        return out
    t_check = time.perf_counter()
    found = check_served(served, {u: feed.plan[u].prompt for u in pick},
                         rows, {"target": target_w, "draft": draft_w},
                         control)
    say(f"reference check: {len(pick)} requests, "
        f"{time.perf_counter() - t_check:.2f} s")
    checks = judge(found, conf["limits"])
    out["correct"] = all(c["ok"] for c in checks.values())
    if control:
        out["stats"] = reading_stats(found)
    out["checks"] = checks
    for name, c in checks.items():
        say(f"check {name}: {c['value']} (limit {c['limit']}) "
            f"{'ok' if c['ok'] else 'FAILED'}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_benchmark()
    cells = {c["name"]: c for c in bench["workloads"]}
    if args.workload not in cells:
        say(f"unknown workload {args.workload!r}; known: {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    devices = tpu_devices(cell["chips"])
    say(f"devices: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}")
    import model
    import traffic
    out = run_cell(bench, cell, model.load_config(cell["config"]),
                   traffic.load_mix(cell["traffic"]), args.seed, args.seconds,
                   bool(args.trace), devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
