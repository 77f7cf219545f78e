"""One traced run of a cell, read through the program's own spans and
counters beside the harness's result.  On a TPU host:

  python3 bench/tests/phase_run.py CELL OUT_DIR SEED SECONDS \
      [--steps N] [--python-tracer]

runs the cell as ``run.py --trace 1`` does, keeping the trace under
OUT_DIR/trace, and prints one JSON line: the harness's result, the
program's counters (``DBStats``) over the run's untraced part (from the
first timestep begun after the profiler stopped to the close), and the
phase reduction of the trace (``phases.py``).  The profiler's Python
tracer stays off unless ``--python-tracer`` (``run.py`` leaves it on, and
it slows a traced timestep by about a third: PERF.md).  OUT_DIR/
phases.json.gz keeps the first N (default 3) traced timesteps, the form
``data/phases_q25-7b.json.gz`` was recorded in."""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402,F401  (puts bench/ and src/ on the path)

import jax  # noqa: E402

import model  # noqa: E402
import phases  # noqa: E402
import run  # noqa: E402
import serve  # noqa: E402
import traffic  # noqa: E402
from trace import find  # noqa: E402

COUNTERS = ("timesteps", "hits", "misses", "tokens_committed")


def logged_build(log: list, state: dict):
    """``serve.build`` that notes the engine's counters as each timestep
    begins (inside the harness's admission seam) and keeps the live
    ``DBStats`` for a reading at the close."""
    real = serve.build

    def build(*args, **kwargs):
        executor, engine = real(*args, **kwargs)
        admit = engine.sched.admit

        def noted(now):
            state["stats"] = engine.stats
            log.append((time.perf_counter(),
                        *(getattr(engine.stats, k) for k in COUNTERS)))
            return admit(now)

        engine.sched.admit = noted
        return executor, engine
    return build


def untraced_counters(log, stats, stopped: float) -> dict:
    """The counters' growth from the first timestep begun after the
    profiler stopped to the close, and what they give."""
    first = next(row for row in log if row[0] >= stopped)
    d = {k: getattr(stats, k) - v for k, v in zip(COUNTERS, first[1:])}
    decided = d["hits"] + d["misses"]
    return {**d,
            "tokens_per_timestep": d["tokens_committed"] / d["timesteps"]
            if d["timesteps"] else None,
            "acceptance_rate": 100.0 * d["hits"] / decided
            if decided else None}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("out")
    ap.add_argument("seed", type=int)
    ap.add_argument("seconds", type=float)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--python-tracer", action="store_true")
    args = ap.parse_args(argv)
    bench = run.load_benchmark()
    cell = run.find_cell(bench, args.cell)
    devices = run.tpu_devices(cell["chips"])
    log, state, stops = [], {}, []
    serve.build = logged_build(log, state)
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = int(args.python_tracer)
    jax.profiler.start_trace = lambda path: start(path,
                                                  profiler_options=options)

    def noted_stop():
        stops.append(time.perf_counter())
        stop()

    jax.profiler.stop_trace = noted_stop
    trace_dir = os.path.join(args.out, "trace")
    res = run.run_cell(bench, cell, model.load_config(cell["config"]),
                       traffic.load_mix(cell["traffic"]), args.seed,
                       args.seconds, True, devices, keep_trace=trace_dir)
    rec = phases.load(find(trace_dir))
    phases.save(phases.trim(rec, args.steps),
                os.path.join(args.out, "phases.json.gz"))
    red = phases.reduce(rec)
    print(f"device clock offset: {red['offset_ms']:.4f} ms from "
          f"{red['runs_matched']} runs", file=sys.stderr)
    print(json.dumps({
        "cell": args.cell, "seed": args.seed, "seconds": args.seconds,
        "python_tracer": args.python_tracer, "result": res,
        "program": untraced_counters(log, state["stats"], stops[0]),
        "phases": {**red, "metrics": phases.metrics(red)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
