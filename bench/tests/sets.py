"""The spread of a cell: two sets of runs on the same seeds, then traced
runs, each ``bench/run.py`` as its own process (the way the benchmark is
run; this parent never touches JAX, so the chip stays free for them).

  python3 bench/tests/sets.py CELL SECONDS OUT_DIR --seeds S1 ... S6 \
      [--trace-seeds T1 T2 T3] [--root DIR]

Writes each run's result line to ``OUT_DIR/<cell>.jsonl`` and the end of
its standard error to ``OUT_DIR/<cell>.<n>.err``, and prints, per set and
metric, the median and the spread: the distance between the first and
third quartiles (``statistics.quantiles(values, n=4)``) over the median.
``--root`` runs the benchmark from another checkout (a ``git archive``
of the tree)."""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ERR_TAIL = 6000


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(root, cell, seed, seconds, trace, out_dir, n):
    t = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        cwd=root, capture_output=True, text=True)
    with open(os.path.join(out_dir, f"{cell}.{n}.err"), "w") as f:
        f.write(proc.stderr[-ERR_TAIL:])
    lines = proc.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"n": n, "seed": seed, "trace": trace, "rc": proc.returncode,
            "wall_s": time.perf_counter() - t, "result": result}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("cell")
    ap.add_argument("seconds", type=float)
    ap.add_argument("out_dir")
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)
    os.makedirs(args.out_dir, exist_ok=True)
    runs = ([(1, s, 0) for s in args.seeds] + [(2, s, 0) for s in args.seeds]
            + [(0, s, 1) for s in args.trace_seeds])
    done = []
    with open(os.path.join(args.out_dir, f"{args.cell}.jsonl"), "a") as log:
        for n, (set_no, seed, trace) in enumerate(runs):
            r = one_run(args.root, args.cell, seed, args.seconds, trace,
                        args.out_dir, n)
            r["set"] = set_no
            log.write(json.dumps(r) + "\n")
            log.flush()
            res = r["result"] or {}
            print(json.dumps({"n": n, "set": set_no, "seed": seed,
                              "trace": trace, "rc": r["rc"],
                              "wall_s": round(r["wall_s"], 1),
                              "correct": res.get("correct"),
                              "metrics": {k: v["value"] for k, v in
                                          res.get("metrics", {}).items()},
                              "checks": {k: v["value"] for k, v in
                                         res.get("checks", {}).items()}}),
                  flush=True)
            done.append(r)
    for set_no in (1, 2):
        rows = [r["result"] for r in done if r["set"] == set_no
                and r["result"]]
        names = sorted({k for res in rows for k in res["metrics"]})
        for k in names:
            vals = [res["metrics"][k]["value"] for res in rows
                    if k in res["metrics"]]
            if len(vals) >= 2:
                print(json.dumps({"set": set_no, "metric": k,
                                  "median": statistics.median(vals),
                                  "spread": spread(vals), "values": vals}),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
