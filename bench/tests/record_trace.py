"""Record the small chip trace that ``test_trace.py`` checks the trace
reduction against.  On a TPU host:

  python3 bench/tests/record_trace.py CELL OUT_DIR [SEED [SECONDS]]

runs the cell (seed 11, 4 s by default) with the profiler on and leaves
the trace under OUT_DIR, its program lines and bench spans as
OUT_DIR/trace.json.gz (the form ``data/`` keeps) and their reduction as
OUT_DIR/summary.json."""
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402,F401  (puts bench/ and src/ on the path)

import model  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402
from trace import find, load, reduce, save  # noqa: E402


def main(cell_name: str, out: str, seed: int = 11,
         seconds: float = 4.0) -> int:
    bench = run.load_benchmark()
    cell = run.find_cell(bench, cell_name)
    devices = run.tpu_devices(cell["chips"])
    res = run.run_cell(bench, cell, model.load_config(cell["config"]),
                       traffic.load_mix(cell["traffic"]), seed, seconds, True,
                       devices, keep_trace=out)
    planes = load(find(out))
    save(planes, os.path.join(out, "trace.json.gz"))
    summary = reduce(planes)
    with open(os.path.join(out, "summary.json"), "w") as f:
        json.dump({**dataclasses.asdict(summary), "result": res}, f,
                  indent=1)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    a = sys.argv[1:]
    sys.exit(main(a[0], a[1], int(a[2]) if len(a) > 2 else 11,
                  float(a[3]) if len(a) > 3 else 4.0))
