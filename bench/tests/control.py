"""The control on the chip at a cell's own size: for each seed, one run
of the cell (its own window and load) whose kept logit rows are checked
against the fp32 reference, and the bfloat16 control read on the same
positions.  One process, so set-up compiles once.

  python3 bench/tests/control.py q25-7b.chat-batch 45 SEED [SEED ...]
  python3 bench/tests/control.py qwen25-32b:code-poisson 45 SEED [SEED ...]

Prints one JSON line per seed: for the target and the draft, each
statistic's largest and median reading over the checked rows, the
program's (the lower readings of the limits) and the control's (under
``control_``: the upper readings), and whether the run read correct."""
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402,F401  (puts bench/ and src/ on the path)

import model  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402


def main(cell_name: str, seconds: float, seeds) -> int:
    bench = run.load_benchmark()
    cell = run.find_cell(bench, cell_name)
    devices = run.tpu_devices(cell["chips"])
    for seed in seeds:
        out = run.run_cell(bench, cell, model.load_config(cell["config"]),
                           traffic.load_mix(cell["traffic"]), seed,
                           seconds, False, devices, control=True)
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "stats": out["stats"], "checks": out["checks"],
                          "metrics": out["metrics"]}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], float(sys.argv[2]),
                  [int(s) for s in sys.argv[3:]]))
