"""Find the highest arrival rate an open-loop cell sustains: run the
cell at each given rate (its mix otherwise as committed, with
rate x 60 s requests in flight at the open) in one process, with no
check against the reference, and print, per rate, the requests still
waiting at the close, the waits from due to admission and the
end-to-end metrics.  Each rate runs in a process of its own (a second
serving stack beside the first does not fit a 32B cell's chip); this
parent never touches JAX.  The knee is the highest rate whose backlog
does not grow; the committed rate is four fifths of it.

  python3 bench/tests/sweep.py qwen25-32b:code-poisson 90 SEED RATE [RATE ...]

A cell not in ``BENCHMARK.json`` is named ``<config>:<traffic>``.
"""
import json
import os
import subprocess
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import tiny  # noqa: E402,F401  (puts bench/ and src/ on the path)

import model  # noqa: E402
import run  # noqa: E402
import traffic  # noqa: E402


def one_rate(cell_name: str, seconds: float, seed: int, rate: float) -> int:
    bench = run.load_benchmark()
    cell = run.find_cell(bench, cell_name)
    devices = run.tpu_devices(cell["chips"])
    mix = traffic.load_mix(cell["traffic"])
    swept = {**mix, "rate_per_s": rate,
             "in_flight_at_open": min(round(rate * 60), 16)}
    out = run.run_cell(bench, cell, model.load_config(cell["config"]),
                       swept, seed, seconds, False, devices, check=False)
    print(json.dumps({"rate_per_s": rate, "attempted": out["attempted"],
                      "waiting": out["waiting"],
                      "queue_wait_ms": out["queue_wait_ms"],
                      "metrics": out["metrics"]}), flush=True)
    return 0


def main(argv) -> int:
    if len(argv) == 4:
        return one_rate(argv[0], float(argv[1]), int(argv[2]),
                        float(argv[3]))
    for rate in argv[3:]:
        subprocess.run([sys.executable, os.path.abspath(__file__),
                        *argv[:3], rate], check=False)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
