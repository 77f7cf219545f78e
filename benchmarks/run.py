"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus human-readable detail).
Figures:
  fig3  draft top-k "scale effect"          (paper Fig. 3)
  fig4  tree-parameter sweep                (paper Fig. 4, Fig. 6 acceptance)
  fig5  PP / STPP / PipeDec latency         (paper Fig. 5)
  fig7  stochastic decoding                 (paper Fig. 7)
  fig8  throughput vs concurrency           (paper Fig. 8)
  roofline  dry-run roofline table          (EXPERIMENTS.md §Roofline)
  kernels   Pallas kernel micro-bench
"""
from __future__ import annotations

import sys


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig3_topk, fig4_tree_params, fig5_latency,
                            fig6_accuracy, fig7_stochastic, fig8_throughput,
                            kernels_bench, roofline)
    modules = [fig3_topk, fig4_tree_params, fig5_latency, fig6_accuracy,
               fig7_stochastic, fig8_throughput, roofline, kernels_bench]
    rows = []
    for mod in modules:
        try:
            rows.extend(mod.run(verbose=True))
        except Exception as e:  # keep the harness alive; report the failure
            rows.append((mod.__name__.split(".")[-1], 0.0,
                         f"ERROR:{type(e).__name__}:{e}"))
    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    if any(str(r[2]).startswith("ERROR") for r in rows):
        sys.exit(1)


if __name__ == "__main__":
    main()
