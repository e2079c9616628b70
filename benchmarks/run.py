"""Benchmark harness: one module per paper table/figure (+ microbenches).

Prints ``name,us_per_call,derived`` CSV (one row per measurement).

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig4 fig11 # subset by prefix
"""

from __future__ import annotations

import sys
import time

from benchmarks import (
    fig2_profile,
    fig4_baselines,
    fig5_exit_depth,
    fig6_pareto,
    fig7_exit_config,
    fig8_slo,
    fig9_model_combo,
    fig10_cross_platform,
    fig11_ablation,
    fig12_lattice,
    fig13_workloads,
    fig14_cluster,
    fig15_drift,
    fig16_timeline,
    fig17_seedband,
    micro_kernels,
    micro_scheduler,
    table1_accuracy,
)
from repro.runtime.compilation import enable_compile_cache

MODULES = {
    "fig2": fig2_profile,
    "table1": table1_accuracy,
    "fig4": fig4_baselines,
    "fig5": fig5_exit_depth,
    "fig6": fig6_pareto,
    "fig7": fig7_exit_config,
    "fig8": fig8_slo,
    "fig9": fig9_model_combo,
    "fig10": fig10_cross_platform,
    "fig11": fig11_ablation,
    "fig12": fig12_lattice,
    "fig13": fig13_workloads,
    "fig14": fig14_cluster,
    "fig15": fig15_drift,
    "fig16": fig16_timeline,
    "fig17": fig17_seedband,
    "micro_scheduler": micro_scheduler,
    "micro_kernels": micro_kernels,
}


def main() -> None:
    wanted = sys.argv[1:] or list(MODULES)
    enable_compile_cache()
    print("name,us_per_call,derived")
    t0 = time.time()
    for key in wanted:
        mod = MODULES.get(key)
        if mod is None:
            print(f"# unknown benchmark {key!r}; known: {sorted(MODULES)}",
                  file=sys.stderr)
            continue
        for row in mod.run():
            print(row.csv(), flush=True)
    print(f"# total_wall_s={time.time() - t0:.1f}", file=sys.stderr)


if __name__ == "__main__":
    main()
