"""Benchmark orchestrator — one module per paper table/figure + extensions.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).

  table5               paper Table 5 (selection decisions)
  table6               paper Table 6 (NPB run parameters)
  fig1_2_suite_vs_k    paper Figs 1-2 (suite energy/runtime vs K)
  fig3_4_per_benchmark paper Figs 3-4 (per-benchmark energy/runtime vs K)
  scheduler_ablation   beyond-paper modes + fault-tolerance sweeps
  npb_kernels          the NPB-analogue workloads (verified, Mop/s)
  tpu_campaign         energy-aware placement of LM jobs on TPU tiers
  roofline_bench       per-cell roofline terms from the dry-run records
"""

from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (table5, table6, fig1_2_suite_vs_k,
                            fig3_4_per_benchmark, scheduler_ablation,
                            npb_kernels, tpu_campaign, roofline_bench,
                            dvfs_pareto)
    suites = [
        ("table5", table5.run),
        ("table6", table6.run),
        ("fig1_2", fig1_2_suite_vs_k.run),
        ("fig3_4", fig3_4_per_benchmark.run),
        # the scheduler-ablation suites come from the module's own registry
        # (single source — scheduler_ablation.main() writes the same rows
        # to BENCH_scheduler.json)
        *scheduler_ablation.SUITES,
        ("npb", npb_kernels.run),
        ("tpu_campaign", tpu_campaign.run),
        ("roofline", roofline_bench.run),
        ("dvfs_pareto", dvfs_pareto.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            for row in fn():
                n, us, derived = row
                print(f"{n},{us:.1f},{derived}")
        except Exception as e:   # noqa: BLE001
            failures += 1
            print(f"{name},0.0,ERROR:{e!r}")
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(f"{failures} benchmark suites failed")


if __name__ == "__main__":
    main()
