"""Benchmark entry point: one section per paper table/figure and serving
feature.  Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # full (tee'd in CI)
    PYTHONPATH=src python -m benchmarks.run --quick    # fast smoke (= CI)
    REPRO_QUICK=1 PYTHONPATH=src python -m benchmarks.run  # same, via env
"""
from __future__ import annotations

import argparse
import os
import time
import traceback

MODULES = [
    "benchmarks.cost_model_fit",
    "benchmarks.fig6_tiling",
    "benchmarks.fig7_uniform",
    "benchmarks.fig8_granularity",
    "benchmarks.fig9_sot",
    "benchmarks.fig10_threshold",
    "benchmarks.fig11_workloads",
    "benchmarks.fig12_upfront",
    "benchmarks.fig_serving",
    "benchmarks.fig_cache",
    "benchmarks.fig_roi",
    "benchmarks.fig_tuning",
    "benchmarks.fig_server",
    "benchmarks.fig_cluster",
    "benchmarks.fig_repair",
]


def main() -> None:
    import importlib

    ap = argparse.ArgumentParser(description="TASM benchmark suite")
    ap.add_argument("--quick", action="store_true",
                    help="smoke sizes + soft latency gates, exactly what CI "
                         "runs (sets REPRO_QUICK=1 so local runs match CI "
                         "without exporting env vars by hand)")
    ap.add_argument("--only", metavar="SUBSTR", default=None,
                    help="run only modules whose name contains SUBSTR")
    args = ap.parse_args()
    if args.quick:
        # before any benchmark module is imported: they read the env at
        # import time to size their workloads
        os.environ["REPRO_QUICK"] = "1"
    modules = [m for m in MODULES if args.only is None or args.only in m]

    t_start = time.time()
    failures = []
    for mod_name in modules:
        print(f"# === {mod_name} ===", flush=True)
        t0 = time.time()
        try:
            mod = importlib.import_module(mod_name)
            mod.main()
        except Exception as e:  # noqa: BLE001 - benchmark isolation
            failures.append(mod_name)
            print(f"{mod_name},0.0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc()
        print(f"# {mod_name} took {time.time() - t0:.1f}s", flush=True)
    print(f"# total {time.time() - t_start:.1f}s; failures: {failures or 'none'}",
          flush=True)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
