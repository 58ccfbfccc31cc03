"""Benchmark driver: one module per paper table/figure.

Prints `name,us_per_call,derived` CSV rows.  `--fast` trims the grids
(single dataset, fewer selectivities) for CI-style runs.
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig9,table6,fig10,fig11,fig12,fig13,"
                         "fig_planner,table2,table3,table4,table5,table7")
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()

    from benchmarks import (fig9_qps, fig10_breakdown, fig11_limit_k,
                            fig12_correlation, fig13_tmap, fig_planner,
                            table2_datasets, table3_build,
                            table4_hnsw_quant, table5_quant, table6_metrics,
                            table7_concurrency)
    from benchmarks.common import emit

    suites = {
        "table2": lambda: table2_datasets.run(),
        "table3": lambda: table3_build.run(
            ("sift10m",) if args.fast else ("sift10m", "openai5m")),
        "fig9": lambda: fig9_qps.run(
            ("sift10m",) if args.fast else ("sift10m", "openai5m"),
            (0.05, 0.3) if args.fast else fig9_qps.SELECTIVITIES),
        "table6": lambda: table6_metrics.run(
            sels=(0.01, 0.1, 0.5) if args.fast
            else table6_metrics.SELECTIVITIES),
        "fig10": lambda: fig10_breakdown.run(),
        "fig11": lambda: fig11_limit_k.run(),
        "fig12": lambda: fig12_correlation.run(),
        "fig13": lambda: fig13_tmap.run(),
        "fig_planner": lambda: fig_planner.run(
            sels=(0.05, 0.5) if args.fast else fig_planner.SELS,
            corrs=("none",) if args.fast else fig_planner.CORRS)[0],
        "table4": lambda: table4_hnsw_quant.run(),
        "table5": lambda: table5_quant.run(),
        "table7": lambda: table7_concurrency.run(),
    }
    chosen = (args.only.split(",") if args.only else list(suites))
    t0 = time.time()
    failures = 0
    for name in chosen:
        print(f"# --- {name} ---", flush=True)
        try:
            t1 = time.time()
            rows = suites[name]()
            emit(rows, name)
            print(f"# {name}: {len(rows)} rows in {time.time()-t1:.0f}s",
                  flush=True)
        except Exception as e:  # keep the suite running
            failures += 1
            import traceback
            traceback.print_exc()
            print(f"# {name}: FAILED {type(e).__name__}: {e}", flush=True)
    print(f"# total {time.time()-t0:.0f}s, failures={failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    main()
