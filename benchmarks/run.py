"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig5,table2]

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    args = ap.parse_args()

    from . import kernel_micro, paper_figures, training_race

    benches = {
        "fig1": paper_figures.bench_fig1_transient,
        "table1": paper_figures.bench_table1_bounds,
        "fig2_3": paper_figures.bench_fig2_fig3_optimal_p,
        "fig4": paper_figures.bench_fig4_vs_baselines,
        "fig5": paper_figures.bench_fig5_delays,
        "fig11": paper_figures.bench_fig11_optimal_delays,
        "fig12": paper_figures.bench_fig12_3cluster,
        "table2": training_race.bench_table2_accuracy,
        "kernels": kernel_micro.bench_kernels,
    }
    selected = [s for s in args.only.split(",") if s] or list(benches)

    print("name,us_per_call,derived")
    failures = 0
    for key in selected:
        fn = benches[key]
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{key},0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
            continue
        for name, us, derived in rows:
            print(f"{name},{us:.1f},{derived}", flush=True)
        print(f"# {key} done in {time.time()-t0:.1f}s", file=sys.stderr, flush=True)
    if failures:
        raise SystemExit(f"{failures} benchmark group(s) failed")


if __name__ == "__main__":
    main()
