"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig8,...]

Prints ``name,us_per_call,derived`` CSV rows (also saved to
results/bench.csv).  A module that raises is reported as an ``ERROR:``
row, the others still run, and the harness then exits 1.  Default is
the quick profile (~10 min on one CPU core); --full runs the
paper-scale sweeps.
"""
import argparse
import os
import sys
import time

# Give the CPU host virtual devices BEFORE jax first initializes so the
# distributed-pricing section of appc_warm_start runs on a real multi-device
# mesh (no-op when XLA_FLAGS already pins a device count, e.g. on TPU).
from repro.hostdev import ensure_host_devices, use_compile_cache

ensure_host_devices()
use_compile_cache()

from benchmarks import (ablations, analysis_bench, batch_lp, cache_bench,
                        concurrency_bench, dual_reducer_bench, grid,
                        infeasibility, partitioning, pds_scaling,
                        ratio_score, roofline, scaling, warm_start)
from benchmarks.common import ROWS

MODULES = {
    "fig7_ratio_score": ratio_score,
    "fig8_scaling": scaling,
    "fig9_infeasibility": infeasibility,
    "table3_grid": grid,
    "miniexp1_2_4_ablations": ablations,
    "miniexp3_pds": pds_scaling,
    "miniexp5_partitioning": partitioning,
    "miniexp7_8_dual_reducer": dual_reducer_bench,
    "appc_warm_start": warm_start,
    "cache": cache_bench,
    "concurrency": concurrency_bench,
    "batch_lp": batch_lp,
    "roofline": roofline,
    "analysis": analysis_bench,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="")
    args = ap.parse_args()
    only = [s for s in args.only.split(",") if s]
    t0 = time.time()
    failed = []
    print("name,us_per_call,derived")
    for name, mod in MODULES.items():
        if only and not any(o in name for o in only):
            continue
        print(f"# === {name} ===", flush=True)
        t = time.time()
        try:
            mod.run(full=args.full)
        # repro: allow[REPRO004] harness by design: record and continue
        except Exception as e:  # keep the harness going; record the failure
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", flush=True)
            failed.append(name)
        print(f"# {name} took {time.time() - t:.1f}s", flush=True)
    os.makedirs("results", exist_ok=True)
    with open("results/bench.csv", "w") as f:
        f.write("name,us_per_call,derived\n")
        f.write("\n".join(ROWS) + "\n")
    print(f"# total {time.time() - t0:.1f}s; {len(ROWS)} rows -> results/bench.csv")
    if failed:
        print(f"# FAILED: {','.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == '__main__':
    main()
