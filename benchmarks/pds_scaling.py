"""Paper Mini-Experiment 3 (App. C): Parallel Dual Simplex behaviour.

This container has one CPU core, so OpenMP-style core-count speedups are
not measurable; we report the quantities the TPU port is built around:

  * per-iteration wall time vs n (pricing + BFRT are O(n) vectorised),
  * BFRT long-step size: bound flips absorbed by the FIRST iteration
    (paper: ~n/2 single-step equivalents),
  * total simplex iterations to optimality (tiny, thanks to BFRT),
  * per-device collective bytes of the distributed pq_step (from the
    multi-pod dry-run artifacts, when present): O(num_buckets), not O(n).
"""
from __future__ import annotations

import glob
import json

import numpy as np

from benchmarks.common import emit, timed
from repro.core.lp import solve_lp_np


def run(full: bool = False):
    rng = np.random.default_rng(0)
    sizes = (10_000, 100_000, 1_000_000) if full else (10_000, 100_000)
    for n in sizes:
        c = rng.normal(size=n)
        A = np.stack([np.ones(n), rng.normal(14, 1.5, n),
                      rng.normal(10, 2.0, n)])
        E = 30
        bl = np.array([15.0, 14 * E - 9, -np.inf])
        bu = np.array([45.0, 14 * E + 9, 10 * E + 8])
        res, t = timed(solve_lp_np, c, A, bl, bu, np.ones(n))
        emit(f"miniexp3/pds/n{n}", t / max(res.iters, 1) * 1e6,
             f"iters={res.iters};status={res.status}")
    # BFRT long-step: flips in the first iteration
    n = 100_000
    c = -np.abs(rng.normal(size=n))       # maximize-like: everything wants up
    A = np.stack([rng.normal(14, 1.5, n)])
    bl = np.array([-np.inf])
    bu = np.array([14.0 * n * 0.5])       # forces ~half the vars to flip
    res, _ = timed(solve_lp_np, c, A, bl, bu, np.ones(n))
    emit("miniexp3/bfrt_longstep/n100000", 0.0,
         f"iters={res.iters};support={int((res.x > 0).sum())}")
    # distributed pq_step collective bytes (from dry-run artifacts)
    for f in sorted(glob.glob("results/dryrun/pq_step__*.json")):
        rec = json.load(open(f))
        if rec.get("status") == "OK":
            emit(f"miniexp3/pq_step/{rec['mesh']}", 0.0,
                 f"coll_bytes={rec['collectives'].get('total', 0):.3e};"
                 f"devices={rec['n_devices']}")
