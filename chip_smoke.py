"""Chip smoke test: the package-query engine's main path on a TPU.

    python chip_smoke.py                  # one chip, 10M-row TPC-H relation
    python chip_smoke.py --chips 4        # the mesh path on a four-chip host
    JAX_PLATFORMS=cpu python chip_smoke.py --rows 200000   # host rehearsal

One chip, in order:

  relation  a TPC-H ``lineitem``-like relation (paper Table 2 columns)
            made from ``--seed``;
  build     ``PackageQueryEngine(...).partition()``: the segstats kernel
            and the DLV column scans of wide length classes (a round's
            thousands of short partitions) run on the chip; lone long
            spans (each layer's first rounds, Algorithm 7's samples) are
            cut on the host by the jump scan;
  kernels   segstats, pricing and BFRT select against their host twins
            at real widths, each lowered to a Mosaic ``tpu_custom_call``;
  default   three ``Q2_TPCH`` queries (hardness 1, 3, 5) through
            ``engine.solve`` with default settings;
  device    the same queries through the device LP engines (layer LPs
            on ``solve_lp``, B&B waves of 64 on ``solve_lp_batch``) plus
            one ``solve_lp_batch(backend="jax")`` flight certified lane by
            lane with ``verify_optimality``;
  kernel LP ``solve_lp_kernel`` on the Q2_TPCH LPs over 2,000 and
            100,000 tuples, each certified by ``verify_optimality`` and
            matched pivot for pivot to ``solve_lp_np``.

``--chips 4`` runs only the mesh path: the build with layer-0 stats
sharded over a 4-device mesh and the queries with every layer LP on
``solve_lp_dist``, both compared with the one-device build and packages.

Every solve must end ``ok`` and pass ``check_package``; on the device
paths every solve must also take exactly the ladder rungs the default
path took for that query (none, or the host-side recovery from an
infeasible representative-level LP that a three-layer hierarchy meets at
hardness 3 and above) and match its objective within 1e-6 relative.  The last
line of output is ``{"ok": true, "device": {...}}``, printed (with exit
code 0) only on a TPU after every phase passed.  On any other backend
the script needs an explicit small ``--rows``, runs every phase, and
exits 1.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DEFAULT_ROWS = 10_000_000      # about TPC-H SF 1.7 of lineitem
HOST_MAX_ROWS = 1_000_000      # off the chip, rehearse small only
ATTRS = ["price", "quantity", "discount", "tax"]
HARDNESS = (1, 3, 5)
D_F, ALPHA = 100, 100_000
REL_TOL = 1e-6
Q2_TPCH_ATTRS = ["quantity", "discount", "tax"]   # its constrained columns


class SmokeFailure(RuntimeError):
    pass


def require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Sums backend compile time as jax reports it."""

    def __init__(self):
        import jax.monitoring
        self.secs = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration
            self.count += 1


CLOCK = None                   # the process's CompileClock, set by main


@contextmanager
def phase(name: str):
    """Prints the phase's wall time and the compile time inside it."""
    t0, c0, k0 = time.perf_counter(), CLOCK.secs, CLOCK.count
    yield
    say(f"{name}: {time.perf_counter() - t0:.3f}s, of which compile "
        f"{CLOCK.secs - c0:.3f}s over {CLOCK.count - k0} executables")


def rel_diff(a: float, b: float) -> float:
    return abs(a - b) / max(1.0, abs(b))


# ------------------------------------------------------------------ phases


def build(table, seed, **kw):
    from repro.core.engine import PackageQueryEngine
    eng = PackageQueryEngine(table, ATTRS, d_f=D_F, alpha=ALPHA, seed=seed,
                             **kw)
    eng.partition()
    return eng


def queries(table):
    from repro.core.hardness import Q2_TPCH, column_stats, instantiate
    stats = column_stats(table, Q2_TPCH_ATTRS)
    return {h: instantiate(Q2_TPCH, stats, h) for h in HARDNESS}


def solve_all(eng, qs, table, seed, label, ref=None, **solve_kw):
    """One session per query, all with the same seed; every solve must
    end ``ok`` with a package the relation validates.  Given the default
    path's results ``ref``, each solve must also take the same ladder
    rungs and reach the same objective within ``REL_TOL``."""
    out = {}
    for h, q in qs.items():
        t0 = time.perf_counter()
        res = eng.session(seed).solve(q, **solve_kw)
        dt = time.perf_counter() - t0
        rep = res.report
        say(f"{label} h={h}: status={rep.status} obj={res.obj!r} "
            f"size={int(res.mult.sum())} time={dt:.3f}s {rep.summary()}")
        require(rep.status == "ok", f"{label} h={h}: status {rep.status} "
                f"({'; '.join(rep.notes)})")
        require(q.check_package(table, res.idx, res.mult),
                f"{label} h={h}: package fails check_package")
        if ref is not None:
            want = ref[h].report.fallbacks
            require(rep.fallbacks == want,
                    f"{label} h={h}: rungs {rep.fallbacks} vs {want}")
            d = rel_diff(res.obj, ref[h].obj)
            require(d <= REL_TOL, f"{label} h={h}: objective {res.obj!r} "
                    f"vs {ref[h].obj!r} (rel {d:.3g})")
        out[h] = res
    if ref is not None:
        say(f"{label}: same rungs as the reference path, objectives "
            f"within {REL_TOL}")
    return out


class RecordingLP:
    """An ``lp_solver`` that keeps every LPResult it returns."""

    def __init__(self, solver):
        self.solver = solver
        self.results = []

    def __call__(self, *a, **kw):
        res = self.solver(*a, **kw)
        self.results.append(res)
        return res


def kernel_parity(eng, table, seed, on_chip):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.lp import row_scaling
    from repro.kernels.bfrt import bfrt_histogram, bfrt_select
    from repro.kernels.ops import interpret_kernels
    from repro.kernels.pricing import pricing
    from repro.kernels.segstats import (segment_stats, segment_stats_np,
                                        segstats_partials)
    interp = interpret_kernels()
    require(interp != on_chip, "kernels interpret on the chip")
    rng = np.random.default_rng(seed)

    def lowered(fn, *args):
        txt = jax.jit(fn).lower(*args).as_text()
        require(("tpu_custom_call" in txt) == on_chip,
                f"{fn}: tpu_custom_call presence != on_chip")

    # segstats: the first 2^20 tuples of the layer-0 partition, in its
    # sorted group order, centered as DLV feeds them
    part = eng.hierarchy.layers[1].part
    X0 = eng.hierarchy.layers[0].X
    R = min(1 << 20, len(part.order))
    rows = part.order[:R]
    gid = part.gid[rows]
    ids = (gid - gid[0]).astype(np.int32)
    G = int(ids[-1]) + 1
    vals = X0[rows] - X0[rows].mean(axis=0)
    t0 = time.perf_counter()
    cnt, sm, sq = jax.device_get(segment_stats(
        jnp.asarray(vals, jnp.float32), jnp.asarray(ids), G,
        interpret=interp))
    dt = time.perf_counter() - t0
    v32 = vals.astype(np.float32).astype(np.float64)
    rc, rs, rq = segment_stats_np(v32, ids, G)
    _, sa, qa = segment_stats_np(np.abs(v32), ids, G)
    require(np.array_equal(cnt, rc), "segstats counts differ")
    es = np.max(np.abs(sm - rs) / (sa + 1e-30))
    eq = np.max(np.abs(sq - rq) / (qa + 1e-30))
    require(es < 1e-5 and eq < 1e-5, f"segstats sums off: {es:.3g} {eq:.3g}")
    lowered(partial(segstats_partials, interpret=interp),
            jnp.asarray(vals, jnp.float32), jnp.asarray(ids))
    say(f"kernel segstats ({R}, {vals.shape[1]}) -> {G} groups: counts "
        f"exact, sum err {es:.3g}, sumsq err {eq:.3g} (of sum |x|), "
        f"{dt:.3f}s")

    # pricing: the Q2_TPCH constraint rows over 100,000 tuples, m = 4
    q = queries(table)[3]
    n = min(100_000, len(next(iter(table.values()))))
    _, A_t, _, _, _ = q.matrices(table, np.arange(n))
    m = A_t.shape[0]
    f32 = np.float32
    A = (-(A_t * row_scaling(A_t)[:, None])).astype(f32)
    rho = rng.normal(size=m).astype(f32)
    d = np.abs(rng.normal(size=n)).astype(f32)
    state = rng.integers(0, 3, n).astype(np.int32)
    lo = np.zeros(n, f32)
    hi = rng.uniform(0.5, 1.5, n).astype(f32)
    s, tol = f32(-1.0), 1e-9
    t0 = time.perf_counter()
    alpha, ratio, cost = jax.device_get(pricing(
        A, rho, d, state, lo, hi, s, interpret=interp, tol=tol))
    dt = time.perf_counter() - t0
    # alpha against the f64 product of the same f32 operands, relative to
    # sum |rho_i A_ij|: an f32 dot product is exact only to e = 1e-6 of
    # that scale.  Ratios and costs are then held to the f64 host twin of
    # pricing_math on the exact alpha, within what an alpha off by e can
    # move them (the compiled kernel may round a cancelling alpha one way
    # for its output and another way inside)
    a_ref = rho.astype(np.float64) @ A.astype(np.float64)
    scale = np.abs(rho.astype(np.float64)) @ np.abs(A.astype(np.float64))
    e = 1e-6 * scale
    ea = np.max(np.abs(alpha - a_ref) / (e + 1e-30))
    require(ea < 1, f"pricing alpha off: {ea:.3g} of the f32 bound")
    sa = float(s) * alpha.astype(np.float64)
    elig = (state < 2) & (((state == 0) & (sa > tol))
                          | ((state == 1) & (sa < -tol)))
    require(np.array_equal(np.isfinite(ratio), elig),
            "pricing eligibility differs")
    w = (hi - lo).astype(np.float64)
    c_ref = np.abs(a_ref) * w
    ec = np.max(np.abs(cost - c_ref)[elig] / (w * e + 1e-6 * c_ref)[elig])
    sure = elig & (np.abs(a_ref) > 2 * e)      # sign of alpha beyond doubt
    r_ref = np.maximum(d / (float(s) * a_ref), 0.0)
    er = np.max(np.abs(ratio - r_ref)[sure]
                / (r_ref * (1e-6 + 2 * e / np.abs(a_ref)) + 1e-30)[sure])
    require(ec < 1 and er < 1,
            f"pricing cost/ratio off: {ec:.3g} / {er:.3g} of the f32 bound")
    lowered(partial(pricing, interpret=interp, tol=tol),
            A, rho, d, state, lo, hi, s)
    say(f"kernel pricing ({m}, {n}): alpha {ea:.3g}, cost {ec:.3g}, "
        f"ratio {er:.3g} of the f32 error bound, {int(elig.sum())} "
        f"eligible, {dt:.3f}s")

    # BFRT select over the priced row, against a sort-based walk; the
    # budget sits midway across a breakpoint of at least median cost, so
    # the kernel's f32 running sums cannot land on the other side
    order = np.argsort(ratio, kind="stable")
    cs = cost[order].astype(np.float64)
    csum = np.cumsum(cs)
    n_elig = int(elig.sum())
    big = np.flatnonzero(cs[:n_elig] >= np.median(cs[:n_elig]))
    k = int(big[np.searchsorted(big, n_elig // 3)])
    budget = 0.5 * (csum[k - 1] + csum[k])
    t0 = time.perf_counter()
    qk, flips, has_cross = jax.device_get(bfrt_select(
        jnp.asarray(ratio), jnp.asarray(cost), jnp.float32(budget),
        interpret=interp))
    dt = time.perf_counter() - t0
    q_ref = int(order[k])
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n)
    flips_ref = np.isfinite(ratio) & (rank < rank[q_ref])
    require(bool(has_cross) and int(qk) == q_ref,
            f"bfrt entering {int(qk)} vs {q_ref}")
    require(np.array_equal(flips, flips_ref), "bfrt flip set differs")
    edges = jnp.linspace(0.0, 1.0, 128, dtype=jnp.float32)
    lowered(partial(bfrt_histogram, interpret=interp),
            jnp.asarray(ratio), jnp.asarray(cost), edges)
    say(f"kernel bfrt ({n}, 128 buckets): entering {q_ref}, "
        f"{int(flips.sum())} flips, exact; {dt:.3f}s")


def batch_flight(table, qs, seed):
    """A K = 64 flight of bound variants on the batched device engine,
    each lane certified by ``verify_optimality`` and matched to
    ``solve_lp_np``."""
    import numpy as np

    from repro.core.lp import OPTIMAL, solve_lp_np, verify_optimality
    from repro.core.lp_batch import batch_stats, solve_lp_batch
    rng = np.random.default_rng(seed)
    N = len(next(iter(table.values())))
    ids = np.sort(rng.choice(N, size=min(2000, N), replace=False))
    c, A_t, bl, bu, ub = qs[3].matrices(table, ids)
    n, K = len(c), 64
    ubs = [np.where(rng.random(n) < 0.1, 0.0, ub) for _ in range(K)]
    d0 = batch_stats()["dispatches"]
    t0 = time.perf_counter()
    lanes = solve_lp_batch(c, A_t, bl, bu, ubs, backend="jax")
    dt = time.perf_counter() - t0
    require(batch_stats()["dispatches"] == d0 + 1, "flight not dispatched")
    n_opt = 0
    for k, (res, ub_k) in enumerate(zip(lanes, ubs)):
        ref = solve_lp_np(c, A_t, bl, bu, ub_k)
        require(res.status == ref.status,
                f"lane {k}: status {res.status} vs {ref.status}")
        if res.status != OPTIMAL:
            continue
        ok, why = verify_optimality(res, c, A_t, bl, bu, ub_k)
        require(ok, f"lane {k}: {why}")
        require(rel_diff(res.obj, ref.obj) <= REL_TOL,
                f"lane {k}: obj {res.obj!r} vs {ref.obj!r}")
        n_opt += 1
    require(n_opt > 0, "no optimal lane in the flight")
    say(f"solve_lp_batch(jax) flight n={n} K={K}: {n_opt} optimal lanes "
        f"certified, all statuses match solve_lp_np, {dt:.3f}s")


def kernel_lp(table, qs, seed):
    """``solve_lp_kernel`` (f32 pricing operands, f64 factor state) on
    the Q2_TPCH LPs over 2,000 and 100,000 tuples: each answer has
    ``solve_lp_np``'s status and pivot count, and an optimal one is
    certified by ``verify_optimality``."""
    import numpy as np

    from repro.core.lp import OPTIMAL, solve_lp_np, verify_optimality
    from repro.core.lp_kernel import solve_lp_kernel
    rng = np.random.default_rng(seed)
    N = len(next(iter(table.values())))
    for n in sorted({min(2000, N // 2), min(100_000, N // 2)}):
        ids = np.sort(rng.choice(N, size=n, replace=False))
        for h, q in qs.items():
            c, A_t, bl, bu, ub = q.matrices(table, ids)
            ref = solve_lp_np(c, A_t, bl, bu, ub)
            t0 = time.perf_counter()
            res = solve_lp_kernel(c, A_t, bl, bu, ub)
            dt = time.perf_counter() - t0
            require(res.status == ref.status and res.iters == ref.iters,
                    f"solve_lp_kernel n={n} h={h}: status {res.status}, "
                    f"{res.iters} pivots vs {ref.status}, {ref.iters}")
            if res.status == OPTIMAL:
                ok, why = verify_optimality(res, c, A_t, bl, bu, ub)
                require(ok, f"solve_lp_kernel n={n} h={h}: {why}")
                require(rel_diff(res.obj, ref.obj) <= REL_TOL,
                        f"solve_lp_kernel n={n} h={h}: obj {res.obj!r} vs "
                        f"{ref.obj!r}")
            say(f"solve_lp_kernel n={n} h={h}: status={res.status} "
                f"pivots={res.iters} obj={res.obj!r}, certified, as "
                f"solve_lp_np; {dt:.3f}s")


def one_chip(table, seed, on_chip):
    from repro.core.lp import solve_lp
    from repro.core.lp_batch import batch_cache_stats, batch_stats

    with phase("build"):
        eng = build(table, seed)
        say(f"layer sizes {[l.size for l in eng.hierarchy.layers]}")
    with phase("kernels"):
        kernel_parity(eng, table, seed, on_chip)

    qs = queries(table)
    with phase("default path"):
        ref = solve_all(eng, qs, table, seed, "default")

    with phase("device path"):
        lp = RecordingLP(solve_lp)
        d0 = batch_stats()["dispatches"]
        solve_all(eng, qs, table, seed, "device", ref,
                  lp_solver=lp, ilp_kwargs={"wave_width": 64})
        require(lp.results, "no layer LP ran on the device engine")
        say(f"device: {len(lp.results)} layer LPs on solve_lp, "
            f"{sum(r.iters for r in lp.results)} pivots, "
            f"{batch_stats()['dispatches'] - d0} B&B wave dispatches over "
            f"{batch_cache_stats()['misses']} batch shape classes")
    with phase("batch flight"):
        batch_flight(table, qs, seed)
    with phase("kernel LP"):
        kernel_lp(table, qs, seed)


def four_chips(table, seed, rows):
    import jax
    import numpy as np

    from repro.core.guard import HOST_FALLBACK
    from repro.core.lp import solve_lp
    from repro.core.partitioner import mesh_stats_counts
    from repro.launch.mesh import make_local_mesh
    require(len(jax.devices()) == 4, f"--chips 4 sees {len(jax.devices())} "
            "devices")
    mesh = make_local_mesh(data=4)
    chunk_rows = max(1 << 16, rows // 8)
    chunks = -(-rows // chunk_rows)
    require(chunks > 1, f"{rows} rows fit one chunk of {chunk_rows}: "
            "layer 0 would not take the mesh path")

    with phase("build 1 device"):
        one = build(table, seed, chunk_rows=chunk_rows)
    c0 = mesh_stats_counts()["chunks"]
    with phase("build 4 devices"):
        four = build(table, seed, chunk_rows=chunk_rows, mesh=mesh)
    ran = mesh_stats_counts()
    require(ran["chunks"] - c0 == chunks and ran["devices"] == 4,
            f"layer-0 stats ran {ran['chunks'] - c0} sharded chunks over "
            f"{ran['devices']} devices; want {chunks} over 4")
    say(f"layer-0 stats: {chunks} chunks of {chunk_rows} rows, each "
        "sharded over 4 devices")
    L1, L4 = one.hierarchy.layers, four.hierarchy.layers
    require(len(L1) == len(L4), "layer counts differ")
    for i in range(1, len(L1)):
        require(np.array_equal(L1[i].part.offsets, L4[i].part.offsets),
                f"layer {i} group offsets differ")
    e0 = np.max(np.abs(L1[1].X - L4[1].X) / (np.abs(L1[1].X) + 1e-30))
    say(f"builds agree: groups {[l.size for l in L4]}, offsets equal, "
        f"layer-1 reps rel err {e0:.3g}")

    qs = queries(table)
    with phase("1-device path"):
        ref = solve_all(one, qs, table, seed, "1 device")
    with phase("mesh path"):
        lp = RecordingLP(partial(solve_lp, mesh=mesh))
        solve_all(four, qs, table, seed, "mesh", ref, lp_solver=lp)
        require(lp.results, "no layer LP ran on the mesh")
        for r in lp.results:
            require(not any(s.startswith(HOST_FALLBACK) for s in r.notes),
                    f"an LP fell back to the host: {r.notes}")
            require(r.pivot_stats.get("shards") == 4,
                    f"an LP priced on {r.pivot_stats.get('shards')} shards")
        say(f"mesh: {len(lp.results)} layer LPs on solve_lp_dist over 4 "
            f"shards, {sum(r.iters for r in lp.results)} pivots, no host "
            "fallback")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--rows", type=int, default=None,
                    help=f"relation size (default {DEFAULT_ROWS:,} on a "
                    "TPU; required elsewhere)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro import hostdev
    if args.chips == 4:
        hostdev.ensure_host_devices(4)   # the host rehearsal's 4 devices
    hostdev.use_compile_cache()
    import jax
    import repro.core  # noqa: F401  (x64 on, as the engine runs)

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    say(f"devices: {len(jax.devices())} x {dev.platform} "
        f"({dev.device_kind})")
    if not on_chip and (args.rows is None or args.rows > HOST_MAX_ROWS):
        print(f"chip_smoke: no TPU found ({dev.platform}); pass --rows "
              f"<= {HOST_MAX_ROWS:,} to rehearse on the host",
              file=sys.stderr)
        return 1
    rows = args.rows or DEFAULT_ROWS
    global CLOCK
    CLOCK = CompileClock()

    from repro.data.synth_tables import make_table
    with phase("total"):
        with phase("relation"):
            table = make_table("tpch", rows, args.seed)
            say(f"tpch lineitem-like, {rows:,} rows x {len(table)} "
                f"columns, seed {args.seed}")
        if args.chips == 4:
            four_chips(table, args.seed, rows)
        else:
            one_chip(table, args.seed, on_chip)

    if not on_chip:
        print("chip_smoke: every phase passed on the host rehearsal; "
              "no TPU, so no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
