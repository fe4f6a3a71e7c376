"""PackageQueryEngine: the public API tying the pipeline together.

    engine = PackageQueryEngine(table, attrs, d_f=100, alpha=100_000)
    engine.partition()                       # offline: build the hierarchy
    result = engine.solve(query)             # Progressive Shading
    base   = engine.solve_direct(query)      # black-box ILP (Gurobi stand-in)
    sr     = engine.solve_sketchrefine(query)

``table`` may be a dict of resident numpy columns or any
:class:`~repro.core.relation.Relation` (e.g. ``MemmapRelation`` over an
on-disk matrix).  Streamed relations run the whole pipeline out-of-core:
layer 0 is partitioned through the bucketing backend (Appendix D.2,
``memory_rows`` bounding the resident set), the shading cascade passes
candidate-id subsets down, and Dual Reducer / validation gather only the
<= alpha candidate rows — an end-to-end solve holds O(alpha +
memory_rows) rows resident.  ``solve_direct``/``lp_bound`` assemble their
full-relation form chunk-wise behind a size guard (they are the
full-materialisation baselines by definition).
"""
from __future__ import annotations

import time
from typing import Optional, Sequence

import numpy as np

from repro.core import guard
from repro.core import ilp as ilp_mod
from repro.core.dual_reducer import PackageResult
from repro.core.hierarchy import Hierarchy
from repro.core.lp import OPTIMAL, solve_lp_np
from repro.core.paql import PackageQuery
from repro.core.relation import Relation, as_relation, io_retry_count
from repro.core.shading import progressive_shading
from repro.core.sketchrefine import sketch_refine


class PackageQueryEngine:
    def __init__(self, table, attrs: Sequence[str],
                 *, d_f: int = 100, alpha: int = 100_000,
                 seed: int = 0, partitioner_backend: str = "dlv",
                 layer0_backend: Optional[str] = None,
                 chunk_rows: Optional[int] = None,
                 memory_rows: Optional[int] = None, mesh=None,
                 cache=None):
        self.table: Relation = as_relation(table, columns=list(attrs))
        self.attrs = list(attrs)
        self.d_f = d_f
        self.alpha = alpha
        self.partitioner_backend = partitioner_backend
        self.layer0_backend = layer0_backend
        self.chunk_rows = chunk_rows
        self.memory_rows = memory_rows
        self.mesh = mesh
        self.rng = np.random.default_rng(seed)
        self.hierarchy: Optional[Hierarchy] = None
        # cross-query artifact cache: True -> a private QCache; or pass a
        # QCache instance shared across engines (the serving-layer shape)
        if cache is True:
            from repro.core.qcache import QCache
            cache = QCache()
        # identity test, not truthiness: an empty QCache has len() == 0
        self.cache = None if cache in (None, False) else cache

    @property
    def n(self) -> int:
        return self.table.num_rows

    def session(self, seed: int = 0) -> "PackageQueryEngine":
        """A per-session engine sharing this engine's table, hierarchy
        and cross-query cache, with a PRIVATE rng.

        The serving-layer shape: one resident engine (partitioned once)
        serves many concurrent sessions — ``engine.rng`` is the only
        unshareable state (a numpy Generator is not thread-safe and its
        draw order must stay per-session deterministic), so each session
        gets its own seeded Generator while the heavy shared structures
        (Relation, Hierarchy, QCache — each thread-safe or read-only
        after partition) stay common.
        """
        import copy
        s = copy.copy(self)
        s.rng = np.random.default_rng(seed)
        return s

    @property
    def partition_time_s(self) -> float:
        """Seconds of the hierarchy build (its ``build`` span); 0 before."""
        return self.hierarchy.spans.seconds("build") \
            if self.hierarchy is not None else 0.0

    def partition(self) -> "PackageQueryEngine":
        self.hierarchy = Hierarchy(self.table, self.attrs, d_f=self.d_f,
                                   alpha=self.alpha, rng=self.rng,
                                   backend=self.partitioner_backend,
                                   layer0_backend=self.layer0_backend,
                                   chunk_rows=self.chunk_rows,
                                   memory_rows=self.memory_rows,
                                   mesh=self.mesh)
        return self

    # ------------------------------------------------------------ solvers
    def solve(self, query: PackageQuery, *, dr_q: int = 500,
              ilp_kwargs: Optional[dict] = None,
              budget: Optional[guard.SolveBudget] = None,
              guarded: bool = True,
              **ps_kwargs) -> PackageResult:
        """Progressive Shading (the paper's algorithm).  Extra kwargs are
        the ablation knobs of progressive_shading (layer_solver, sampler,
        dr_aux).

        Guarded by default: every call returns a PackageResult carrying a
        ``guard.SolveReport`` (``res.report``) with a defined status —
        ok / degraded / infeasible / budget_exhausted / error — and never
        raises; ``budget=`` (a ``guard.SolveBudget``) bounds the whole
        cascade end to end.  ``guarded=False`` disables the degradation
        ladder and re-raises exceptions (the unguarded baseline for the
        robustness bench).

        With a ``cache`` (engine knob), solves consult the cross-query
        artifact cache before descending and populate it after clean
        solves; hit/miss/prune counters land on ``res.report``."""
        if self.hierarchy is None:
            self.partition()
        if self.cache is not None:
            self.cache.register(self.hierarchy)
        t0 = time.time()
        report = guard.SolveReport(budget=budget or guard.SolveBudget(),
                                   monitor=guard.NumericalMonitor())
        report.budget.start()
        io0 = io_retry_count()
        with report.spans.span("solve") as ann:
            try:
                res = progressive_shading(self.hierarchy, query, self.table,
                                          alpha=self.alpha, dr_q=dr_q,
                                          rng=self.rng,
                                          ilp_kwargs=ilp_kwargs,
                                          budget=report.budget,
                                          report=report, ladder=guarded,
                                          qcache=self.cache, **ps_kwargs)
            # repro: allow[REPRO004] guard contract: guarded solve must
            # never raise -- contain, report, and return an empty
            # (infeasible) result
            except Exception as e:
                if not guarded:
                    raise
                # guard contract: never raise — contain, report, return
                # empty
                report.status = guard.ERROR
                report.note(f"error: {type(e).__name__}: {e}")
                res = PackageResult(False, np.zeros(0, np.int64),
                                    np.zeros(0), 0.0, 0.0, status="error")
            # the counters ride on the trace's pq.solve event
            ann.set_metadata(
                ilp_lp_pivots=report.ilp_lp_pivots,
                ilp_node_lp_s=report.ilp_node_lp_s,
                ilp_node_lps=report.ilp_node_lps,
                ilp_node_lps_carried=report.ilp_node_lps_carried,
                ilp_capped=report.ilp_capped)
        report.fault_retries = io_retry_count() - io0
        res.report = report.finalize(res.feasible)
        res.status += f" t={time.time() - t0:.3f}s"
        return res

    def solve_direct(self, query: PackageQuery,
                     ilp_kwargs: Optional[dict] = None) -> PackageResult:
        """Black-box ILP over the full relation (the Gurobi role).  The
        standard form streams chunk-wise off a Relation; a size guard
        raises for relations too large to hold densely."""
        c, A, bl, bu, ub = query.matrices(self.table, None)
        res = ilp_mod.solve_ilp(c, A, bl, bu, ub, **(ilp_kwargs or {}))
        if not res.feasible:
            return PackageResult(False, np.zeros(0, np.int64), np.zeros(0),
                                 0.0, 0.0, status="ilp_infeasible")
        nz = res.x > 0.5
        obj = -res.obj if query.maximize else res.obj
        lp_obj = -res.lp_obj if query.maximize else res.lp_obj
        return PackageResult(True, np.flatnonzero(nz), res.x[nz], obj,
                             lp_obj, status="ok")

    def solve_sketchrefine(self, query: PackageQuery,
                           tau_frac: float = 0.001,
                           ilp_kwargs: Optional[dict] = None) -> PackageResult:
        return sketch_refine(query, self.table, self.attrs,
                             tau_frac=tau_frac, ilp_kwargs=ilp_kwargs,
                             memory_rows=self.memory_rows,
                             chunk_rows=self.chunk_rows)

    def lp_bound(self, query: PackageQuery) -> float:
        """LP relaxation over the full relation (integrality-gap metric).
        Streams its matrix assembly like solve_direct (same size guard)."""
        c, A, bl, bu, ub = query.matrices(self.table, None)
        res = solve_lp_np(c, A, bl, bu, ub, max_iters=20000)
        if res.status != OPTIMAL:
            return np.nan
        return -res.obj if query.maximize else res.obj
