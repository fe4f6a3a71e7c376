"""A whole run off the chip, at a small size, with the timed path broken
underneath: each fault the cells can have must make ``correct`` false,
as must the control (float32 in the program's place); the sound program
must not."""
import dataclasses

import numpy as np
import pytest

from bench.lib.harness import result_line, run
from repro.core.engine import PackageQueryEngine


class AnswerAltered(PackageQueryEngine):
    """One tuple of every package swapped for another where it is made."""

    def solve(self, query, **kw):
        res = super().solve(query, **kw)
        if len(res.idx):
            res.idx = res.idx.copy()
            res.idx[0] = next(i for i in range(self.n) if i not in res.idx)
        return res


class StateUnchanged(PackageQueryEngine):
    """Every query answered with the package of the query before it."""

    last = None

    def solve(self, query, **kw):
        res = super().solve(query, **kw)
        last, StateUnchanged.last = StateUnchanged.last, res
        if last is not None:
            res = dataclasses.replace(res, idx=last.idx, mult=last.mult,
                                      obj=last.obj)
        return res


class ObjectiveSwapped(PackageQueryEngine):
    """Each package optimises another column than the objective, and the
    objective is reported as the package's own: feasible, consistent,
    poor."""

    def solve(self, query, **kw):
        other = next(c.attr for c in query.constraints
                     if c.attr not in (None, query.objective_attr))
        res = super().solve(dataclasses.replace(query, objective_attr=other),
                            **kw)
        col = self.table.column(query.objective_attr)
        res.obj = float(np.sum(col[res.idx] * res.mult))
        return res


class HalfTheGroup(PackageQueryEngine):
    """Layer 1's representatives taken over the first half of each
    group's members, the rest left out."""

    def partition(self):
        super().partition()
        lay = self.hierarchy.layers[1]
        X = self.hierarchy.layers[0].X
        p = lay.part
        for g in range(p.num_groups):
            a, b = p.offsets[g], p.offsets[g + 1]
            p.reps[g] = X[p.order[a:a + max(1, (b - a) // 2)]].mean(axis=0)
        return self


class MemberMoved(PackageQueryEngine):
    """One tuple's group id changed after the build."""

    def partition(self):
        super().partition()
        p = self.hierarchy.layers[1].part
        p.gid[p.order[0]] = (p.gid[p.order[0]] + 1) % p.num_groups
        return self


def _run(cell, engine_cls=None, seconds=3.0, **kw):
    return run(cell, 20260, seconds, False, rows=cell.config["rows"],
               require_chip=False, engine_cls=engine_cls, **kw)


def test_sound_run_is_correct_and_control_is_not(small_cell):
    out = _run(small_cell(), control=True)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"], out["checks"]
    assert not all(v <= lim for v, lim in out["control_checks"].values())
    ctl = out["control_checks"]
    assert ctl["obj_rel_err"][0] > ctl["obj_rel_err"][1]
    assert ctl["rep_rel_err"][0] > ctl["rep_rel_err"][1]
    line = result_line(out)
    assert line.index('"checks"') > line.index('"device"')


@pytest.mark.parametrize("fault,broken", [
    (AnswerAltered, "obj_rel_err"), (StateUnchanged, None),
    (ObjectiveSwapped, "lp_gap"), (HalfTheGroup, "rep_rel_err"),
    (MemberMoved, "partition_faults")])
def test_fault_makes_correct_false(small_cell, fault, broken):
    StateUnchanged.last = None
    # a stale answer needs a query before it: a longer window
    out = _run(small_cell(), fault,
               seconds=12.0 if fault is StateUnchanged else 3.0)
    assert out["attempted"] > (fault is StateUnchanged)
    assert not out["correct"], out["checks"]
    if broken:
        v, lim = out["checks"][broken]
        assert v > lim


def test_sdss_small_run_is_correct(small_cell):
    out = _run(small_cell("sdss-apogee-10m", "q1-h1to7"))
    assert out["correct"], out["checks"]
    assert np.isfinite(out["metrics"]["query_p95_ms"]["value"])


def test_cached_sessions_with_repeats_are_correct(small_cell):
    """The shape of the cache cell listed first under Open questions:
    several closed-loop sessions over one QCache, with repeats and
    tightened variants."""
    cell = small_cell()
    cell.config["cache_bytes"] = 64 << 20
    cell.traffic.update(clients=3, repeat_share=0.3, variant_share=0.4,
                        variant_step=0.5)
    out = _run(cell)
    kinds = {r["kind"] for r in out["record"]["queries"]}
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 3 and "cold" in kinds
    assert sum(r["cache_hits"] + r["cache_misses"]
               for r in out["record"]["queries"]) > 0
