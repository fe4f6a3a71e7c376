"""Spans and counters of a solve (SolveReport.spans, the ILP counters)
and of a hierarchy build (Hierarchy.spans), and their copy in a
profiler trace."""
import glob
import os

import numpy as np
import pytest

from repro.core import ilp as ilp_mod
from repro.core.engine import PackageQueryEngine
from repro.core.hardness import Q2_TPCH, column_stats, instantiate
from repro.core.spans import SpanLog
from repro.data.synth_tables import make_table

ATTRS = ["price", "quantity", "discount", "tax"]
ILP_KW = dict(max_nodes=200, time_limit_s=15)
PHASES = ("shade", "dr.lp", "ilp.incumbent", "ilp.search")


@pytest.fixture(scope="module")
def engine():
    table = make_table("tpch", 12_000, seed=1)
    eng = PackageQueryEngine(table, ATTRS, d_f=20, alpha=100, seed=0)
    return eng.partition(), column_stats(table, ATTRS)


def _names(log):
    return [s.name for s in log.spans]


def test_span_log_nests_and_subtracts_children():
    log = SpanLog()
    with log.span("a"):
        with log.span("b"):
            pass
        with log.span("b"):
            with log.span("c"):
                pass
    with log.span("a"):
        pass
    log.add("k")
    log.add("k", 2)
    assert [(s.name, s.parent) for s in log.spans] == [
        ("a", -1), ("b", 0), ("b", 0), ("c", 2), ("a", -1)]
    for s in log.spans:
        assert 0 <= s.t0_ns <= s.t1_ns
    parent, child = log.spans[0], log.spans[1]
    assert parent.t0_ns <= child.t0_ns and child.t1_ns <= parent.t1_ns
    b = sum(s.t1_ns - s.t0_ns for s in log.spans[1:3]) * 1e-9
    assert log.seconds("b") == pytest.approx(b)
    assert 0 <= log.seconds("a", own=True) <= log.seconds("a")
    assert log.seconds("a", own=True) == pytest.approx(
        log.seconds("a") - b)
    assert log.counters == {"k": 3}


def test_span_closes_on_exception():
    log = SpanLog()
    with pytest.raises(ValueError):
        with log.span("a"):
            raise ValueError
    with log.span("b"):
        pass
    assert [(s.name, s.parent) for s in log.spans] == [("a", -1), ("b", -1)]
    assert log.spans[0].t1_ns >= log.spans[0].t0_ns


@pytest.mark.parametrize("hardness", [1.0, 3.0])
def test_solve_records_its_phases_nested(engine, hardness):
    eng, stats = engine
    res = eng.solve(instantiate(Q2_TPCH, stats, hardness), ilp_kwargs=ILP_KW)
    assert res.feasible
    log = res.report.spans
    names = _names(log)
    assert names[0] == "solve" and names.count("solve") == 1
    assert names.count("shade") == eng.hierarchy.L
    for name in PHASES:
        assert name in names, name
    assert len(names) <= 20                    # never one span per node
    spans = log.spans
    for s in spans[1:]:
        p = spans[s.parent]
        # the ILP's phases sit in the solve, beside Dual Reducer's LPs
        assert p.name == "solve", (s.name, p.name)
        assert p.t0_ns <= s.t0_ns <= s.t1_ns <= p.t1_ns
    for name in ("solve",) + PHASES:
        assert log.seconds(name, own=True) >= 0, name
    assert log.seconds("solve", own=True) < log.seconds("solve")


def test_ilp_counters_sum_the_sub_ilps(engine, monkeypatch):
    eng, stats = engine
    got = []
    solve = ilp_mod.solve_ilp

    def spy(*a, **k):
        got.append(solve(*a, **k))
        return got[-1]
    monkeypatch.setattr(ilp_mod, "solve_ilp", spy)
    q = instantiate(Q2_TPCH, stats, 3.0)
    rep = eng.solve(q, ilp_kwargs=ILP_KW).report
    assert got and rep.ilp_lp_pivots == sum(r.lp_iters for r in got)
    assert rep.ilp_nodes == sum(r.nodes for r in got) > 1
    assert rep.ilp_capped == sum(
        r.status in (ilp_mod.ILP_FEASIBLE, ilp_mod.ILP_LIMIT) for r in got)
    assert 0 < rep.ilp_node_lp_s <= rep.spans.seconds("ilp.search")

    got.clear()
    rep = eng.solve(q, ilp_kwargs=dict(ILP_KW, max_nodes=1)).report
    assert rep.ilp_capped >= 1 and rep.ilp_nodes <= len(got)
    assert rep.ilp_lp_pivots == sum(r.lp_iters for r in got)


def test_direct_ilp_records_nothing_without_a_report():
    c = -np.arange(1.0, 6.0)
    A = np.ones((1, 5))
    res = ilp_mod.solve_ilp(c, A, np.array([2.0]), np.array([3.5]),
                            np.ones(5))
    assert res.feasible and res.x.sum() == 3


def test_build_records_each_round(engine):
    eng, _ = engine
    hier = eng.hierarchy
    log = hier.spans
    names = _names(log)
    rounds = log.counters["dlv_rounds"]
    assert names[0] == "build" and names.count("build") == 1
    assert rounds >= hier.L
    for name in ("dlv.sort", "dlv.cuts", "dlv.stats"):
        assert names.count(name) == rounds, name
    assert names.count("dlv.scale") == names.count("build.finalize") \
        == hier.L
    assert all(s.parent == 0 for s in log.spans[1:])
    assert eng.partition_time_s == log.seconds("build") > 0
    assert log.seconds("build", own=True) >= 0


def test_solve_lands_in_a_profile(engine, tmp_path):
    import jax
    from jax.profiler import ProfileData
    eng, stats = engine
    with jax.profiler.trace(str(tmp_path)):
        res = eng.solve(instantiate(Q2_TPCH, stats, 3.0), ilp_kwargs=ILP_KW)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("pq."):
                    events.setdefault(ev.name, []).append(ev)
    assert {"pq." + n for n in ("solve",) + PHASES} <= set(events)
    stats_ = dict(events["pq.solve"][0].stats)
    rep = res.report
    assert stats_["ilp_lp_pivots"] == rep.ilp_lp_pivots
    assert stats_["ilp_capped"] == rep.ilp_capped
    assert stats_["ilp_node_lp_s"] == pytest.approx(rep.ilp_node_lp_s)
