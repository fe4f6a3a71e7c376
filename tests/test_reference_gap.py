"""The engine's answers against the LP relaxation over the whole relation:
three query templates x four hardness levels x three data seeds.  Each
answer must be feasible, meet every constraint, lie on the right side of
the bound, and sit within the benchmark's ``lp_gap`` limit of it."""
import numpy as np
import pytest

from repro.core.engine import PackageQueryEngine
from repro.core.hardness import (Q1_SDSS, Q2_TPCH, Q4_TPCH, column_stats,
                                 instantiate)
from repro.data.synth_tables import make_table

ROWS = 2000
LP_GAP_LIMIT = 0.07          # the benchmark's lp_gap limit
TEMPLATES = {"Q1_SDSS": (Q1_SDSS, "sdss"), "Q2_TPCH": (Q2_TPCH, "tpch"),
             "Q4_TPCH": (Q4_TPCH, "tpch")}
# Q4_TPCH answers sit 10-20% off the bound on two of the three relations
# (ROADMAP R8 has the numbers).
_R8 = {("Q4_TPCH", 0), ("Q4_TPCH", 2)}


def _cases():
    for name in TEMPLATES:
        for seed in (0, 1, 2):
            marks = ()
            if (name, seed) in _R8:
                marks = pytest.mark.xfail(
                    strict=True, reason="ROADMAP R8: Q4_TPCH answer over "
                    "the lp_gap limit on this relation")
            for h in (1, 3, 5, 7):
                yield pytest.param(name, seed, h, marks=marks,
                                   id=f"{name}-seed{seed}-h{h}")


@pytest.fixture(scope="module")
def engines():
    """One partitioned engine per (data kind, seed), built on first use."""
    built = {}

    def get(kind, seed):
        if (kind, seed) not in built:
            table = make_table(kind, ROWS, seed)
            attrs = list(table)[:4]
            eng = PackageQueryEngine(table, attrs, d_f=20, alpha=200,
                                     seed=0)
            eng.partition()
            built[kind, seed] = eng, table, column_stats(table, attrs)
        return built[kind, seed]
    return get


@pytest.mark.parametrize("name,seed,h", list(_cases()))
def test_answer_within_lp_gap_of_the_bound(engines, name, seed, h):
    template, kind = TEMPLATES[name]
    eng, table, stats = engines(kind, seed)
    q = instantiate(template, stats, h)
    res = eng.solve(q)
    assert res.feasible, res.status
    assert q.check_package(table, res.idx, res.mult)
    lp = eng.lp_bound(q)
    assert np.isfinite(lp)
    short = (lp - res.obj) if q.maximize else (res.obj - lp)
    assert short >= -1e-9 * max(abs(lp), 1.0), (res.obj, lp)
    gap = short / max(abs(lp), abs(res.obj), 1e-300)
    assert gap <= LP_GAP_LIMIT, (res.obj, lp, gap)
