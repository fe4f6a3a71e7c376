"""The benchmark's copies of the data and query generators reproduce the
program's, so that moving them out of the program changed nothing, and
the TPC-H relation follows the specification's rules."""
import numpy as np
import pytest

from bench.lib import data, queries as Q
from bench.tests.conftest import read_json

SEEDS = [0, 12345, 2**31 + 7]


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("config,kind", [("sdss-apogee-10m", "sdss")])
def test_relation_matches_make_table(config, kind, seed):
    from repro.data.synth_tables import make_table
    cfg = read_json("configs", config)
    got = data.relation(cfg, 5000, seed)
    want = make_table(kind, 5000, seed)
    assert list(got) == list(want)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


@pytest.mark.parametrize("seed", SEEDS)
def test_lineitem_follows_the_tpch_rules(seed):
    cfg = read_json("configs", "tpch-lineitem-10m")
    cols = data.relation(cfg, 30000, seed)
    q = cols["quantity"]
    assert set(np.unique(q)) == set(range(1, 51))
    retail = cols["price"] / q * 100          # p_retailprice in cents
    np.testing.assert_allclose(retail, np.round(retail), atol=1e-6)
    cents = data.retail_cents(np.arange(1, 1001))   # parts = 30000 / 30
    assert set(np.round(retail).astype(int)) <= set(cents.tolist())
    for col, top in (("discount", 10), ("tax", 8)):
        rate = np.round(cols[col] / cols["price"] * 100, 6)
        assert set(np.unique(rate)) == set(range(top + 1))


def test_lineitem_has_the_papers_table2_statistics():
    """Over part keys that cover p_retailprice's formula (large SF), the
    columns have Table 2's means and standard deviations."""
    cols = data.tpch_lineitem(2_000_000, 1, rows_per_part=1.0)
    table2 = {"price": (38240, 23290), "discount": (1912, 1833),
              "tax": (1530, 1485), "quantity": (25.5, 14.43)}
    for col, (mean, std) in table2.items():
        assert abs(cols[col].mean() / mean - 1) < 0.01, col
        assert abs(cols[col].std() / std - 1) < 0.01, col


@pytest.mark.parametrize("config,mix,tname", [
    ("tpch-lineitem-10m", "q2-h1to7", "Q2_TPCH"),
    ("sdss-apogee-10m", "q1-h1to7", "Q1_SDSS")])
def test_instantiate_matches_program(config, mix, tname):
    from repro.core.hardness import TEMPLATES, column_stats, instantiate
    cols = data.relation(read_json("configs", config), 20000, 3)
    tmpl = read_json("traffic", mix)["template"]
    stats = Q.column_stats(cols, Q.template_attrs(tmpl))
    assert stats == column_stats(cols, Q.template_attrs(tmpl))
    for h in (0.5, 1.0, 3.7, 7.0, 12.0):
        want = instantiate(TEMPLATES[tname], stats, h)
        got = Q.to_program(Q.instantiate(tmpl, stats, h))
        assert got == want


def test_plan_is_stratified_and_seeded():
    traffic = read_json("traffic", "q2-h1to7")
    traffic["hardness"] = dict(traffic["hardness"], same_set=False)
    a = Q.plan(traffic, np.random.default_rng([5, 1]), 64)
    b = Q.plan(traffic, np.random.default_rng([5, 1]), 64)
    assert a == b
    h = np.array([p.hardness for p in a]).reshape(4, 16)
    # every block of 16 takes one value from each sixteenth of [1, 7]
    for block in h:
        strata = np.floor((block - 1.0) / 6.0 * 16).astype(int)
        assert sorted(strata) == list(range(16))
    assert {p.kind for p in a} == {"cold"}


def test_same_set_plans_differ_only_in_order():
    traffic = read_json("traffic", "q2-h1to7")
    assert traffic["hardness"]["same_set"]
    blocks = []
    for seed in (5, 2**31 + 9):
        ps = Q.plan(traffic, np.random.default_rng([seed, 1]), 48,
                    set_rng=np.random.default_rng([0, 1, 0]))
        blocks.append([sorted((p.hardness, p.session_seed)
                              for p in ps[i:i + 16]) for i in (0, 16, 32)])
        h = [p.hardness for p in ps[:16]]
        assert sorted(h) == [1.0 + 6.0 * (i + 0.5) / 16 for i in range(16)]
    assert blocks[0] == blocks[1]                 # the same sets
    assert blocks[0][0] != blocks[0][1]           # a new set each block
    a = Q.plan(traffic, np.random.default_rng(5), 16,
               set_rng=np.random.default_rng([0, 1, 0]))
    b = Q.plan(traffic, np.random.default_rng(6), 16,
               set_rng=np.random.default_rng([0, 1, 0]))
    assert a != b                                 # in another order


def test_plan_repeats_and_variants():
    traffic = dict(read_json("traffic", "q2-h1to7"),
                   repeat_share=0.3, variant_share=0.4, variant_step=0.5)
    traffic["hardness"] = dict(traffic["hardness"], same_set=False)
    ps = Q.plan(traffic, np.random.default_rng(9), 400)
    seen = {p.hardness for p in ps if p.kind == "cold"}
    kinds = [p.kind for p in ps]
    assert 0.2 < kinds.count("repeat") / 400 < 0.4
    assert 0.3 < kinds.count("variant") / 400 < 0.5
    assert all(p.hardness in {q.hardness for q in ps[:i]}
               for i, p in enumerate(ps) if p.kind == "repeat")
    assert seen
