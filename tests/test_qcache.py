"""Cross-query artifact cache (repro.core.qcache) + its engine wiring.

Covers the PR-8 contract: canonical signatures (reorder-identity,
containment), exact-hit package reuse with validation, the artifact-only
and contained/pre-prune paths, gap-gated fallback parity, leaf-local
append invalidation, LRU eviction, fingerprint stability, warm-start
rejection observability, and the bounded distributed step cache.
"""
import numpy as np
import pytest

from repro.core.engine import PackageQueryEngine
from repro.core.hardness import Q2_TPCH, Q4_TPCH, column_stats, instantiate
from repro.core.paql import Constraint, PackageQuery
from repro.core.qcache import QCache
from repro.data.synth_tables import make_table

ATTRS = ["price", "quantity", "discount", "tax"]
ILP_KW = dict(max_nodes=200, time_limit_s=15)
N = 12_000
D_F = 20
ALPHA = 800


@pytest.fixture(scope="module")
def dataset():
    table = make_table("tpch", N, seed=1)
    stats = column_stats(table, ATTRS)
    return table, stats


def _engine(table, cache=None, seed=0):
    eng = PackageQueryEngine(table, ATTRS, d_f=D_F, alpha=ALPHA,
                             seed=seed, cache=cache)
    eng.partition()
    return eng


def _pkg(res):
    order = np.argsort(res.idx, kind="stable")
    return np.asarray(res.idx)[order], np.asarray(res.mult)[order]


def _same_package(a, b):
    ia, ma = _pkg(a)
    ib, mb = _pkg(b)
    return np.array_equal(ia, ib) and np.array_equal(ma, mb)


# ------------------------------------------------------------ signatures


def test_signature_reorder_identity():
    cts = (Constraint(None, 2, 10), Constraint("price", 5.0, 50.0),
           Constraint("tax", 0.0, 1.0, avg_target=0.5))
    q1 = PackageQuery("price", True, cts)
    q2 = PackageQuery("price", True, cts[::-1])
    assert q1.signature() == q2.signature()
    assert q1.signature().digest() == q2.signature().digest()


def test_signature_containment(dataset):
    _, stats = dataset
    prime = instantiate(Q2_TPCH, stats, 2.0).signature()
    tight = instantiate(Q2_TPCH, stats, 3.0).signature()
    wide = instantiate(Q2_TPCH, stats, 1.0).signature()
    disjoint = instantiate(Q4_TPCH, stats, 2.0).signature()
    assert tight.contained_in(prime)
    assert tight.contained_in(tight)            # reflexive
    assert not prime.contained_in(tight)        # widening never contained
    assert not wide.contained_in(prime)
    assert not disjoint.contained_in(prime)     # different structure
    assert not prime.contained_in(disjoint)


def test_signature_digest_process_stable():
    q = PackageQuery("price", True, (Constraint(None, 2, 10),))
    d = q.signature().digest()
    assert d == q.signature().digest()
    assert len(d) == 40                         # sha1 hex, not hash()
    q2 = PackageQuery("price", True, (Constraint(None, 2, 11),))
    assert q2.signature().digest() != d


# ------------------------------------------------------- hit/parity paths


def test_exact_hit_package_parity_and_counters(dataset):
    table, stats = dataset
    q = instantiate(Q2_TPCH, stats, 2.0)
    cache = QCache()
    eng = _engine(table, cache=cache)
    r1 = eng.solve(q, ilp_kwargs=ILP_KW)
    r2 = eng.solve(q, ilp_kwargs=ILP_KW)
    assert r1.feasible and r2.feasible
    assert "cached=package" in r2.status
    assert _same_package(r1, r2) and r1.obj == r2.obj
    assert cache.stats.exact_hits == 1 and cache.stats.misses == 1
    assert cache.stats.stores == 1 and cache.stats.bytes > 0
    assert r2.report.cache_hits == 1 and r2.report.cache_pruned_lps > 0
    assert r1.report.cache_misses == 1
    assert "cache=" in r2.report.summary()
    assert r2.report.cache_kind == "package"


def test_artifact_only_mode_parity(dataset):
    table, stats = dataset
    q = instantiate(Q2_TPCH, stats, 2.0)
    cache = QCache(reuse_packages=False)
    eng = _engine(table, cache=cache)
    r1 = eng.solve(q, ilp_kwargs=ILP_KW)
    r2 = eng.solve(q, ilp_kwargs=ILP_KW)
    assert "cached=exact" in r2.status          # re-solved, not replayed
    assert _same_package(r1, r2)
    assert r2.report.cache_pruned_lps > 0


def test_contained_hit_prune_accepted(dataset):
    table, stats = dataset
    cache = QCache(gap_accept=2.0)              # lenient: prune accepted
    eng = _engine(table, cache=cache)
    q_prime = instantiate(Q2_TPCH, stats, 2.0)
    q_tight = instantiate(Q2_TPCH, stats, 3.0)
    r0 = eng.solve(q_prime, ilp_kwargs=ILP_KW)
    assert r0.feasible
    r1 = eng.solve(q_tight, ilp_kwargs=ILP_KW)
    assert r1.feasible
    assert "cached=contained" in r1.status
    assert cache.stats.contained_hits == 1
    # a pruned solve is still a *valid* package with a monotone bound
    assert q_tight.check_package(table, r1.idx, r1.mult)
    assert r1.lp_obj <= r0.lp_obj + 1e-6 * max(1.0, abs(r0.lp_obj))


def test_gap_rejected_prune_falls_back_with_parity(dataset):
    table, stats = dataset
    cache = QCache(gap_accept=-1.0)             # reject every prune
    eng = _engine(table, cache=cache)
    q_prime = instantiate(Q2_TPCH, stats, 2.0)
    q_tight = instantiate(Q2_TPCH, stats, 3.0)
    eng.solve(q_prime, ilp_kwargs=ILP_KW)
    r1 = eng.solve(q_tight, ilp_kwargs=ILP_KW)
    r_cold = _engine(table).solve(q_tight, ilp_kwargs=ILP_KW)
    assert "cached" not in r1.status
    assert "cache_fallback" in r1.report.fallbacks
    assert cache.stats.fallbacks == 1
    assert _same_package(r1, r_cold) and r1.obj == r_cold.obj
    # the fallback cold solve re-populated the tightened entry cleanly
    r2 = eng.solve(q_tight, ilp_kwargs=ILP_KW)
    assert "cached=package" in r2.status and _same_package(r1, r2)


def test_poisoned_entry_falls_back_with_parity(dataset):
    table, stats = dataset
    q = instantiate(Q2_TPCH, stats, 2.0)
    cache = QCache()
    eng = _engine(table, cache=cache)
    r1 = eng.solve(q, ilp_kwargs=ILP_KW)
    (_, _, entry), = cache.entries()
    entry.package_obj += 1e9                    # poison: validation fails
    entry.lp_bound += 1e9
    r2 = eng.solve(q, ilp_kwargs=ILP_KW)
    assert "cached" not in r2.status
    assert "cache_fallback" in r2.report.fallbacks
    assert _same_package(r1, r2) and r1.obj == r2.obj


# ------------------------------------------------ invalidation + appends


def test_append_invalidates_exactly_touched_ancestry(dataset):
    table, stats = dataset
    q = instantiate(Q2_TPCH, stats, 2.0)
    cache = QCache()
    eng = _engine(table, cache=cache)
    r0 = eng.solve(q, ilp_kwargs=ILP_KW)
    assert r0.feasible
    (_, _, entry), = cache.entries()
    hier = eng.hierarchy
    before = {l: set(entry.group_ids(l)) for l in range(1, hier.L + 1)}
    assert entry.complete and all(before[l] for l in before)

    # package-colocated rows guarantee at least one cached leaf is hit
    rows = {a: np.asarray(table[a][r0.idx[:7]], np.float64)
            for a in ATTRS}
    rep = hier.append(rows)
    touched = np.unique(rep.gids)
    ancestors = hier.leaf_ancestors(touched)
    assert np.array_equal(ancestors[1], touched)

    assert not entry.complete
    for l in range(1, hier.L + 1):
        removed = before[l] - set(entry.group_ids(l))
        expected = before[l] & set(int(g) for g in ancestors[l])
        assert removed == expected, (l, removed, expected)
        if removed:
            assert entry.candidates(l) is None
    total_removed = sum(len(before[l] - set(entry.group_ids(l)))
                        for l in before)
    assert cache.stats.invalidated_groups == total_removed > 0

    # an incomplete entry never serves hits again: stale miss
    misses0, stale0 = cache.stats.misses, cache.stats.stale_misses
    assert cache.lookup(hier.fingerprint, q.signature()) is None
    assert cache.stats.stale_misses == stale0 + 1
    assert cache.stats.misses == misses0 + 1


def test_cached_vs_cold_parity_after_append(dataset):
    table, stats = dataset
    q = instantiate(Q2_TPCH, stats, 2.0)
    cache = QCache()
    eng = _engine(table, cache=cache)
    r0 = eng.solve(q, ilp_kwargs=ILP_KW)
    assert r0.feasible
    # rows colocated with the package's own tuples land in cached leaf
    # groups by construction, so this append MUST invalidate the entry
    eng.hierarchy.append({a: np.asarray(table[a][r0.idx[:3]], np.float64)
                          for a in ATTRS})
    (_, _, entry), = cache.entries()
    assert not entry.complete
    r1 = eng.solve(q, ilp_kwargs=ILP_KW)        # stale -> cold, re-store
    r_cold = _engine(table).solve(q, ilp_kwargs=ILP_KW)
    assert "cached" not in r1.status
    assert _same_package(r1, r_cold) and r1.obj == r_cold.obj
    r2 = eng.solve(q, ilp_kwargs=ILP_KW)        # re-populated entry hits
    assert "cached=package" in r2.status and _same_package(r1, r2)


def test_fingerprint_stable_across_rebuilds(dataset):
    table, _ = dataset
    h1 = _engine(table).hierarchy.fingerprint
    h2 = _engine(table).hierarchy.fingerprint
    assert h1 == h2
    eng3 = PackageQueryEngine(table, ATTRS, d_f=D_F + 5, alpha=ALPHA,
                              seed=0)
    eng3.partition()
    assert eng3.hierarchy.fingerprint != h1


# ----------------------------------------------------- eviction + bounds


def test_lru_eviction_by_bytes(dataset):
    table, stats = dataset
    cache = QCache(max_bytes=1)                 # everything over budget
    eng = _engine(table, cache=cache)
    q_a = instantiate(Q2_TPCH, stats, 2.0)
    q_b = instantiate(Q4_TPCH, stats, 1.0)      # disjoint: its own entry
    assert eng.solve(q_a, ilp_kwargs=ILP_KW).feasible
    assert len(cache) == 1                      # sole entry survives
    assert eng.solve(q_b, ilp_kwargs=ILP_KW).feasible
    assert len(cache) == 1 and cache.stats.evictions == 1
    # q_a was evicted: solving it again is a miss, not a hit
    hits0 = cache.stats.hits
    r = eng.solve(q_a, ilp_kwargs=ILP_KW)
    assert r.feasible and "cached" not in r.status
    assert cache.stats.hits == hits0
    assert cache.stats.bytes <= max(e.nbytes for _, _, e
                                    in cache.entries()) + 1


# -------------------------------------------------- warm-start telemetry


def test_warm_rejected_surfaced(dataset, monkeypatch):
    import repro.core.shading as shading_mod
    table, stats = dataset
    q = instantiate(Q2_TPCH, stats, 2.0)
    monkeypatch.setattr(shading_mod, "fill_warm_basis",
                        lambda *a, **k: None)   # every re-map rejects
    eng = _engine(table)
    res = eng.solve(q, ilp_kwargs=ILP_KW)
    assert res.feasible
    assert res.report.warm_rejected > 0
    assert "warm_rejected" in res.report.summary()
    assert any("warm_map_rejected" in n for n in res.report.notes)


# ------------------------------------------------ distributed step cache


def test_bounded_step_cache_counters():
    from repro.core.distributed import (STEP_CACHE_MAXSIZE,
                                        BoundedStepCache, _STEP_CACHE,
                                        step_cache_stats)
    c = BoundedStepCache(maxsize=2)
    made = []
    for key in ("a", "b", "a", "c", "b"):       # LRU 'b' evicted by 'c'
        c.get_or_create(key, lambda k=key: made.append(k) or k.upper())
    assert made == ["a", "b", "c", "b"]
    assert c.hits == 1 and c.misses == 4 and c.evictions == 2
    assert len(c) == 2
    assert c.stats() == {"hits": 1, "misses": 4, "evictions": 2,
                         "lookups": 5, "size": 2, "maxsize": 2}
    assert c.hits + c.misses == c.lookups
    c.clear()
    assert len(c) == 0
    # module-level cache: bounded, stats exposed
    assert _STEP_CACHE.maxsize == STEP_CACHE_MAXSIZE == 64
    assert set(step_cache_stats()) == {"hits", "misses", "evictions",
                                       "lookups", "size", "maxsize"}
