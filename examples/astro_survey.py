"""The paper's running example (§1): pick night-sky regions that may hold
unseen quasars — average brightness above a threshold, total red shift in a
band, maximise combined quasar log-likelihood — swept across the paper's
hardness levels, with SketchRefine as the baseline.

    PYTHONPATH=src python examples/astro_survey.py
"""
import numpy as np

from repro.core.engine import PackageQueryEngine
from repro.core.hardness import column_stats, instantiate, QueryTemplate, BoundSpec
from repro.core.paql import Constraint, PackageQuery


def main():
    rng = np.random.default_rng(7)
    n = 40_000
    regions = {
        "quasar_ll": rng.normal(-0.03, 0.02, n),      # log-likelihood
        "brightness": rng.gamma(4.0, 2.0, n),
        "redshift": rng.normal(1.55, 0.35, n),
        "explored": (rng.random(n) < 0.35).astype(np.float64),
    }
    regions["unexplored"] = 1.0 - regions["explored"]
    attrs = ["quasar_ll", "brightness", "redshift"]

    # SELECT PACKAGE(*) FROM Regions WHERE explored='false'
    # SUCH THAT COUNT(*) = 10 AND AVG(brightness) >= 8
    #       AND SUM(redshift) BETWEEN 14 AND 17
    # MAXIMIZE SUM(quasar_ll)
    query = PackageQuery(
        objective_attr="quasar_ll", maximize=True,
        constraints=(
            Constraint(None, 10, 10),
            Constraint("brightness", lo=0.0, avg_target=8.0),  # AVG >= 8
            Constraint("redshift", lo=14.0, hi=17.0),
        ),
        predicate_attr="unexplored")   # local predicate (Appendix E)

    eng = PackageQueryEngine(regions, attrs, d_f=25, alpha=2500, seed=0)
    eng.partition()
    res = eng.solve(query)
    print(f"regions package: feasible={res.feasible}")
    if res.feasible:
        idx = res.idx
        print(f"  {len(idx)} regions, sum log-lik={res.obj:.4f}")
        print(f"  avg brightness={regions['brightness'][idx].mean():.2f} >= 8")
        print(f"  sum redshift={regions['redshift'][idx].sum():.2f} in [14,17]")
        assert np.all(regions["explored"][idx] == 0.0), "local predicate!"
        print("  all selected regions unexplored (local predicate holds)")

    # hardness sweep on the same relation (paper §4.1 machinery)
    tmpl = QueryTemplate(
        name="astro", objective_attr="quasar_ll", maximize=True,
        count_lo=10, count_hi=30,
        bounds=(BoundSpec("brightness", "ge"), BoundSpec("redshift",
                                                         "between")))
    stats = column_stats(regions, attrs)
    print("\nhardness sweep (PS vs SketchRefine solve):")
    for h in (1, 3, 5, 7, 9):
        q = instantiate(tmpl, stats, h)
        ps = eng.solve(q)
        sr = eng.solve_sketchrefine(q)
        print(f"  h={h}: PS={'Y' if ps.feasible else 'n'} "
              f"SR={'Y' if sr.feasible else 'n'}"
              + (f"  obj={ps.obj:.4f}" if ps.feasible else ""))


if __name__ == "__main__":
    from repro.hostdev import use_compile_cache
    use_compile_cache()
    main()
