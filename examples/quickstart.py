"""Quickstart: answer a package query over a synthetic relation with
Progressive Shading, and compare against the direct ILP.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core.engine import PackageQueryEngine
from repro.core.paql import Constraint, PackageQuery


def main():
    rng = np.random.default_rng(0)
    n = 50_000
    # A relation of products: value, weight, volume
    table = {
        "value": rng.lognormal(3.0, 0.6, n),
        "weight": rng.uniform(0.2, 9.0, n),
        "volume": rng.uniform(0.1, 4.0, n),
    }

    # SELECT PACKAGE(*) FROM products REPEAT 0
    # SUCH THAT 10 <= COUNT(*) <= 30
    #       AND SUM(weight) <= 60 AND SUM(volume) BETWEEN 18 AND 22
    # MAXIMIZE SUM(value)
    query = PackageQuery(
        objective_attr="value", maximize=True,
        constraints=(
            Constraint(None, 10, 30),
            Constraint("weight", hi=60.0),
            Constraint("volume", lo=18.0, hi=22.0),
        ))

    eng = PackageQueryEngine(table, ["value", "weight", "volume"],
                             d_f=25, alpha=2500, seed=0)
    eng.partition()
    print(f"hierarchy: {[l.size for l in eng.hierarchy.layers]} "
          f"(partitioned in {eng.partition_time_s:.1f}s)")

    res = eng.solve(query)
    assert res.feasible and query.check_package(table, res.idx, res.mult)
    lp = eng.lp_bound(query)
    print(f"Progressive Shading: {int(res.mult.sum())} tuples, "
          f"value={res.obj:.1f} (LP bound {lp:.1f}, "
          f"gap {(lp + .1) / (res.obj + .1):.4f})  [{res.status}]")
    print(f"  weight={table['weight'][res.idx] @ res.mult:.1f} <= 60, "
          f"volume={table['volume'][res.idx] @ res.mult:.2f} in [18, 22]")

    direct = eng.solve_direct(query, dict(max_nodes=300, time_limit_s=30))
    if direct.feasible:
        print(f"Direct ILP (black-box role): value={direct.obj:.1f}")


if __name__ == "__main__":
    from repro.hostdev import use_compile_cache
    use_compile_cache()
    main()
