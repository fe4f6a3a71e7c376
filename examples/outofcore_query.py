"""Out-of-core package query: solve over an on-disk memmap relation that
is never loaded into memory.

Writes a ~1M-row relation to disk chunk-by-chunk, wraps it in a
``MemmapRelation``, and runs the full Progressive Shading pipeline on it:
layer 0 is partitioned through the Appendix D.2 bucketing backend under a
``memory_rows`` budget, the shading cascade passes candidate ids down, and
Dual Reducer / validation gather only the <= alpha candidate rows.  The
peak relation-resident row count is printed at the end — it stays at
candidate/chunk scale, not the relation's.

    PYTHONPATH=src python examples/outofcore_query.py
"""
import os
import tempfile

import numpy as np

from repro.core import relation as relation_mod
from repro.core.engine import PackageQueryEngine
from repro.core.paql import Constraint, PackageQuery
from repro.core.relation import MemmapRelation

ATTRS = ["value", "weight", "volume"]


def write_relation(path: str, n: int, chunk: int = 1 << 18) -> None:
    """Stream the synthetic relation to disk — it never exists in RAM."""
    mm = np.lib.format.open_memmap(path, mode="w+", dtype=np.float64,
                                   shape=(n, len(ATTRS)))
    for a in range(0, n, chunk):
        rng = np.random.default_rng(a)
        b = min(a + chunk, n)
        mm[a:b, 0] = rng.lognormal(3.0, 0.6, b - a)     # value
        mm[a:b, 1] = rng.uniform(0.2, 9.0, b - a)       # weight
        mm[a:b, 2] = rng.uniform(0.1, 4.0, b - a)       # volume
    mm.flush()


def main():
    n = 1_000_000
    tmp = tempfile.mkdtemp(prefix="pq_example_")
    path = os.path.join(tmp, "products.npy")
    print(f"writing {n} rows -> {path}")
    write_relation(path, n)

    rel = MemmapRelation.from_npy(path, ATTRS)

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

    relation_mod.reset_peak_resident()
    eng = PackageQueryEngine(rel, ATTRS, d_f=50, alpha=10_000, seed=0,
                             memory_rows=200_000, chunk_rows=100_000)
    eng.partition()     # streamed: bucketed DLV under the memory budget
    print(f"hierarchy: {[l.size for l in eng.hierarchy.layers]} "
          f"(partitioned in {eng.partition_time_s:.1f}s, "
          f"backend=bucketing)")

    res = eng.solve(query)
    assert res.feasible and query.check_package(rel, res.idx, res.mult)
    w = rel.gather_rows(res.idx, ("weight", "volume"))
    print(f"Progressive Shading: {int(res.mult.sum())} tuples, "
          f"value={res.obj:.1f}  [{res.status}]")
    print(f"  weight={w['weight'] @ res.mult:.1f} <= 60, "
          f"volume={w['volume'] @ res.mult:.2f} in [18, 22]")
    print(f"peak relation-resident rows: "
          f"{relation_mod.peak_resident_rows()} (of {n} total)")


if __name__ == "__main__":
    from repro.hostdev import use_compile_cache
    use_compile_cache()
    main()
