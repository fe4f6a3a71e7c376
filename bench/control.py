"""Readings of the program and of its controls, seed by seed, on one build.

    python bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13 \
        [--degraded 11 12 13] [--data-seed N] [--engine-seed N]

Set-up is made once (relation, build, warm-up), as a run makes it; then
for each seed a window of ``--seconds`` on that seed's query stream and
the comparison three times:

- ``checks``: the program's answers and hierarchy;
- ``control_checks``: the control, the program's objectives and
  representatives recomputed in float32, the nearest precision below the
  float64 the configurations state;
- for each seed of ``--degraded``, ``checks`` of a window of each
  fault of ``FAULTS``: ``objective_swapped`` is planted where each
  answer is made and keeps the package feasible and its reported
  objective consistent but poor, which ``lp_gap`` has to catch.

One JSON line per reading.  The limits in the configuration file are set
from them (``PERF.md``).  ``--data-seed`` and ``--engine-seed`` read
another relation or another build of the same one.  The benchmark's own
runs never run this.
"""
import argparse
import dataclasses
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


class ObjectiveSwapped:
    """A fault planted where each answer is made: the package optimises
    the first constrained column in place of the objective, and the
    objective is reported as that package's own, so that only its
    quality is wrong."""

    def __init__(self, eng):
        self.eng = eng

    def session(self, seed):
        import numpy as np
        s = self.eng.session(seed)
        solve = s.solve

        def swapped(q, **kw):
            other = next(c.attr for c in q.constraints
                         if c.attr not in (None, q.objective_attr))
            res = solve(dataclasses.replace(q, objective_attr=other), **kw)
            col = s.table.column(q.objective_attr)
            res.obj = float(np.sum(col[res.idx] * res.mult))
            return res
        s.solve = swapped
        return s


FAULTS = {"objective_swapped": ObjectiveSwapped}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--degraded", type=int, nargs="*", default=[])
    ap.add_argument("--data-seed", type=int, default=None)
    ap.add_argument("--engine-seed", type=int, default=None)
    ap.add_argument("--rows", type=int, default=None,
                    help="rehearse at this many rows (off the chip)")
    args = ap.parse_args(argv)
    import numpy as np
    from bench.lib import harness as H
    from bench.lib import reference as ref
    cell = H.load_cell(args.workload)
    cfg = cell.config
    b = H.build(cell, False, rows=args.rows, require_chip=args.rows is None,
                data_seed=args.data_seed, engine_seed=args.engine_seed)
    H.warm_up(cell, b, H.seed_key(args.seeds[0]))
    X0 = np.stack([b.cols[a] for a in cfg["attrs"]], axis=1)
    layers = H.hierarchy_layers(b.eng.hierarchy)
    key0 = H.seed_key(args.seeds[0])
    hier = {side: {k: v for k, (v, _) in H.check_hierarchy(
                cfg, X0, layers, key0, control=ctl).items()}
            for side, ctl in (("checks", False), ("control_checks", True))}
    n = len(X0)
    del X0, layers
    gc.collect()
    head = {"data_seed": args.data_seed, "engine_seed": args.engine_seed,
            "build_s": b.spans.s["build"],
            "platform": b.devs[0].platform}
    lim = cfg["limits"]
    # lp_gap is read once below: the control's packages are the program's
    no_gap = dict(cfg, limits={k: v for k, v in lim.items()
                               if not k.startswith("lp_gap")})
    runs = [("program", args.seeds, b.eng)]
    runs += [(name, args.degraded, fault(b.eng))
             for name, fault in FAULTS.items()]
    for kind, seeds, eng in runs:
        for seed in seeds:
            key = H.seed_key(seed)
            recs, window_s = H.window(eng, H.client_streams(cell, b, key),
                                      args.seconds, None)
            answers = [r["answer"] for r in recs]
            gaps = ref.lp_gaps(b.cols, answers, n,
                               np.random.default_rng([key, 4]),
                               int(lim["lp_gap_sample"]))
            line = dict(head, kind=kind, seed=seed, attempted=len(recs))
            sides = (("checks", False), ("control_checks", True)) \
                if kind == "program" else (("checks", False),)
            for side, ctl in sides:
                line[side] = {k: v for k, (v, _) in H.check_answers(
                    no_gap, b.cols, answers, key, control=ctl).items()}
                line[side]["lp_gap"] = max((g for _, g in gaps), default=0.0)
                line[side].update(hier[side])
            rec = {"queries": recs, "window_s": window_s}
            line["metrics"] = {m: H.reader(m)(rec) for m in (
                "query_p50_ms", "query_p95_ms", "queries_per_s",
                "lp_pivots_per_query", "ilp_nodes_per_query",
                "ladder_rungs_per_query")}
            # per query: hardness, status, rungs, ms; the sampled gaps
            line["queries"] = [[round(r["hardness"], 3), r["status"],
                                r["rungs"], round(1e3 * r["latency_s"], 1)]
                               for r in recs]
            line["gaps"] = [[round(recs[i]["hardness"], 3), g]
                            for i, g in gaps]
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
