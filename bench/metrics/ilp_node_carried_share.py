"""Share of B&B's node LPs resumed from their parent's factorization:
SolveReport.ilp_node_lps_carried over ilp_node_lps (carried on
pq.solve), summed over the queries of the traced window."""
from bench.lib import program_spans as ps


def read(rec):
    qs = [q["stats"] for q in ps.window_queries(rec)
          if "ilp_node_lps" in q["stats"]]
    lps = sum(s["ilp_node_lps"] for s in qs)
    return sum(s.get("ilp_node_lps_carried", 0) for s in qs) / lps \
        if lps else None
