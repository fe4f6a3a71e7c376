"""Self time of B&B before its node loop (pq.ilp.incumbent: root LP,
rounding, swaps, restarts, diving, feasibility pump), ms per query of
the traced window."""
from bench.lib.program_spans import self_ms


def read(rec):
    return self_ms(rec, "ilp.incumbent")
