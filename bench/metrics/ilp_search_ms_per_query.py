"""Self time of B&B's node loop (pq.ilp.search), ms per query of the
traced window."""
from bench.lib.program_spans import self_ms


def read(rec):
    return self_ms(rec, "ilp.search")
