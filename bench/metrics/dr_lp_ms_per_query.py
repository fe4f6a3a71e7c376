"""Self time of Dual Reducer's LPs (pq.dr.lp: the candidates' gather,
lp1 and its retry, the auxiliary LP), ms per query of the traced
window."""
from bench.lib.program_spans import self_ms


def read(rec):
    return self_ms(rec, "dr.lp")
