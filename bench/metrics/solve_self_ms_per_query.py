"""Time of a solve that none of its phases covers (pq.solve self:
warm-basis re-maps, sub-ILP gathers, the guard), ms per query of the
traced window."""
from bench.lib.program_spans import self_ms


def read(rec):
    return self_ms(rec, "solve")
