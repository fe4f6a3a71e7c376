"""Self time of the layer Shading steps (pq.shade: layer LPs, ladder
retries, Neighbor Sampling), ms per query of the traced window."""
from bench.lib.program_spans import self_ms


def read(rec):
    return self_ms(rec, "shade")
