"""Public wrappers for the Pallas kernels, and the one place that decides
whether a kernel is compiled or interpreted.

The kernel signatures take ``interpret`` without a default.  These
wrappers pass ``interpret_kernels()``: compiled by Mosaic on a TPU,
interpreted (kernel bodies executed as jnp on the host) on any other
backend, which is how the CPU test suite checks them against their
oracles in ``ref.py``.  On a TPU nothing interprets.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.bfrt import bfrt_histogram, bfrt_select
from repro.kernels.pricing import pricing
from repro.kernels.segstats import (segment_stats, segment_stats_np,
                                    segstats_partials)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def interpret_kernels() -> bool:
    """Interpret the Pallas kernels everywhere except on a TPU."""
    return not on_tpu()


def pricing_op(A, rho, d, state, lo, hi, s, **kw):
    return pricing(A, rho, d, state, lo, hi, s,
                   interpret=interpret_kernels(), **kw)


def bfrt_select_op(ratio, cost, budget, **kw):
    return bfrt_select(ratio, cost, budget, interpret=interpret_kernels(),
                       **kw)


def segment_stats_op(vals, ids, num_groups, **kw):
    return segment_stats(vals, ids, num_groups,
                         interpret=interpret_kernels(), **kw)


def segment_stats_auto(vals, ids, num_groups):
    """Kernel on TPU, exact bincount twin on hosts (the partitioner path).

    CAVEAT: the TPU kernel accumulates in float32 (MXU one-hot matmuls) —
    callers must center ``vals`` (DLV passes globally-centered values) and
    the resulting sum/sumsq only steer split selection, never final reps
    (``partitioner.group_stats`` recomputes those exactly).  Groups far
    from the global mean relative to their spread lose variance precision;
    see ROADMAP "TPU-resident build" for the per-block centering follow-on.
    """
    if on_tpu():
        cnt, sm, sq = segment_stats(jnp.asarray(vals, jnp.float32),
                                    jnp.asarray(ids, jnp.int32),
                                    num_groups, interpret=False)
        return (np.asarray(cnt, np.float64), np.asarray(sm, np.float64),
                np.asarray(sq, np.float64))
    return segment_stats_np(vals, ids, num_groups)


__all__ = ["pricing_op", "bfrt_select_op", "segment_stats_op",
           "segment_stats_auto", "segment_stats_np", "bfrt_histogram",
           "segstats_partials", "on_tpu", "interpret_kernels"]
