"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips of
TPU v5e; multi-pod adds a leading 'pod' axis (2 pods = 512 chips).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many devices exist (tests / examples)."""
    return jax.make_mesh((data, model), ("data", "model"))


def make_abstract_mesh(shape, axes):
    """AbstractMesh: build shardings without real devices."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


# TPU v5e hardware constants used by the roofline analysis.
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link (per the brief)
HBM_BYTES = 16 * 2**30         # 16 GiB per chip
