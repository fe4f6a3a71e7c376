"""The relation of a deployment, made from a seed.

A configuration gives either ``generator``, the name of a published
data rule below, or ``columns``, a list of independent column recipes.

``tpch_lineitem`` follows the TPC-H specification's own rules for
LINEITEM (v3, Sec. 4.2.3), from one ``numpy`` generator, in this order:

    partkey   uniform on [1, parts], parts = rows / rows_per_part
    quantity  uniform on {1, ..., 50}
    discount  rate uniform on {0.00, 0.01, ..., 0.10}
    tax       rate uniform on {0.00, 0.01, ..., 0.08}

with ``p_retailprice = (90000 + (partkey // 10) % 20001
+ 100 * (partkey % 1000)) / 100``; ``price`` is l_extendedprice =
quantity * p_retailprice, and ``discount`` and ``tax`` are the amounts
price * rate (the paper's Table 2 gives their statistics).

A column recipe is drawn in the order the configuration lists it:

    v = <draw>(rng, n)                        integers | lognormal |
                                              exponential | gamma | normal
    v[rng.random(n) < zero_share] = 0         where ``zero_share`` is given
    v = v * (std / v.std()); v - v.mean() + mean   where ``std`` is given
    v = clip(v, clip_lo)                      where ``clip_lo`` is given

This is the paper's Table 1 recipe (column marginals matched by mean
and standard deviation), the same draws in the same order as
``repro.data.synth_tables.make_table``, kept here so that a change to
the program cannot move the benchmark's data.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def _draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    kind = spec["draw"]
    if kind == "integers":
        return rng.integers(spec["low"], spec["high"], n).astype(np.float64)
    if kind == "lognormal":
        return rng.lognormal(mean=spec["mu"], sigma=spec["sigma"], size=n)
    if kind == "exponential":
        return rng.exponential(scale=spec["scale"], size=n)
    if kind == "gamma":
        return rng.gamma(shape=spec["shape"], scale=spec["scale"], size=n)
    if kind == "normal":
        return rng.normal(spec["mean"], spec["std"], n)
    raise ValueError(f"unknown draw {kind!r}")


def column(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    v = _draw(rng, spec, n)
    if "zero_share" in spec:
        v[rng.random(n) < spec["zero_share"]] = 0.0
    if "target_std" in spec:
        v = v * (spec["target_std"] / v.std())
        v = v - v.mean() + spec["target_mean"]
    if "clip_lo" in spec:
        v = np.clip(v, spec["clip_lo"], None)
    return v


def make_relation(columns: Sequence[dict], n: int,
                  seed: int) -> Dict[str, np.ndarray]:
    """Resident float64 columns, drawn in the listed order."""
    rng = np.random.default_rng(seed)
    return {spec["name"]: column(rng, spec, n) for spec in columns}


def retail_cents(partkey: np.ndarray) -> np.ndarray:
    """TPC-H P_RETAILPRICE of each part key, in cents."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def tpch_lineitem(n: int, seed: int,
                  rows_per_part: float) -> Dict[str, np.ndarray]:
    """LINEITEM's price, quantity, discount and tax by TPC-H's rules."""
    rng = np.random.default_rng(seed)
    parts = max(1, int(round(n / rows_per_part)))
    partkey = rng.integers(1, parts + 1, n)
    quantity = rng.integers(1, 51, n)
    disc = rng.integers(0, 11, n)
    tax = rng.integers(0, 9, n)
    cents = quantity * retail_cents(partkey)
    return {"price": cents / 100.0,
            "quantity": quantity.astype(np.float64),
            "discount": (cents * disc) / 10000.0,
            "tax": (cents * tax) / 10000.0}


GENERATORS = {"tpch_lineitem": tpch_lineitem}


def relation(cfg: dict, n: int, seed: int) -> Dict[str, np.ndarray]:
    """The configuration's relation at ``n`` rows."""
    gen = cfg.get("generator")
    if gen is None:
        return make_relation(cfg["columns"], n, seed)
    return GENERATORS[gen["name"]](n, seed, **gen.get("params", {}))
