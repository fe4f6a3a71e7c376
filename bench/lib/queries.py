"""Package queries at a hardness, and the traffic that carries them.

``instantiate`` is the paper's Sec. 4.1 rule (the same arithmetic as
``repro.core.hardness.instantiate``, copied so that the program cannot
move the yardstick): for a template with m bound constraints and
expected package size E, every bound is set so that a random package of
E tuples meets it with probability 10^(-h/m), by the central limit
theorem over the column's mean and standard deviation.

A query here is plain data (``Query``); ``to_program`` turns it into the
program's own ``PackageQuery``, the one thing a user hands the engine.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

SQRT2 = math.sqrt(2.0)
INF = float("inf")


def ndtri(p: float) -> float:
    """Inverse standard normal CDF: Acklam's rational approximation plus
    one Halley step (|error| < 1e-12)."""
    if not 0.0 < p < 1.0:
        raise ValueError(p)
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    plow, phigh = 0.02425, 1 - 0.02425
    if p < plow:
        ql = math.sqrt(-2 * math.log(p))
        x = (((((c[0] * ql + c[1]) * ql + c[2]) * ql + c[3]) * ql + c[4]) * ql
             + c[5]) / ((((d[0] * ql + d[1]) * ql + d[2]) * ql + d[3]) * ql + 1)
    elif p <= phigh:
        ql = p - 0.5
        r = ql * ql
        x = (((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4]) * r
             + a[5]) * ql / (((((b[0] * r + b[1]) * r + b[2]) * r + b[3]) * r
                              + b[4]) * r + 1)
    else:
        ql = math.sqrt(-2 * math.log(1 - p))
        x = -(((((c[0] * ql + c[1]) * ql + c[2]) * ql + c[3]) * ql + c[4]) * ql
              + c[5]) / ((((d[0] * ql + d[1]) * ql + d[2]) * ql + d[3]) * ql + 1)
    e = 0.5 * math.erfc(-x / SQRT2) - p
    u = e * math.sqrt(2 * math.pi) * math.exp(x * x / 2)
    return x - u / (1 + x * u / 2)


@dataclasses.dataclass(frozen=True)
class Query:
    """SUM(objective) to optimise under lo <= SUM(attr) <= hi per
    constraint (attr None: COUNT); each tuple at most repeat+1 times."""
    objective: str
    maximize: bool
    constraints: Tuple[Tuple[Optional[str], float, float], ...]
    repeat: int = 0


def column_stats(table: Dict[str, np.ndarray],
                 attrs) -> Dict[str, Tuple[float, float]]:
    return {a: (float(np.mean(table[a])), float(np.std(table[a])))
            for a in attrs}


def instantiate(template: dict, stats: Dict[str, Tuple[float, float]],
                hardness: float) -> Query:
    """The template's bounds at hardness h (paper Sec. 4.1)."""
    lo_n, hi_n = template["count"]
    E = 0.5 * (lo_n + hi_n)
    bounds = template["bounds"]
    p = 10.0 ** (-hardness / len(bounds))
    cons: List[Tuple[Optional[str], float, float]] = [
        (None, float(lo_n), float(hi_n))]
    for attr, kind in bounds:
        mu, sigma = stats[attr]
        se = math.sqrt(E) * sigma
        if kind == "ge":
            cons.append((attr, E * mu + se * ndtri(1 - p), INF))
        elif kind == "le":
            cons.append((attr, -INF, E * mu + se * ndtri(p)))
        elif kind == "between":
            z = ndtri(0.5 * (1 + p))
            cons.append((attr, E * mu - z * se, E * mu + z * se))
        else:
            raise ValueError(kind)
    return Query(template["objective"], bool(template["maximize"]),
                 tuple(cons), int(template.get("repeat", 0)))


def template_attrs(template: dict) -> List[str]:
    return [a for a, _ in template["bounds"]]


def to_program(q: Query):
    """The program's ``PackageQuery`` for ``q``."""
    from repro.core.paql import Constraint, PackageQuery
    return PackageQuery(q.objective, q.maximize,
                        tuple(Constraint(a, lo, hi)
                              for a, lo, hi in q.constraints),
                        repeat=q.repeat)


# ------------------------------------------------------------- traffic


@dataclasses.dataclass(frozen=True)
class Planned:
    hardness: float
    session_seed: int
    kind: str            # cold | repeat | variant


def plan(traffic: dict, rng: np.random.Generator, count: int,
         set_rng: Optional[np.random.Generator] = None) -> List[Planned]:
    """``count`` queries of one client's stream.

    Hardness is stratified: each block of ``strata`` queries takes one
    value from each of ``strata`` equal slices of [lo, hi], in an order
    drawn from the seed, so every seed asks for the same mix of work.
    With ``same_set`` the blocks themselves are the same for every seed:
    each value is its slice's midpoint and each session seed is drawn
    from ``set_rng`` (seeded by the traffic's ``set_seed``), so that a
    run's seed only orders each block.
    A query is, with probability ``repeat_share``, an exact repeat of an
    earlier query of the stream and, with probability ``variant_share``,
    a tightened variant of one (its hardness raised by up to
    ``variant_step``); otherwise it is cold."""
    hd = traffic["hardness"]
    lo, hi, strata = float(hd["lo"]), float(hd["hi"]), int(hd["strata"])
    same = bool(hd.get("same_set", False))
    rep = float(traffic.get("repeat_share", 0.0))
    var = float(traffic.get("variant_share", 0.0))
    step = float(traffic.get("variant_step", 0.0))
    out: List[Planned] = []
    while len(out) < count:
        if same:
            u = np.full(strata, 0.5)
            seeds = set_rng.integers(1 << 31, size=strata)
        else:
            u = rng.random(strata)
        for i in rng.permutation(strata):
            h = lo + (hi - lo) * (i + u[i]) / strata
            kind, r = "cold", rng.random()
            if out and r < rep + var:
                h = out[int(rng.integers(len(out)))].hardness
                kind = "repeat"
                if r >= rep:
                    h += step * rng.random()
                    kind = "variant"
            sseed = int(seeds[i]) if same else int(rng.integers(1 << 31))
            out.append(Planned(h, sseed, kind))
    return out[:count]
