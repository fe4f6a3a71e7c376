"""From a profiler trace (``.xplane.pb``) to device busy time.

A device's busy time is the union of the intervals in which an operation
ran on it: the events of its ``XLA Ops`` line (``XLA Modules`` where a
plane has no op line).  Busy time is averaged over the device planes.
An idle gap is a stretch of the traced extent in which the device ran
nothing; it is labelled with the innermost benchmark span (``bench.*``
host annotation) around its middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Za-z_]+:\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merged, sorted intervals covering exactly the given ones."""
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def covered(intervals: Sequence[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``busy`` (merged) does not cover."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, min(a, hi)))
        t = max(t, b)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read(path: str) -> dict:
    """Device op intervals per device plane (seconds), op names with
    their total device seconds, the benchmark's host spans, and the
    traced extent."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    dev: Dict[str, List[Interval]] = {}
    ops: Dict[str, float] = defaultdict(float)
    spans: List[Tuple[str, float, float]] = []
    lo, hi = float("inf"), float("-inf")
    for plane in pd.planes:
        lines = list(plane.lines)
        if DEVICE_PLANE.match(plane.name):
            names = {ln.name for ln in lines}
            use = next((n for n in OP_LINES if n in names), None)
            iv = dev.setdefault(plane.name, [])
            for ln in lines:
                if ln.name != use:
                    continue
                for ev in ln.events:
                    a, b = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                    iv.append((a, b))
                    # "%fusion.3 = f32[...] fusion(...)": the op's name
                    ops[ev.name.split(" = ", 1)[0]] += b - a
                    lo, hi = min(lo, a), max(hi, b)
            continue
        for ln in lines:
            for ev in ln.events:
                a, b = ev.start_ns * 1e-9, ev.end_ns * 1e-9
                lo, hi = min(lo, a), max(hi, b)
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, a, b))
    return {"devices": dev, "ops": dict(ops), "spans": spans,
            "extent": (lo, hi) if lo <= hi else (0.0, 0.0)}


def label(spans, t: float, default: str) -> str:
    """The innermost (shortest) benchmark span that holds time t.  A
    span is written when it ends, so one that outlasts the trace is
    missing: ``default`` names the slice instead."""
    inside = [(b - a, name) for name, a, b in spans if a <= t <= b]
    return min(inside)[1] if inside else default


def summarize(path: str, default: str = "bench") -> dict:
    """busy_s (mean over devices), device count, the ten ops with most
    device time, and the ten longest idle gaps of the first device,
    each labelled with the host span around it."""
    r = read(path)
    lo, hi = r["extent"]
    per_dev = [union(iv) for _, iv in sorted(r["devices"].items())]
    busy = sum(covered(u) for u in per_dev) / len(per_dev) if per_dev \
        else 0.0
    idle = gaps(per_dev[0] if per_dev else [], lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    return {"busy_s": busy, "devices": len(per_dev),
            "ops": sorted(r["ops"].items(), key=lambda kv: -kv[1])[:10],
            "gaps": [[label(r["spans"], 0.5 * (a + b), default), b - a]
                     for a, b in idle[:10]]}
