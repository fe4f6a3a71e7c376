"""The program's own spans in a traced window, query by query.

The engine opens a ``pq.<phase>`` host annotation around each phase of
a solve (``repro.core.spans``): ``pq.solve`` around the whole query,
inside it ``pq.shade`` (one per layer), ``pq.dr.lp``,
``pq.ilp.incumbent`` and ``pq.ilp.search``; ``pq.solve`` carries the
solve's B&B counters as event stats.  A span's self time is its
duration less the time its child spans cover; spans nest by
containment on one host thread.  A trace of a program without these
spans gives no queries, and the metrics that read them read nothing.
"""
from __future__ import annotations

import os
from collections import defaultdict
from typing import List, Optional, Tuple

PREFIX = "pq."
QUERY = PREFIX + "solve"

Event = Tuple[str, float, float, dict]      # name, start s, end s, stats


def self_times(events: List[Event]) -> List[Tuple[Event, float, int]]:
    """Each event of one thread with its self seconds and the index (in
    the returned list) of the event that encloses it, or -1."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    own = [e[2] - e[1] for e in evs]
    parent = [-1] * len(evs)
    stack: List[int] = []
    for i, (_, a, b, _) in enumerate(evs):
        while stack and evs[stack[-1]][2] < b:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
            own[stack[-1]] -= b - a
        stack.append(i)
    return [(e, own[i], parent[i]) for i, e in enumerate(evs)]


def queries_of(lines: List[List[Event]]) -> List[dict]:
    """One entry per ``pq.solve`` event: ``self`` (seconds of self time
    per span name, the phases nested in it included) and ``stats``."""
    out = []
    for events in lines:
        rows = self_times(events)
        query = [-1] * len(rows)
        for i, ((name, _, _, stats), own, p) in enumerate(rows):
            query[i] = query[p] if p >= 0 else -1
            if name == QUERY:
                query[i] = len(out)
                out.append({"self": defaultdict(float), "stats": stats})
            if query[i] >= 0:
                out[query[i]]["self"][name[len(PREFIX):]] += own
    return out


def read(path: str) -> List[dict]:
    """The queries of the trace at ``path``."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        for ln in plane.lines:
            events = [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                       dict(ev.stats))
                      for ev in ln.events if ev.name.startswith(PREFIX)]
            if events:
                lines.append(events)
    return queries_of(lines)


def window_queries(rec: dict) -> List[dict]:
    """The queries of a ``--trace 1`` run's traced window; none where
    the run was not traced or its program records no spans."""
    if not (rec.get("trace") or {}).get("window"):
        return []
    from bench.lib import trace as tr
    from bench.lib.harness import OUT_DIR
    try:
        path = tr.find_xplane(os.path.join(OUT_DIR, "trace", "window"))
    except FileNotFoundError:
        return []
    return read(path)


def self_ms(rec: dict, name: str) -> Optional[float]:
    """Mean self time of span ``name`` per query of the window, in ms."""
    qs = window_queries(rec)
    return 1e3 * sum(q["self"].get(name, 0.0) for q in qs) / len(qs) \
        if qs else None


def counter(rec: dict, key: str, scale: float = 1.0) -> Optional[float]:
    """Mean of counter ``key`` per query of the window."""
    qs = [q for q in window_queries(rec) if key in q["stats"]]
    return scale * sum(q["stats"][key] for q in qs) / len(qs) \
        if qs else None
