"""Host spans and counters of one request or one hierarchy build.

A :class:`SpanLog` belongs to one solve (``SolveReport.spans``) or one
build (``Hierarchy.spans``) and is written by one thread.  ``span(name)``
records ``Span(name, parent, t0_ns, t1_ns)`` on
``time.perf_counter_ns()``, ``parent`` being the index of the span open
on this log when it started (-1 for none), and opens
``jax.profiler.TraceAnnotation("pq." + name)`` around the same code, so
that a profile taken meanwhile holds the span on the device trace's
clock.  Spans stay in memory; nothing reads them back into control flow.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

PREFIX = "pq."


class Span(NamedTuple):
    name: str
    parent: int     # index of the enclosing span in the log, or -1
    t0_ns: int
    t1_ns: int      # -1 while the span is open


class SpanLog:
    def __init__(self):
        self.spans: List[Span] = []
        self.counters: Dict[str, float] = {}
        self._open: List[int] = []

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[TraceAnnotation]:
        """Record the enclosed code as span ``name``; yields the trace
        annotation, to which ``set_metadata(**values)`` attaches values."""
        i = len(self.spans)
        parent = self._open[-1] if self._open else -1
        with TraceAnnotation(PREFIX + name) as ann:
            t0 = time.perf_counter_ns()
            self.spans.append(Span(name, parent, t0, -1))
            self._open.append(i)
            try:
                yield ann
            finally:
                self._open.pop()
                self.spans[i] = Span(name, parent, t0,
                                     time.perf_counter_ns())

    def add(self, counter: str, value: float = 1) -> None:
        self.counters[counter] = self.counters.get(counter, 0) + value

    def seconds(self, name: str, *, own: bool = False) -> float:
        """Total seconds of the closed spans called ``name``; ``own``
        leaves out the time their child spans cover."""
        dur = [s.t1_ns - s.t0_ns if s.t1_ns >= 0 else 0 for s in self.spans]
        out = 0
        for i, s in enumerate(self.spans):
            if s.name == name:
                out += dur[i]
            if own and s.parent >= 0 and self.spans[s.parent].name == name:
                out -= dur[i]
        return out * 1e-9


def span(log: Optional[SpanLog], name: str):
    """``log.span(name)``, or nothing where the caller keeps no log."""
    return log.span(name) if log is not None else contextlib.nullcontext()
