"""Compile time as JAX reports it, and the benchmark's own spans."""
from __future__ import annotations

import contextlib
import threading
import time


class CompileClock:
    """Counts executables JAX builds (compiled, or loaded from the
    persistent cache) and sums the seconds it spent on them."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.secs = 0.0
        self.count = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            with self._lock:
                self.secs += duration
                self.count += 1

    def read(self):
        with self._lock:
            return self.secs, self.count


class Spans:
    """Named host-clock spans: seconds per name."""

    def __init__(self):
        self.s = {}

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.perf_counter()
        yield
        self.s[name] = self.s.get(name, 0.0) + time.perf_counter() - t0
