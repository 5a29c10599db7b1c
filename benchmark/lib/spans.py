"""The benchmark's own spans, around the calls INTO each layer.

A span is kept in memory as `(name, start_ns, end_ns)` on the host's
monotonic clock and, at the same time, written into the profiler's
trace as a `jax.profiler.TraceAnnotation`, so that a traced run has
the host's activity on the device trace's clock.  Spans inside the
program are a later `tracing` PR; these sit in the benchmark's files.
"""

from __future__ import annotations

import contextlib
import time

import jax


class SpanLog:
    def __init__(self):
        self.spans = []           # (name, start_ns, end_ns)

    @contextlib.contextmanager
    def span(self, name: str):
        with jax.profiler.TraceAnnotation(name):
            t0 = time.perf_counter_ns()
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter_ns()))

    def total_ms(self, name: str, start_ns: int, end_ns: int) -> float:
        """Milliseconds spent in spans called `name` that began inside
        `[start_ns, end_ns)`."""
        return sum(e - s for n, s, e in self.spans
                   if n == name and start_ns <= s < end_ns) / 1e6
