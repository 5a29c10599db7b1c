"""Exact counts and host-side timers the benchmark reads.

Three sources, one flat dict per snapshot: the program's profiler
counters and timers (`paddle_tpu.profiler`), and JAX's own compile
events (`jax.monitoring`), which also see the compiles of functional
steps that never pass through the `Executor`.  A metric is the
difference of two snapshots (`run.py: Run.window_delta`)."""

from __future__ import annotations

import collections

import jax

# every compile request reaches this event, served from the persistent
# cache or not: none may happen inside a measured window
COMPILE_REQUEST = "/jax/core/compile/backend_compile_duration"
PERSISTENT_HIT = "/jax/compilation_cache/cache_hits"
PERSISTENT_MISS = "/jax/compilation_cache/cache_misses"


class Counters:
    """Installs the JAX listeners once; `snapshot()` is cheap and takes
    no device time."""

    def __init__(self):
        self._jax = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **kwargs):
        self._jax[event] += 1

    def _on_duration(self, event, duration_secs, **kwargs):
        self._jax[event] += 1

    def snapshot(self) -> dict:
        from paddle_tpu import profiler

        out = dict(self._jax)
        out.update(profiler.get_int_stats())
        out.update(profiler.get_time_stats())
        return out
