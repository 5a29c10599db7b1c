"""Shared bounded compile-cache machinery.

One LRU shape, three tenants: `Executor._cache` (program-signature ->
compiled entry), `CompiledProgram._cache` (the data-parallel twin), and
the serving subsystem's bucketed entry cache
(paddle_tpu/serving/bucketing.py).  Extracted from the ad-hoc
OrderedDict loops the first two grew independently (VERDICT r4 weak #7
bounded both; this module is the single implementation) so the serving
engine's bucket cache is literally the same machinery, not a third
copy.

Also here (ISSUE 36): the listener that turns JAX's own compile events
into start-up phases, installed where this module is imported
(`install_phase_listener`).

Thread safety: the serving engine hits its cache from the dispatch loop
AND the off-path compiler thread, so every operation takes the lock.
The training executor is single-threaded per instance; the lock is
uncontended there and costs one atomic acquire per step.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Callable, Iterator, Optional, Tuple

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def persistent_cache_dirs() -> Tuple[str, str]:
    """Where compiled code persists across processes: `(jax_dir,
    aot_dir)` — JAX's persistent compilation cache and the default of
    FLAGS_aot_cache_dir (fluid/aot_cache.py).

    The rule: if `JAX_COMPILATION_CACHE_DIR` is set, JAX's cache lives
    there (JAX reads the variable itself; code sets no other
    directory) and the AOT cache is its `paddle_aot/` subdirectory.
    If it is not set, they are `<checkout>/.jax_cache` and
    `<checkout>/artifacts/aot_cache`, resolved from this package's own
    location — never from the working directory, a temporary name, a
    pid or the time: the path is part of the cache key, so a directory
    that moves never hits.  `PADDLE_AOT_CACHE_DIR` overrides the AOT
    half either way (fluid/flags.py)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env, os.path.join(env, "paddle_aot")
    return (os.path.join(_CHECKOUT, ".jax_cache"),
            os.path.join(_CHECKOUT, "artifacts", "aot_cache"))


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache under the rule of
    `persistent_cache_dirs` (chip_smoke.py, bench.py; tools/ci.sh
    exports the same directory to the processes it starts).  Returns
    the directory in use."""
    import jax

    jax_dir, _ = persistent_cache_dirs()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", jax_dir)
    # the serving engine stages many sub-second compiles; a restart
    # should find those too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax_dir


# JAX's own events -> phases of the profiler's phase log (ISSUE 36).
# They also see the steps that never pass the Executor (`jax.jit(...)
# .lower().compile()`), and every process pays trace and lower whatever
# the caches hold.  Each carries `time.time()` stamps and `fun_name`.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_JAX_PHASES = {_TRACE: "setup.trace", _LOWER: "setup.lower",
               _BACKEND_COMPILE: "setup.backend_compile"}
_LISTENING = [False]
# per thread: `.open`, how many trace and lower events are under way
# (every `jnp` function is a jit of its own, so tracing or lowering a
# model fires tens of thousands of trace events inside the one that
# matters), and `.retrieved`, the compile under way was a cache hit
_JAX = threading.local()


def _on_jax_start(event, start_time, **_):
    if event == _TRACE or event == _LOWER:
        _JAX.open = getattr(_JAX, "open", 0) + 1


def _on_jax_time_span(event, start_time, end_time, fun_name="", **_):
    phase = _JAX_PHASES.get(event)
    if phase is None:
        return
    if event != _BACKEND_COMPILE:
        _JAX.open = max(getattr(_JAX, "open", 0) - 1, 0)
        if _JAX.open:
            return      # inside another trace or lower, which covers it
    from ..profiler import add_phase, stat_add

    if phase == "setup.trace":
        stat_add("jax_traces_total")
    elif event == _BACKEND_COMPILE:
        # served from the persistent cache: the retrieval event fired
        # inside this interval, and nothing was compiled
        if getattr(_JAX, "retrieved", False):
            _JAX.retrieved = False
            phase = "setup.cache_load"
        else:
            stat_add("backend_compiles_total")
    # JAX stamps time.time(); the phase log is on perf_counter
    to_perf = time.perf_counter() - time.time()
    add_phase(phase, start_time + to_perf, end_time - start_time,
              attrs={"fun_name": fun_name})


def _on_jax_duration(event, duration_secs, **_):
    if event != _CACHE_RETRIEVAL:
        return
    from ..profiler import add_phase

    # a duration only, reported as the retrieval ends
    _JAX.retrieved = True
    add_phase("setup.cache_load", time.perf_counter() - duration_secs,
              duration_secs)


def install_phase_listener() -> None:
    """Listen to JAX's trace, lower, backend-compile and cache-retrieval
    events and keep each as a `setup.*` phase (`profiler.get_phases()`):
    `setup.trace`, `setup.lower`, `setup.backend_compile` — or
    `setup.cache_load` where the persistent cache served it —, of
    traces and lowerings the outermost only; counters
    `jax_traces_total` and `backend_compiles_total`.  Called once,
    where this module is imported; calling it again does nothing.  For
    any other event a listener is one dictionary lookup."""
    if _LISTENING[0]:
        return
    import jax

    jax.monitoring.register_scalar_listener(_on_jax_start)
    jax.monitoring.register_event_time_span_listener(_on_jax_time_span)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _LISTENING[0] = True


install_phase_listener()


class CompileCache:
    """Bounded LRU for compiled entries.

    `stat_prefix` wires hit/miss/eviction counters into
    paddle_tpu.profiler's StatRegistry (`<prefix>_cache_hits`,
    `<prefix>_cache_misses`, `<prefix>_cache_evictions`) so cache
    behavior is observable wherever the tenant lives.
    """

    def __init__(self, capacity: int, stat_prefix: Optional[str] = None,
                 on_evict: Optional[Callable[[Any, Any], None]] = None):
        if capacity < 1:
            raise ValueError(f"CompileCache capacity must be >= 1, "
                             f"got {capacity}")
        self.capacity = capacity
        self._od: "collections.OrderedDict[Any, Any]" = \
            collections.OrderedDict()
        self._lock = threading.RLock()
        self._stat_prefix = stat_prefix
        # eviction must actually RELEASE what the entry holds (device
        # const/feed arrays, the AOT executable) — an evicted-but-
        # referenced entry is a silent HBM leak.  The callback runs
        # outside the lock; exceptions are swallowed (accounting must
        # never break a put).
        self._on_evict = on_evict

    def _stat(self, name: str) -> None:
        if self._stat_prefix is not None:
            from ..profiler import stat_add

            stat_add(f"{self._stat_prefix}_cache_{name}")

    def get(self, key) -> Optional[Any]:
        """Entry for `key` (refreshing recency) or None."""
        with self._lock:
            entry = self._od.get(key)
            if entry is not None:
                self._od.move_to_end(key)
                self._stat("hits")
            return entry

    def put(self, key, value) -> None:
        evicted = []
        with self._lock:
            self._od[key] = value
            self._od.move_to_end(key)
            while len(self._od) > self.capacity:
                evicted.append(self._od.popitem(last=False))
                self._stat("evictions")
        if self._on_evict is not None:
            for ekey, evalue in evicted:
                try:
                    self._on_evict(ekey, evalue)
                except Exception:  # noqa: BLE001 - see __init__
                    pass

    def get_or_build(self, key, builder: Callable[[], Any]) -> Any:
        """Entry for `key`, building (and caching) it on miss.

        The builder runs OUTSIDE the lock: compilation takes seconds
        and must not serialize unrelated cache lookups.  Two threads
        racing the same key may both build; last-put wins — acceptable
        for compiled executables (identical, idempotent)."""
        entry = self.get(key)
        if entry is not None:
            return entry
        self._stat("misses")
        entry = builder()
        self.put(key, entry)
        return entry

    def __contains__(self, key) -> bool:
        with self._lock:
            return key in self._od

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)

    def __iter__(self) -> Iterator:
        with self._lock:
            return iter(list(self._od))

    def keys(self):
        with self._lock:
            return list(self._od)

    def values(self):
        with self._lock:
            return list(self._od.values())

    def items(self):
        with self._lock:
            return list(self._od.items())

    def clear(self) -> None:
        with self._lock:
            self._od.clear()
