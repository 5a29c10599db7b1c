"""Profiler — host event recording + device trace.

Reference: paddle/fluid/platform/profiler.{h,cc} (`RecordEvent` RAII
markers, EnableProfiler/DisableProfiler aggregation tables,
profiler.proto) + DeviceTracer over CUPTI (device_tracer.h:43) +
tools/timeline.py chrome://tracing conversion, and the Python surface
fluid/profiler.py:131,198,255 (SURVEY.md §5.1).

TPU-native re-design: device-side tracing is jax.profiler (XLA's
profiler; TensorBoard/perfetto format replaces chrome://tracing), so
this module provides (a) the RecordEvent host-marker API bridged onto
jax.profiler.TraceAnnotation so host phases appear inside the XLA trace,
(b) a host-side event table with the reference's summary-report shape,
and (c) start/stop entry points that drive jax.profiler.

Since ISSUE 6, RecordEvent and `export_chrome_tracing` are thin
adapters over the span layer in `paddle_tpu.obs` — ONE trace format,
one event path (docs/observability.md).  The aggregate event table
(the reference's summary report) and the StatRegistry/timer tables
below are unchanged; `timed()` additionally records a span when
tracing is enabled, so every instrumented pipeline stage shows up in
the obs trace for free.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict
from typing import NamedTuple, Optional

_STATE = threading.local()
_ENABLED = [False]
_EVENTS = defaultdict(lambda: {"calls": 0, "total": 0.0, "min": None,
                               "max": 0.0})
_EVENTS_LOCK = threading.Lock()
_TRACE_DIR = [None]
# True when start_profiler itself enabled obs tracing (and should
# therefore disable it again on stop); an obs session the user opened
# explicitly is never touched
_OBS_OWNED = [False]

_OBS = None


def _tracing():
    """The obs span tracer module, lazily bound (import-cycle safe:
    obs.cost imports this module lazily too)."""
    global _OBS
    if _OBS is None:
        from ..obs import tracing as _mod

        _OBS = _mod
    return _OBS


class RecordEvent:
    """RAII host event marker (reference: profiler.h:127).  Usable as a
    context manager or start()/end() pair; nests into the XLA trace via
    jax.profiler.TraceAnnotation when device tracing is on."""

    def __init__(self, name, event_type="UserDefined"):
        self.name = name
        self._t0 = None
        self._ann = None

    def begin(self):
        self._t0 = time.perf_counter()
        if _TRACE_DIR[0] is not None:
            import jax

            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()

    def end(self):
        if self._t0 is None:
            return
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if _ENABLED[0]:
            with _EVENTS_LOCK:
                e = _EVENTS[self.name]
                e["calls"] += 1
                e["total"] += dt
                e["min"] = dt if e["min"] is None else min(e["min"], dt)
                e["max"] = max(e["max"], dt)
        # the span layer is the one timeline path (ISSUE 6): a
        # RecordEvent is just a span recorded retroactively — begin/end
        # pairs may legally cross threads, so it never touches the
        # thread-local span stack
        _tracing().TRACER.add_span(self.name, self._t0, dt)
        self._t0 = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()


def start_profiler(state="All", tracer_option="Default", trace_dir=None):
    """(reference: fluid/profiler.py:198 start_profiler).  state 'All'
    also starts the XLA device trace when trace_dir is given."""
    _ENABLED[0] = True
    with _EVENTS_LOCK:
        _EVENTS.clear()
    tr = _tracing().TRACER
    if not tr.enabled:
        # a fresh session must not export the previous session's spans;
        # an obs session the user opened explicitly stays untouched
        tr.enable(reset=True)
        _OBS_OWNED[0] = True
    if trace_dir is not None:
        import jax

        jax.profiler.start_trace(trace_dir)
        _TRACE_DIR[0] = trace_dir


def stop_profiler(sorted_key="total", profile_path=None):
    """(reference: fluid/profiler.py:255).  Prints the event table and
    stops the XLA trace; returns the table rows."""
    _ENABLED[0] = False
    if _OBS_OWNED[0]:
        _tracing().TRACER.disable()
        _OBS_OWNED[0] = False
    if _TRACE_DIR[0] is not None:
        import jax

        jax.profiler.stop_trace()
        _TRACE_DIR[0] = None
    with _EVENTS_LOCK:
        rows = [{"name": k, **v, "avg": v["total"] / max(v["calls"], 1)}
                for k, v in _EVENTS.items()]
    key = {"total": "total", "calls": "calls", "max": "max", "min": "min",
           "ave": "avg"}.get(sorted_key, "total")
    rows.sort(key=lambda r: r[key] or 0, reverse=True)
    if rows:
        print(f"{'Event':<40}{'Calls':>8}{'Total(s)':>12}{'Avg(s)':>12}"
              f"{'Min(s)':>12}{'Max(s)':>12}")
        for r in rows:
            print(f"{r['name']:<40}{r['calls']:>8}{r['total']:>12.6f}"
                  f"{r['avg']:>12.6f}{(r['min'] or 0):>12.6f}"
                  f"{r['max']:>12.6f}")
    if profile_path:
        import json

        with open(profile_path, "w") as f:
            json.dump(rows, f)
    return rows


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None,
             trace_dir=None):
    """(reference: fluid/profiler.py:131)."""
    start_profiler(state, trace_dir=trace_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


def reset_profiler():
    with _EVENTS_LOCK:
        _EVENTS.clear()
    _tracing().TRACER.reset()


def export_chrome_tracing(path):
    """Write the recorded spans as a chrome://tracing / Perfetto JSON
    file.  Thin adapter (ISSUE 6) over `paddle_tpu.obs.export_trace` —
    RecordEvent phases, executor/serving/feed-pipeline spans and their
    cross-thread flow links all land in the ONE trace.  Device-side
    events live in the XLA trace jax.profiler writes to `trace_dir`.

    Returns the number of span events written."""
    from .. import obs

    return obs.export_trace(path)


# ---------------------------------------------------------------------------
# StatRegistry counters (reference platform/monitor.h:77 StatRegistry +
# the STAT_ADD/STAT_RESET macros, exported as core.get_int_stats)
# ---------------------------------------------------------------------------

_STATS: dict = {}
_STATS_LOCK = threading.Lock()

# float accumulators for the executor hot-path pipeline stages
# (host_feed_ms / dispatch_ms / sync_ms): the async dispatch-ahead loop
# reports where host wall time goes per step, and `executor_sync_count`
# (a _STATS int) counts every device->host materialization so tests can
# assert a loop performed ZERO per-step transfers
_TIMES: dict = {}


def stat_add(name: str, value: int = 1) -> None:
    """STAT_ADD equivalent: bump a named global counter."""
    with _STATS_LOCK:
        _STATS[name] = _STATS.get(name, 0) + int(value)


def stat_set(name: str, value: int) -> None:
    with _STATS_LOCK:
        _STATS[name] = int(value)


def stat_max(name: str, value: int) -> None:
    """High-water-mark gauge: keep the max ever observed (ring
    occupancy, in-flight steps) so a test can assert overlap happened
    without sampling the gauge at exactly the right moment."""
    with _STATS_LOCK:
        cur = _STATS.get(name)
        if cur is None or int(value) > cur:
            _STATS[name] = int(value)


def stat_reset(name: str = None) -> None:
    """STAT_RESET: clear one counter, or all of them."""
    with _STATS_LOCK:
        if name is None:
            _STATS.clear()
        else:
            _STATS.pop(name, None)


def get_int_stats() -> dict:
    """Snapshot of every counter (reference core.get_int_stats)."""
    with _STATS_LOCK:
        return dict(_STATS)


# ---------------------------------------------------------------------------
# Hot-path pipeline timers (ISSUE 1): millisecond accumulators for the
# async Executor loop's stages, separate from the RecordEvent table so
# they cost one lock + one float add per step even when profiling is off
# ---------------------------------------------------------------------------

def time_add(name: str, ms: float) -> None:
    """Accumulate `ms` milliseconds on a named pipeline stage
    (host_feed_ms / dispatch_ms / sync_ms)."""
    with _STATS_LOCK:
        _TIMES[name] = _TIMES.get(name, 0.0) + float(ms)


def time_set(name: str, ms: float) -> None:
    """Overwrite a pipeline gauge expressed in milliseconds (e.g.
    `shard_skew_ms`, which is a per-epoch measurement, not a running
    accumulation)."""
    with _STATS_LOCK:
        _TIMES[name] = float(ms)


def time_reset(name: str = None) -> None:
    with _STATS_LOCK:
        if name is None:
            _TIMES.clear()
        else:
            _TIMES.pop(name, None)


def get_time_stats() -> dict:
    """Snapshot of the pipeline stage accumulators, in milliseconds."""
    with _STATS_LOCK:
        return dict(_TIMES)


@contextlib.contextmanager
def timed(name: str):
    """Accumulate the with-block's wall time onto `name` (ms).  When
    span tracing is on, the interval is also recorded as a span, so
    every timed pipeline stage (host_feed_ms, compile_ms, sync_ms,
    serving_*_ms, ...) appears in the obs trace without a second
    instrumentation site."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        time_add(name, dt * 1e3)
        _tracing().TRACER.add_span(name, t0, dt)


# the program's stages in a profiler trace are called `pt.<stage>`
ANNOTATION_PREFIX = "pt."

# ---------------------------------------------------------------------------
# The phase log (ISSUE 36): a stage whose name starts with `setup.` is a
# phase of the process's own set-up (import, weights, trace, lower,
# compile or cache load, ...).  Set-up has hundreds of events, not one a
# step, so these are ALWAYS kept, tracer and profiler session on or off:
# a bounded in-memory list on `time.perf_counter`, read by
# `get_phases()` (docs/observability.md "Start-up"; the benchmark's
# `setup.*` metrics partition `setup_s` with it).
# ---------------------------------------------------------------------------

PHASE_PREFIX = "setup."
PHASE_CAPACITY = 16384


class Phase(NamedTuple):
    """One finished phase.  `parent` is the name of the `setup.*` stage
    that was open on the recording thread (None at top level): what the
    site knew, not an interval test — a phase JAX reports when it ends
    (`add_phase`) may cover stages that closed before it."""
    name: str
    start_s: float              # time.perf_counter()
    dur_s: float
    parent: Optional[str]
    attrs: Optional[dict] = None


_PHASES: list = []
_PHASES_LOCK = threading.Lock()


def _open_phases() -> list:
    """This thread's stack of open `setup.*` stage names."""
    stack = getattr(_STATE, "phases", None)
    if stack is None:
        stack = _STATE.phases = []
    return stack


def add_phase(name: str, start_s: float, dur_s: float,
              parent: Optional[str] = None,
              attrs: Optional[dict] = None) -> None:
    """Record a phase retroactively (`start_s` on `time.perf_counter`),
    for a site that learns of it when it ends.  `parent` defaults to
    the innermost `setup.*` stage open on this thread.  Beyond
    `PHASE_CAPACITY` records the phase is dropped and counted in
    `setup_phases_dropped_total`."""
    if parent is None:
        stack = _open_phases()
        parent = stack[-1] if stack else None
    with _PHASES_LOCK:
        if len(_PHASES) < PHASE_CAPACITY:
            _PHASES.append(Phase(name, start_s, dur_s, parent, attrs))
            return
    stat_add("setup_phases_dropped_total")


def get_phases() -> list:
    """The phases recorded so far, in the order they ENDED."""
    with _PHASES_LOCK:
        return list(_PHASES)


def reset_phases() -> None:
    with _PHASES_LOCK:
        _PHASES.clear()


def phase_totals() -> dict:
    """`{name: seconds}`: per name the length of the union of its
    intervals (a jit inside a jit is two `setup.trace` phases over the
    same seconds), children not taken out — a one-line summary for a
    log; the benchmark's `lib/setup_phases.py` does the partition."""
    by_name = defaultdict(list)
    for p in get_phases():
        by_name[p.name].append((p.start_s, p.start_s + p.dur_s))
    out = {}
    for name, spans in by_name.items():
        total, reach = 0.0, float("-inf")
        for s, e in sorted(spans):
            if e > reach:
                total += e - max(s, reach)
                reach = e
        out[name] = total
    return out


class stage:
    """The one instrument of a site that does a named piece of work:
    the with-block is a `jax.profiler.TraceAnnotation` called
    `pt.<name>`, so that whoever opened a profiler trace
    (`jax.profiler.start_trace`, the benchmark, `obs.profile_window`)
    finds the stage in it on the device's clock; on exit its wall time
    goes onto the millisecond timer `timer` and, when span tracing is
    on, into the obs trace as a span called `name` (with `attrs`).
    Outside a profiler session the annotation is a flag test.

    Per-step sites (the Executor's feed copies, dispatch and host
    materialisation: `host_feed_ms` / `dispatch_ms` / `sync_ms`) pay
    that and no more.  A stage called `setup.<phase>` is also kept in
    the phase log, always (see `add_phase`)."""

    __slots__ = ("name", "timer", "attrs", "_phase", "_annotation", "_t0")

    def __init__(self, name: str, timer: str | None = None,
                 attrs: dict | None = None):
        self.name = name
        self.timer = timer
        self.attrs = attrs
        self._phase = name.startswith(PHASE_PREFIX)

    def __enter__(self):
        import jax

        self._annotation = jax.profiler.TraceAnnotation(
            ANNOTATION_PREFIX + self.name, **self.attrs) if self.attrs \
            else jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + self.name)
        self._annotation.__enter__()
        if self._phase:
            _open_phases().append(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if self.timer is not None:
            time_add(self.timer, dt * 1e3)
        if self._phase:
            # closes correctly when the body raised: this stage's entry
            # is the innermost one left, whatever leaked above it
            stack = _open_phases()
            while stack and stack.pop() != self.name:
                pass
            add_phase(self.name, self._t0, dt, attrs=self.attrs)
        _tracing().TRACER.add_span(self.name, self._t0, dt,
                                   attrs=self.attrs)
        return False


def count_sync(n: int = 1) -> None:
    """Record a device->host materialization on the executor hot path.
    Every sanctioned sync point calls this; the async-loop test asserts
    the counter stays flat across steps."""
    stat_add("executor_sync_count", n)
