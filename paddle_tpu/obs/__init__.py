"""paddle_tpu.obs — end-to-end observability (ISSUE 6 + 7 tentpoles).

One layer, four surfaces:

* **Span tracing** (`obs.span` / flow ids / `obs.export_trace`): causal
  wall-time spans across every thread of the stack — Executor dispatch,
  compile-cache misses (transform -> verify -> XLA compile), the feed
  pipeline's producer/ring, and the serving engine's admission ->
  coalesce -> dispatch -> complete pipeline, linked across threads by
  flow ids.  Export is Chrome-trace/Perfetto JSON: ONE file shows a
  train step or a serving request end to end.

* **Cost attribution** (`obs.cost`): per-executable FLOPs/bytes from
  XLA `cost_analysis`, cached with the CompileCache entry at compile
  time and combined with measured dispatch intervals into live
  `mfu_pct` / `hbm_bw_pct` gauges; plus the `collective_bytes_<type>`
  bytes-on-wire counters the quantized-collectives ROADMAP item will
  assert against.

* **Per-op attribution** (`obs.opprof` / `obs.op_profile(program)`):
  every op lowers inside `jax.named_scope` with its greppable
  `program#<id>/block<idx>/op<id>:<type>[pass=...]` provenance, and
  each compile-cache miss walks the AOT executable's HLO to fold
  per-instruction FLOPs/bytes/fusions/relayouts back onto source
  Program ops — through the transform pipeline's rewrites — so the
  whole-program MFU number decomposes into named ops
  (`tools/tracetool.py top-ops`, BENCH `detail.op_profile`).

* **Snapshot** (`obs.snapshot()`): one structured export — span
  summary + every profiler timer/counter + the cost gauges + the
  per-op profiles — tagged with this host's process index
  (`all_hosts=True` gathers every host's tables into one merged
  view), embedded by bench.py in BENCH JSON `detail.obs` and by
  `obs.export_trace` in the trace file's otherData (so
  `tools/tracetool.py` can attribute stalls and report MFU from the
  trace alone).

Enable/disable at runtime (`obs.enable()` / `obs.disable()`); disabled
tracing is a single attribute check per site — the async hot path's
zero-sync, zero-transfer contract is untouched either way
(docs/observability.md).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import json as _json
import os as _os
import threading as _threading

from . import cost
from . import devprof
from . import memprof
from . import numerics
from . import opprof
from . import telemetry
from .tracing import NULL_SPAN, TRACER, Tracer  # noqa: F401

__all__ = ["span", "add_span", "new_flow", "attach_flow", "current_span",
           "enable", "disable", "enabled", "reset", "snapshot",
           "export_trace", "op_profile", "profile_window", "roofline",
           "mem_profile", "memory_ledger", "publish_mem_oom",
           "bisect_nonfinite", "numerics_report",
           "cost", "devprof", "memprof", "numerics", "opprof",
           "telemetry",
           "start_telemetry", "stop_telemetry", "maybe_start_telemetry",
           "telemetry_epoch_refresh", "telemetry_handle", "TRACER",
           "NULL_SPAN", "Tracer"]


def enable(reset: bool = False) -> None:
    """Turn span recording on (optionally clearing the buffer along
    with any completed devprof captures — see reset())."""
    TRACER.enable(reset=reset)
    if reset:
        devprof.reset()


def disable() -> None:
    TRACER.disable()


def enabled() -> bool:
    return TRACER.enabled


def reset() -> None:
    """Clear the span buffer and drop counter (enabled state kept).
    Completed devprof captures are cleared too — a fresh trace must
    not merge device tracks from a window profiled before the
    reset."""
    TRACER.reset()
    devprof.reset()


def span(name: str, flow=None, attrs: Optional[dict] = None):
    """Context manager recording one span on this thread's track; the
    shared no-op singleton while tracing is disabled."""
    return TRACER.span(name, flow=flow, attrs=attrs)


def add_span(name: str, t0: float, dur: float, flow=None,
             attrs: Optional[dict] = None) -> None:
    """Record a span retroactively (perf_counter seconds)."""
    TRACER.add_span(name, t0, dur, flow=flow, attrs=attrs)


def new_flow() -> int:
    """Mint a process-unique flow id linking spans across threads."""
    return TRACER.new_flow()


def attach_flow(flow) -> None:
    TRACER.attach_flow(flow)


def current_span():
    return TRACER.current_span()


def op_profile(program=None, label: Optional[str] = None) \
        -> Optional[Dict[str, Any]]:
    """The per-op cost-attribution table for `program` (matched by the
    SOURCE prog_id its rows attribute to), for an exact executable
    `label`, or the most recently compiled executable when neither is
    given.  None until a compile-cache miss has captured one.  Rows
    carry `program#<id>/block<idx>/op<id>:<type>[pass=...]` provenance
    plus flops/bytes shares, fusion membership, transpose/relayout
    counts and collective payload bytes (docs/observability.md)."""
    prog_id = getattr(program, "prog_id", None) \
        if program is not None else None
    return opprof.profile_for(prog_id=prog_id, label=label)


def profile_window(steps: Optional[int] = None,
                   label: Optional[str] = None):
    """Arm a bounded *measured* device-time capture window
    (obs/devprof.py): `jax.profiler` trace around the next dispatches,
    then `devprof.device_time` on what it wrote: device seconds by the
    program's own names (`result["by_name"]`).  Use as a
    context manager, or pass `steps=N` and let the Executor training
    loop auto-stop it."""
    return devprof.profile_window(steps=steps, label=label)


def roofline(program=None, label: Optional[str] = None) \
        -> Optional[Dict[str, Any]]:
    """The measured roofline for `program` (matched by the SOURCE
    prog_id the window's join attributed time to), for an exact window
    `label`, or the most recent window when neither is given: per-op
    measured time vs opprof FLOPs/bytes -> achieved-FLOPs/achieved-BW
    and a compute-/memory-/relayout-bound verdict.  None until a
    profile_window has finished."""
    prog_id = getattr(program, "prog_id", None) \
        if program is not None else None
    res = devprof.result_for(prog_id=prog_id, label=label)
    return res.get("roofline") if res else None


def mem_profile(program=None, label: Optional[str] = None) \
        -> Optional[Dict[str, Any]]:
    """The static memory-attribution table for `program` (matched by
    the SOURCE prog_id its rows attribute to), for an exact executable
    `label`, or the most recently compiled executable when neither is
    given.  None until a compile-cache miss has captured one.  Rows
    attribute the executable's temp-buffer peak (`memory_analysis()`)
    to `program#<id>/block<idx>/op<id>:<type>` provenance, with the
    remainder in an explicit `unattributed` bin
    (docs/observability.md)."""
    prog_id = getattr(program, "prog_id", None) \
        if program is not None else None
    return memprof.profile_for(prog_id=prog_id, label=label)


def memory_ledger() -> Dict[str, Any]:
    """The live device-memory ledger: every byte the framework
    intentionally holds on device (scope vars, compile-cache
    const/feed caches, feed-ring staged batches, KV pages, in-flight
    ckpt snapshots), reconciled against `device.memory_stats()` —
    `bytes_in_use = ledger total + executable temp + unattributed`,
    with the residual explicit.  Device fields are None on backends
    without memory_stats (CPU)."""
    return memprof.memory_ledger()


def publish_mem_oom(label: str = "", error: Any = "") -> Dict[str, Any]:
    """RESOURCE_EXHAUSTED forensics: assemble the mem_oom report
    (ledger at failure time + the failing executable's top static temp
    buffers) and publish it as a flight bundle.  With a live telemetry
    session the watchdog writes a full bundle (series + memory.json);
    otherwise a minimal bundle lands in the PADDLE_OBS_FLIGHT_DIR (if
    set).  Always returns the report; never raises — this runs on the
    dispatch except-path."""
    doc = memprof.oom_report(label=label, error=error)
    handle = _TELEMETRY
    try:
        if handle is not None and handle.watchdog is not None:
            handle.watchdog.trigger(
                "mem_oom",
                f"RESOURCE_EXHAUSTED dispatching {label or '<program>'}"
                f": {str(error)[:200]}")
        else:
            flight_dir = _obs_flag("obs_flight_dir",
                                   "PADDLE_OBS_FLIGHT_DIR", "", str)
            if flight_dir:
                telemetry.write_standalone_bundle(
                    flight_dir, "mem_oom",
                    f"RESOURCE_EXHAUSTED dispatching "
                    f"{label or '<program>'}",
                    {"memory.json": doc})
    except Exception:  # noqa: BLE001 - forensics must not mask the OOM
        pass
    return doc


def bisect_nonfinite(program, feed=None, scope=None, fetch_list=None,
                     transform: bool = True) -> Dict[str, Any]:
    """First-NaN bisection (obs/numerics.py): transform `program`
    exactly as the executor would, replay it op-by-op eagerly over
    `scope` + `feed`, and name the FIRST op in program order whose
    output goes non-finite — provenance with [pass=...] tags,
    construction stack (`op_callstack`), and input stats.  Offline
    forensics; under `PADDLE_OBS_NUMERICS=bisect` the executor runs
    the same replay automatically when the async NaN monitor fires."""
    return numerics.bisect_nonfinite(program, feed=feed, scope=scope,
                                     fetch_list=fetch_list,
                                     transform=transform)


def numerics_report() -> Dict[str, Any]:
    """The full numeric-health document (`numerics.json` in flight
    bundles): per-op nan/inf/absmax/l2 aggregate keyed by provenance,
    training-health gauges, the AMP loss scale, and the last hit +
    bisection report.  Drains pending stats first."""
    return numerics.numerics_doc()


def _process_index() -> int:
    try:
        from ..distributed.parallel import _safe_process_index

        return int(_safe_process_index())
    except Exception:  # noqa: BLE001 - no jax/dist: single host
        return 0


def _local_tables() -> Dict[str, Any]:
    from .. import profiler

    stats = profiler.get_int_stats()
    times = profiler.get_time_stats()
    return {
        "counters": dict(stats),
        "timers_ms": {k: round(float(v), 3) for k, v in times.items()},
    }


def _gather_host_tables(local: Dict[str, Any]) -> Dict[str, Any]:
    """All-gather each host's counter/timer tables (the shard_skew_ms
    epoch-boundary idiom from dataset.feed_pipeline: fine OFF the hot
    path, degrades to the local view when gathering is unavailable).
    Tables are variable-length, so the JSON payload is length-gathered
    first, then gathered as padded byte arrays."""
    import json as _json

    from ..dataset.feed_pipeline import host_topology

    index, count = host_topology()
    if count <= 1:
        return {str(index): local}
    try:
        import numpy as np
        from jax.experimental import multihost_utils

        data = _json.dumps(local).encode()
        lens = np.asarray(multihost_utils.process_allgather(
            np.int32(len(data)))).ravel()
        buf = np.zeros(int(lens.max()), np.uint8)
        buf[:len(data)] = np.frombuffer(data, np.uint8)
        bufs = np.asarray(multihost_utils.process_allgather(buf))
        out = {}
        for i, n in enumerate(lens):
            out[str(i)] = _json.loads(
                bytes(bufs[i, :int(n)]).decode())  # sync-ok: snapshot boundary
        return out
    except Exception:  # noqa: BLE001 - observability, not control flow
        return {str(index): local}


def snapshot(all_hosts: bool = False) -> Dict[str, Any]:
    """One structured observability export: span summary, every
    profiler counter/timer, cost gauges, bytes-on-wire counters, and
    the per-op cost-attribution tables.  Tagged with this host's
    `jax.process_index()`; `all_hosts=True` additionally all-gathers
    every host's counter/timer tables into `hosts` (a collective —
    every process of a pod run must call it, e.g. at an epoch/export
    boundary) so the pod exports ONE merged view."""
    local = _local_tables()
    snap = {
        "host": _process_index(),
        "spans": TRACER.summary(),
        "cost": cost.snapshot(),
        "op_profile": opprof.snapshot(),
        "devprof": devprof.snapshot(),
        "memory": memprof.snapshot(),
        "numerics": numerics.snapshot(),
        **local,
    }
    if all_hosts:
        snap["hosts"] = _gather_host_tables(local)
    return snap


# ---------------------------------------------------------------------------
# Live telemetry session (ISSUE 10 tentpole wiring).  The stdlib-only
# machinery lives in obs/telemetry.py; this is the in-process glue:
# flag/env resolution, the profiler/cost source bundle, the watchdog's
# export callbacks, and a refcounted singleton so a training loop and a
# serving engine in one process share a sampler + endpoint.
# ---------------------------------------------------------------------------

class _TelemetryHandle:
    """One live telemetry session: sampler thread + optional HTTP
    endpoint + watchdog.  `port` is the bound port (None without
    HTTP); close() is refcount-aware via stop_telemetry()."""

    def __init__(self, collector, server, watchdog):
        self.collector = collector
        self.server = server
        self.watchdog = watchdog
        self.port = server.port if server is not None else None

    def close(self) -> None:
        stop_telemetry()


_TELEMETRY: Optional[_TelemetryHandle] = None
_TELEMETRY_REFS = 0
_TELEMETRY_LOCK = _threading.Lock()


def _obs_flag(name: str, env_var: str, default, typ):
    """Resolve a PADDLE_OBS_* knob: fluid flag first (which itself was
    env-seeded at import), then a late env read for processes that set
    the variable after paddle_tpu import, then the default."""
    try:
        from ..fluid import flags as _flags

        entry = _flags._REGISTRY.get(name)
        if entry is not None and entry["value"] != entry["default"]:
            return typ(entry["value"])
    except Exception:  # noqa: BLE001 - flags registry unavailable
        pass
    env = _os.environ.get(env_var)
    if env is not None:
        try:
            return typ(env)
        except ValueError:
            pass
    return default


def start_telemetry(port: Optional[int] = None,
                    sample_s: Optional[float] = None,
                    flight_dir: Optional[str] = None,
                    flight_keep: Optional[int] = None,
                    flight_min_interval_s: Optional[float] = None,
                    thresholds: Optional[dict] = None) -> _TelemetryHandle:
    """Start (or join) the process-wide telemetry session: background
    sampler over the profiler/cost tables, anomaly watchdog + flight
    recorder, and — when `port` >= 0 (0 = ephemeral) — the /metrics +
    /healthz + /snapshot + /debug/trace HTTP endpoint.  Refcounted:
    every start_telemetry() must be paired with a stop_telemetry() (or
    handle.close()); the session tears down on the last one."""
    global _TELEMETRY, _TELEMETRY_REFS
    with _TELEMETRY_LOCK:
        if _TELEMETRY is not None:
            _TELEMETRY_REFS += 1
            return _TELEMETRY
        if port is None:
            port = _obs_flag("obs_http_port", "PADDLE_OBS_HTTP_PORT",
                             -1, int)
        if sample_s is None:
            sample_s = _obs_flag("obs_sample_s", "PADDLE_OBS_SAMPLE_S",
                                 telemetry.DEFAULT_SAMPLE_S, float)
        if flight_dir is None:
            flight_dir = _obs_flag("obs_flight_dir",
                                   "PADDLE_OBS_FLIGHT_DIR",
                                   "artifacts/flight", str)
        if flight_keep is None:
            flight_keep = _obs_flag("obs_flight_keep",
                                    "PADDLE_OBS_FLIGHT_KEEP", 5, int)
        if flight_min_interval_s is None:
            flight_min_interval_s = _obs_flag(
                "obs_flight_min_interval_s",
                "PADDLE_OBS_FLIGHT_MIN_INTERVAL_S", 60.0, float)
        def _bundle_meta() -> dict:
            # run-config stamp for the bundle manifest: a diff between
            # two bundles can tell a deliberate quant_collectives flip
            # (expected ~4x collective_bytes shift) from real drift
            from ..parallel import quant_collectives as _qc

            meta = {"quant_collectives": _qc.mode()}
            try:
                # which tenants shared the device at dump time
                # (multi-tenant fleet, serving/registry.py) — an
                # incident bundle without the co-tenant list cannot
                # distinguish noisy-neighbour from self-inflicted
                from ..serving.registry import active_tenants

                tenants = active_tenants()
                if tenants:
                    meta["tenants"] = tenants
            except Exception:  # noqa: BLE001 - meta only
                pass
            return meta

        watchdog = telemetry.Watchdog(
            thresholds=thresholds,
            artifacts_dir=flight_dir or None,
            keep=flight_keep,
            min_interval_s=flight_min_interval_s,
            trace_cb=export_trace,
            snapshot_cb=snapshot,
            op_profile_cb=opprof.snapshot,
            mem_cb=memprof.memory_doc,
            numerics_cb=numerics.numerics_doc,
            meta_cb=_bundle_meta)
        collector = telemetry.Collector(
            sources=telemetry.default_sources(),
            sample_s=sample_s, watchdog=watchdog)

        def _overhead(ms: float) -> None:
            from .. import profiler

            profiler.time_add("telemetry_sample_ms", ms)

        collector.overhead_cb = _overhead
        collector.snapshot_cb = snapshot
        collector.trace_json_cb = TRACER.chrome_trace
        server = None
        if port is not None and port >= 0:
            server = telemetry.TelemetryServer(collector,
                                               port=port).start()
        collector.start()
        _TELEMETRY = _TelemetryHandle(collector, server, watchdog)
        _TELEMETRY_REFS = 1
        return _TELEMETRY


def stop_telemetry() -> None:
    """Release one reference on the telemetry session; the sampler and
    endpoint shut down when the last holder releases."""
    global _TELEMETRY, _TELEMETRY_REFS
    with _TELEMETRY_LOCK:
        if _TELEMETRY is None:
            return
        _TELEMETRY_REFS -= 1
        if _TELEMETRY_REFS > 0:
            return
        handle, _TELEMETRY, _TELEMETRY_REFS = _TELEMETRY, None, 0
    handle.collector.stop()
    if handle.server is not None:
        handle.server.stop()


def maybe_start_telemetry() -> Optional[_TelemetryHandle]:
    """The PADDLE_OBS_HTTP_PORT auto-attach seam used by
    Executor.train_from_dataset and serving.Engine: starts (or joins)
    the telemetry session when the port knob is set (>= 0), returns
    None — no thread, no endpoint, no overhead — when it is not."""
    port = _obs_flag("obs_http_port", "PADDLE_OBS_HTTP_PORT", -1, int)
    if port is None or port < 0:
        return None
    return start_telemetry(port=port)


def telemetry_handle() -> Optional[_TelemetryHandle]:
    return _TELEMETRY


def telemetry_epoch_refresh() -> None:
    """Refresh the telemetry endpoint's pod-merged `/snapshot` view.
    Rides the existing epoch-boundary collective (the shard_skew_ms
    gather in dataset.feed_pipeline._finish_epoch) so the all-gather
    happens where every host already participates; a no-op without a
    live session."""
    handle = _TELEMETRY
    if handle is None:
        return
    try:
        handle.collector.refresh_merged(
            lambda: snapshot(all_hosts=True))
    except Exception:  # noqa: BLE001 - observability, not control flow
        pass


def export_trace(path: str, include_snapshot: bool = True) -> int:
    """Write the recorded spans as Chrome-trace/Perfetto JSON.  The
    snapshot rides in otherData so tracetool can summarize MFU and
    stall attribution from the one file.  The device's timeline is the
    profiler's own trace, where the Executor's stages appear as `pt.*`
    annotations (`profiler.stage`).  Returns the span ("X") event
    count."""
    other = None
    if include_snapshot:
        snap = snapshot()
        snap.pop("spans", None)  # the events ARE the span detail
        other = {"snapshot": snap}
    doc = TRACER.chrome_trace(other_data=other)
    try:
        # ledger samples as a Chrome "C" counter track, aligned with
        # the span timeline (both perf_counter-clocked)
        doc["traceEvents"].extend(memprof.chrome_counter_events())
    except Exception:  # noqa: BLE001 - the host trace must still export
        pass
    with open(path, "w") as f:
        _json.dump(doc, f)
    return sum(1 for e in doc["traceEvents"] if e.get("ph") == "X")
