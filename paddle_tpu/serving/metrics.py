"""Serving observability: profiler-exported stats + latency percentiles.

Every counter/gauge below lives in paddle_tpu.profiler's StatRegistry
(`profiler.get_int_stats()`) or the pipeline-timer table
(`profiler.get_time_stats()`), so the serving engine is observable
through the exact surface the training hot path already uses
(docs/async_hot_path.md "Observability").

Int stats (get_int_stats):

| stat                          | meaning                                 |
|-------------------------------|-----------------------------------------|
| serving_requests_total        | requests admitted                       |
| serving_rejected_total        | requests refused with EngineOverloaded  |
| serving_cancelled_total       | requests cancelled before completion    |
| serving_completed_total       | requests answered                       |
| serving_batches_total         | batches dispatched                      |
| serving_batch_rows_total      | summed request rows over all batches    |
| serving_batch_requests_total  | summed request count over all batches   |
| serving_batch_occupancy_max   | largest per-batch request count seen    |
| serving_queue_depth           | gauge: requests currently queued        |
| serving_in_flight             | gauge: batches dispatched, not complete |
| serving_trace_count           | bucketed-cache compiles (engine + Predictor) |
| serving_pad_rows_total        | padding rows added by bucketing         |
| serving_kv_pages_in_use       | gauge: PageTable pages allocated — under |
|                               | lazy growth this tracks REAL demand, so |
|                               | it is the admission-pressure signal the |
|                               | kv_pressure watchdog rule divides by    |
|                               | serving_kv_pages_capacity               |
| serving_kv_pages_capacity     | gauge: allocatable pages (num_pages - 1;|
|                               | page 0 is the reserved scratch page)    |
| serving_kv_bytes              | gauge: device bytes backing in-use KV pages |
| serving_kv_pages_extended     | decode-time PageTable.extend successes  |
| serving_kv_backpressure_total | extend refusals (pool exhausted) that   |
|                               | paused a slot instead of killing batch  |
| serving_kv_paused_total       | slots paused awaiting free KV pages     |
| serving_kv_preempt_total      | paused-livelock preemptions (one slot   |
|                               | early-retired to free pages)            |
| serving_prefill_count         | prefill dispatches (autoregressive)     |
| serving_prefill_chunks        | chunked-prefill chunk dispatches        |
| serving_ragged_fallback_total | ragged paged-attention Mosaic rejections|
|                               | that fell back to the dense XLA path    |
| flash_fallback_total          | flash-attention shapes Mosaic refused   |
|                               | (ladder exhausted or probe refused) that|
|                               | took XLA attention (prefill, training); |
|                               | like the ragged one, bumped once per    |
|                               | refused shape at trace time, never per  |
|                               | step; chip_smoke.py and the TPU lane    |
|                               | fail on a non-zero count of either      |
| flash_packed_layout_total     | flash-attention instances traced on the |
|                               | projections' (B, S, H*D) layout (head   |
|                               | pairs at D = 64): no head transposes    |
| flash_fwd_pieces_total        | pieces the forward flash kernel of each |
|                               | traced instance walks a grid step: the  |
|                               | heads of the step, one at a time (1: a  |
|                               | whole-tile step; 4 BERT s512 and JoyAI, |
|                               | 6 BERT s128, 8 SDAR); at trace time     |
| serving_decode_steps          | decode-step dispatches (autoregressive) |

Per-tenant series (multi-tenant fleet, serving/registry.py): every
registered model `<t>` gets its own family, written via
`tenant_stat(t, suffix)` so the names stay collector-foldable
(`serving_tenant_<t>_<suffix>`); the watchdog's
`tenant_rejection_spike` rule scans exactly this namespace:

| stat                                | meaning                              |
|-------------------------------------|--------------------------------------|
| serving_tenant_<t>_requests_total   | requests admitted for tenant t       |
| serving_tenant_<t>_rejected_total   | tenant-quota rejections for t        |
| serving_tenant_<t>_completed_total  | requests answered for tenant t       |
| serving_tenant_<t>_queued           | gauge: t's requests currently queued |
| serving_tenant_<t>_cache_evictions  | t's per-model compile-cache evictions|

Per-tenant timers: `serving_tenant_<t>_request_ms` (summed
submit->response latency; the same name also feeds a host-side
latency reservoir for per-tenant p50/p99 via `latency_stats`).

Time stats (get_time_stats, milliseconds):

| timer                | meaning                                        |
|----------------------|------------------------------------------------|
| serving_queue_ms     | summed request wait, submit -> dispatch        |
| serving_dispatch_ms  | host time to enqueue a batch on device         |
| serving_compile_ms   | off-path bucket compiles (request parked)      |
| serving_response_ms  | sanctioned device->host materialization at the |
|                      | response boundary                              |

Latency percentiles are host-side only (they need the full per-request
distribution, which a counter table cannot carry): a bounded reservoir
per metric name, drained by `latency_stats()` as p50/p99.
Reservoir names in use: `serving_request_ms` (submit -> response),
`serving_prefill_chunk_ms` (host wall time per chunked-prefill chunk),
and `serving_ttft_ms` (admission -> first token, recorded when the
last prefill chunk lands).
"""

from __future__ import annotations

import re
import threading
from collections import deque
from typing import Dict, Optional

from ..profiler import stat_add, stat_set

_TENANT_SAFE = re.compile(r"[^0-9A-Za-z_]")


def tenant_stat(tenant: str, suffix: str) -> str:
    """Stat name for one tenant's series: `serving_tenant_<t>_<suffix>`
    with the tenant name sanitized to the profiler's identifier
    alphabet (the telemetry collector folds every profiler stat into a
    series, so these names ARE the /metrics per-tenant surface)."""
    return f"serving_tenant_{_TENANT_SAFE.sub('_', str(tenant))}_{suffix}"


_CAP = 8192
_LAT: Dict[str, deque] = {}
_LAT_LOCK = threading.Lock()


def record_latency(name: str, ms: float) -> None:
    """Append one request latency (milliseconds) to the bounded
    per-name reservoir."""
    with _LAT_LOCK:
        q = _LAT.get(name)
        if q is None:
            q = _LAT[name] = deque(maxlen=_CAP)
        q.append(float(ms))


def latency_stats(name: str = "serving_request_ms") -> Optional[dict]:
    """{count, mean_ms, p50_ms, p99_ms, max_ms} for `name`, or None if
    nothing was recorded."""
    # copy under the lock, sort OUTSIDE it: an 8192-entry sort inside
    # _LAT_LOCK would block the completer thread's record_latency on
    # every stats scrape (the telemetry sampler polls this per sample)
    with _LAT_LOCK:
        q = _LAT.get(name)
        vals = list(q) if q else None
    if not vals:
        return None
    vals.sort()

    def pct(p):
        i = min(len(vals) - 1, int(round(p / 100.0 * (len(vals) - 1))))
        return vals[i]

    return {
        "count": len(vals),
        "mean_ms": sum(vals) / len(vals),
        "p50_ms": pct(50.0),
        "p99_ms": pct(99.0),
        "max_ms": vals[-1],
    }


def reset_latency(name: str = None) -> None:
    with _LAT_LOCK:
        if name is None:
            _LAT.clear()
        else:
            _LAT.pop(name, None)


_OCC_LOCK = threading.Lock()
_OCC_MAX = [0]


def observe_batch(n_requests: int, rows: int, pad_rows: int) -> None:
    """Record one dispatched batch: occupancy counters + padding waste."""
    stat_add("serving_batches_total")
    stat_add("serving_batch_rows_total", rows)
    stat_add("serving_batch_requests_total", n_requests)
    if pad_rows:
        stat_add("serving_pad_rows_total", pad_rows)
    with _OCC_LOCK:
        if n_requests > _OCC_MAX[0]:
            _OCC_MAX[0] = n_requests
            stat_set("serving_batch_occupancy_max", n_requests)


def reset_occupancy() -> None:
    with _OCC_LOCK:
        _OCC_MAX[0] = 0
    stat_set("serving_batch_occupancy_max", 0)


def mean_occupancy(stats: dict) -> float:
    """Requests per batch, from a get_int_stats() snapshot."""
    batches = stats.get("serving_batches_total", 0)
    if not batches:
        return 0.0
    return stats.get("serving_batch_requests_total", 0) / batches
