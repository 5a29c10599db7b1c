"""Live production telemetry (ISSUE 10): metrics time-series, /metrics
+ /healthz endpoints, and an anomaly watchdog with flight-recorder
dumps.

PRs 6-7 made the stack *measurable* — spans, cost gauges, per-op
profiles — but every surface is pull-based and post-hoc: someone has to
already be asking.  This module is the always-on layer the TF system
paper (arxiv 1605.08695) treats as a first-class requirement: a
production replica is watched from the OUTSIDE while it runs, and a
2am anomaly leaves a post-mortem record nobody had to be exporting.

Three pieces:

* **Collector** — a background sampler thread folds the profiler
  counter/timer tables and the `obs.cost` gauges into bounded
  per-metric ring-buffer time series every `PADDLE_OBS_SAMPLE_S`
  seconds.  Cumulative counters are stored as per-sample DELTAS,
  gauges as levels; memory is fixed (`capacity` points per series,
  `max_series` series) and overflow is counted, never silent.  The
  sampler's own overhead is a timer (`telemetry_sample_ms`).

* **Export** — `prometheus_text()` renders the canonical scrape format
  (counters as cumulative `paddle_tpu_*` totals, gauges as levels) and
  `Collector.to_json()` the full series dump; `TelemetryServer` is a
  stdlib `http.server` serving `/metrics` (`?format=json` for the JSON
  body), `/healthz` (503 + reason once the watchdog fires),
  `/snapshot` (`?all_hosts=1` for the pod-merged view refreshed at
  epoch boundaries via the existing gather idiom) and `/debug/trace`
  (Chrome-trace of the current span buffer).

* **Watchdog + flight recorder** — a rule registry evaluated per
  sample: step-time spike vs rolling median, MFU drop, non-finite loss
  (the async check_nan_inf seam's `nan_inf_hits_total` counter),
  serving rejection-rate / queue-saturation spikes, `ckpt_stall_ms`
  blowup, feed-ring starvation, `collective_bytes_*` jumps (the
  EQuARX guard direction).  A firing rule flips `/healthz` unhealthy
  with a reason and atomically publishes a flight-record bundle
  (trace + snapshot + op-profile table + the full series window) to
  an artifacts dir — rate-limited, and GC'd with the checkpoint
  retention idiom (keep newest N, sweep half-written tmp dirs).

Nothing at module level imports jax or paddle_tpu.  In-process
wiring (profiler/cost sources, the HTTP attach on `train_from_dataset`
/ `serving.Engine`) lives in `paddle_tpu.obs.start_telemetry`;
`series_stats` / `replay_rules` below read and replay a saved JSON
dump.
"""

from __future__ import annotations

import collections
import json
import os
import re
import shutil
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

SAMPLE_S_ENV = "PADDLE_OBS_SAMPLE_S"
DEFAULT_SAMPLE_S = 1.0
DEFAULT_CAPACITY = 600          # points per series (10 min at 1 Hz)
DEFAULT_MAX_SERIES = 512
TMP_PREFIX = "_tmp_"            # half-written bundle marker (ckpt idiom)
BUNDLE_PREFIX = "flight_"

# int stats that are levels, not cumulative counters: store as-is
GAUGE_STATS = frozenset({
    "serving_queue_depth", "serving_in_flight",
    "serving_batch_occupancy_max", "serving_kv_pages_in_use",
    "serving_kv_bytes",
    "ring_occupancy", "ring_occupancy_max",
    "in_flight_steps", "in_flight_steps_max",
    "loss_scale", "nan_inf_first_step",
})
# timer-table entries written with time_set (per-epoch gauges), not
# time_add accumulators
GAUGE_TIMERS = frozenset({"shard_skew_ms"})


def _is_gauge_stat(name: str) -> bool:
    """Levels vs cumulative counters.  Beyond the fixed set, the
    multi-tenant fleet mints one `serving_tenant_<t>_queued` depth
    gauge PER REGISTERED MODEL (serving/batcher.py stat_set) — matched
    by shape since tenant names are dynamic."""
    return name in GAUGE_STATS or (
        name.startswith("serving_tenant_") and name.endswith("_queued"))

COUNTER = "counter"
GAUGE = "gauge"


def _sanitize(value: float) -> float:
    v = float(value)
    # NaN/Inf would corrupt the JSON dump and the Prometheus line
    return v if v == v and abs(v) != float("inf") else 0.0


class Series:
    """One bounded metric time series: (t, value) ring buffer.

    Counters hold per-sample deltas (plus the last cumulative raw value
    in `cum`, which is what Prometheus wants); gauges hold levels.
    Overflow evicts the oldest point and counts it in `dropped`."""

    __slots__ = ("name", "kind", "points", "dropped", "cum")

    def __init__(self, name: str, kind: str,
                 capacity: int = DEFAULT_CAPACITY):
        self.name = name
        self.kind = kind
        self.points: collections.deque = collections.deque(
            maxlen=max(2, int(capacity)))
        self.dropped = 0
        self.cum = 0.0

    def add(self, t: float, value: float) -> None:
        if len(self.points) == self.points.maxlen:
            self.dropped += 1
        self.points.append((round(float(t), 3), _sanitize(value)))

    def values(self) -> List[float]:
        return [p[1] for p in self.points]

    def last(self) -> Optional[float]:
        return self.points[-1][1] if self.points else None

    def as_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "dropped": self.dropped,
                "cum": self.cum,
                "points": [[t, v] for t, v in self.points]}


class MetricStore:
    """name -> Series, bounded in BOTH dimensions (points per series
    and series count); every eviction/refusal is counted."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 max_series: int = DEFAULT_MAX_SERIES):
        self.capacity = int(capacity)
        self.max_series = int(max_series)
        self.series_dropped = 0
        self._series: Dict[str, Series] = {}
        self._lock = threading.Lock()

    def record(self, t: float, name: str, kind: str, value: float,
               cum: Optional[float] = None) -> None:
        with self._lock:
            s = self._series.get(name)
            if s is None:
                if len(self._series) >= self.max_series:
                    self.series_dropped += 1
                    return
                s = self._series[name] = Series(name, kind,
                                                self.capacity)
            s.add(t, value)
            if cum is not None:
                s.cum = _sanitize(cum)

    # -- the rule/view surface (shared with _ReplayView) -------------------
    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._series)

    def vals(self, name: str) -> List[float]:
        with self._lock:
            s = self._series.get(name)
            return s.values() if s is not None else []

    def last(self, name: str) -> Optional[float]:
        with self._lock:
            s = self._series.get(name)
            return s.last() if s is not None else None

    def get(self, name: str) -> Optional[Series]:
        with self._lock:
            return self._series.get(name)

    def points_dropped(self) -> int:
        with self._lock:
            return sum(s.dropped for s in self._series.values())

    def as_dict(self) -> Dict[str, Any]:
        with self._lock:
            return {name: s.as_dict()
                    for name, s in sorted(self._series.items())}


# ---------------------------------------------------------------------------
# Watchdog rules.  Each rule is `fn(view, cfg) -> Optional[reason]` over
# the series view (vals/last/names) — pure, so `replay_rules` can replay
# them over a saved dump with no live collector.
# ---------------------------------------------------------------------------

DEFAULT_THRESHOLDS: Dict[str, float] = {
    "min_points": 5,            # samples before spike rules arm
    "step_spike_x": 3.0,        # step_ms > Nx rolling median
    "mfu_drop_frac": 0.5,       # mfu_pct < frac * rolling median
    "mfu_floor_pct": 0.5,       # ignore MFU noise below this level
    "reject_min": 5,            # rejected requests per sample to arm
    "reject_rate": 0.5,         # rejected / (rejected + admitted)
    "tenant_reject_min": 5,     # per-tenant rejections to arm
    "tenant_reject_rate": 0.5,  # per-tenant rejected / offered
    "queue_spike_x": 3.0,       # queue depth > Nx rolling median
    "queue_min": 8,             # and at least this deep
    "ckpt_stall_ms": 500.0,     # ckpt backpressure per sample window
    "starvation_frac": 0.5,     # ring empty-wait fraction of window
    "window_ms": 1000.0,        # sample window (set from sample_s)
    "collective_jump_frac": 0.5,  # bytes-on-wire growth within window
    "collective_min_bytes": 1024.0,
    "host_lost_stale_s": 300.0,   # pod-merged snapshot staleness limit
    "hbm_pressure_frac": 0.92,    # bytes_in_use / bytes_limit ceiling
    "hbm_headroom_temp_frac": 1.0,  # headroom vs biggest static temp
    "grad_spike_x": 10.0,         # grad_norm_total > Nx rolling median
    "grad_norm_min": 1e-3,        # ignore grad-norm noise below this
    "loss_scale_collapse_frac": 0.0625,  # last <= frac * window peak
    "loss_scale_min_peak": 4.0,   # scale peak before the rule arms
    "kv_pressure_frac": 0.90,     # serving KV pages in-use / capacity
}


def _median(xs: List[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if not n:
        return 0.0
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def _spike_vs_median(xs: List[float], factor: float,
                     min_points: int) -> Optional[Tuple[float, float]]:
    """(last, median) when the last point exceeds factor * rolling
    median of the preceding nonzero points, else None."""
    if len(xs) < min_points:
        return None
    prev = [x for x in xs[:-1] if x > 0.0]
    if len(prev) < min_points - 1:
        return None
    med = _median(prev)
    last = xs[-1]
    if med > 1e-3 and last > factor * med:
        return last, med
    return None


def rule_step_time_spike(v, cfg) -> Optional[str]:
    hit = _spike_vs_median(v.vals("step_ms"), cfg["step_spike_x"],
                           int(cfg["min_points"]))
    if hit is None:
        return None
    last, med = hit
    return (f"step_ms {last:.2f} is {last / med:.1f}x the rolling "
            f"median {med:.2f}")


def rule_mfu_drop(v, cfg) -> Optional[str]:
    xs = v.vals("mfu_pct")
    if len(xs) < cfg["min_points"]:
        return None
    prev = [x for x in xs[:-1] if x > 0.0]
    if len(prev) < cfg["min_points"] - 1:
        return None
    med = _median(prev)
    last = xs[-1]
    if med >= cfg["mfu_floor_pct"] and last < cfg["mfu_drop_frac"] * med:
        return (f"mfu_pct fell to {last:.3f} from a rolling median of "
                f"{med:.3f}")
    return None


def rule_non_finite_loss(v, cfg) -> Optional[str]:
    d = v.last("nan_inf_hits_total")
    if d and d > 0:
        return (f"{int(d)} non-finite value(s) caught by the async "
                f"check_nan_inf scan this sample")
    return None


def rule_serving_rejection_spike(v, cfg) -> Optional[str]:
    rej = v.last("serving_rejected_total") or 0.0
    adm = v.last("serving_requests_total") or 0.0
    if rej < cfg["reject_min"]:
        return None
    rate = rej / max(1.0, rej + adm)
    if rate > cfg["reject_rate"]:
        return (f"rejection rate {rate:.0%} ({int(rej)} rejected vs "
                f"{int(adm)} admitted this sample)")
    return None


def rule_tenant_rejection_spike(v, cfg) -> Optional[str]:
    """Per-tenant admission health (multi-tenant fleet,
    serving/registry.py): one tenant hammering its quota fires with
    the TENANT'S name even while the fleet-wide rejection rate stays
    green — the global rule averages the noisy neighbour away; this
    one scans every `serving_tenant_<t>_rejected_total` series the
    collector folded from the profiler tables."""
    worst = None
    for name in v.names():
        if not name.startswith("serving_tenant_") \
                or not name.endswith("_rejected_total"):
            continue
        rej = v.last(name) or 0.0
        if rej < cfg["tenant_reject_min"]:
            continue
        tenant = name[len("serving_tenant_"):-len("_rejected_total")]
        adm = v.last(f"serving_tenant_{tenant}_requests_total") or 0.0
        rate = rej / max(1.0, rej + adm)
        if rate > cfg["tenant_reject_rate"] \
                and (worst is None or rate > worst[1]):
            worst = (tenant, rate, rej, adm)
    if worst is None:
        return None
    tenant, rate, rej, adm = worst
    return (f"tenant {tenant!r} rejection rate {rate:.0%} "
            f"({int(rej)} rejected vs {int(adm)} admitted this "
            f"sample; per-tenant quota, serving/registry.py)")


def rule_serving_queue_saturation(v, cfg) -> Optional[str]:
    xs = v.vals("serving_queue_depth")
    hit = _spike_vs_median(xs, cfg["queue_spike_x"],
                           int(cfg["min_points"]))
    if hit is None or xs[-1] < cfg["queue_min"]:
        return None
    last, med = hit
    return (f"serving queue depth {int(last)} is {last / med:.1f}x the "
            f"rolling median {med:.1f}")


def rule_ckpt_stall(v, cfg) -> Optional[str]:
    d = v.last("ckpt_stall_ms")
    if d and d > cfg["ckpt_stall_ms"]:
        return (f"checkpoint backpressure {d:.0f} ms this sample "
                f"(threshold {cfg['ckpt_stall_ms']:.0f} ms)")
    return None


def rule_feed_starvation(v, cfg) -> Optional[str]:
    d = v.last("ring_empty_wait_ms")
    lim = cfg["starvation_frac"] * cfg["window_ms"]
    if d and d > lim:
        return (f"consumer starved {d:.0f} ms of a "
                f"{cfg['window_ms']:.0f} ms sample window waiting on "
                f"the feed ring")
    return None


def rule_collective_bytes_jump(v, cfg) -> Optional[str]:
    # quantization-aware (docs/spmd.md): a deliberate
    # FLAGS_quant_collectives flip moves every collective_bytes_*
    # counter by design (~4x) — when the quant_collectives_mode gauge
    # changed inside this window, the flip IS the baseline reset, not
    # an anomaly
    mode_xs = v.vals("quant_collectives_mode")
    if len(set(mode_xs)) > 1:
        return None
    for name in v.names():
        if not name.startswith("collective_bytes_"):
            continue
        xs = v.vals(name)
        if len(xs) < 2:
            continue
        before = sum(xs[:-1])
        last = xs[-1]
        if before > 0 and last > cfg["collective_min_bytes"] \
                and last > cfg["collective_jump_frac"] * before:
            return (f"{name} grew by {last:.0f} bytes in one sample "
                    f"({before:.0f} over the rest of the window)")
    return None


def rule_host_lost(v, cfg) -> Optional[str]:
    """A host dropped out of the pod-merged snapshot, or the merged
    view itself went stale.  `hosts_reporting` is recorded at every
    refresh_merged; on a single-host run the peak never exceeds 1 and
    the rule stays silent."""
    xs = v.vals("hosts_reporting")
    peak = max(xs) if xs else 0.0
    if peak > 1 and xs[-1] < peak:
        return (f"{int(peak - xs[-1])} host(s) missing from the "
                f"pod-merged snapshot ({int(xs[-1])}/{int(peak)} "
                f"reporting)")
    age = v.last("merged_age_s")
    if peak > 1 and age is not None and age > cfg["host_lost_stale_s"]:
        return (f"pod-merged snapshot is {age:.0f} s stale (limit "
                f"{cfg['host_lost_stale_s']:.0f} s) — the gather "
                f"stopped reaching this host")
    return None


def rule_hbm_pressure(v, cfg) -> Optional[str]:
    """Device HBM nearly full, or headroom below the biggest compiled
    program's static temp requirement (the next dispatch of that
    program cannot fit).  The `hbm_*` gauges only exist where
    `device.memory_stats()` reports them (TPU); on single-host CPU the
    series are absent and this rule is silent by construction."""
    in_use = v.last("hbm_bytes_in_use")
    limit = v.last("hbm_limit_bytes")
    if in_use is None or limit is None or limit <= 0:
        return None
    # in_use and limit are sums over the local devices; the fullest
    # device decides, where memprof reports it (several devices)
    frac = v.last("hbm_fullest_device_frac")
    if frac is None:
        frac = in_use / limit
    if frac > cfg["hbm_pressure_frac"]:
        return (f"hbm_bytes_in_use {in_use:.0f} of {limit:.0f} bytes: "
                f"the fullest device is at {frac:.0%} of its limit "
                f"(threshold {cfg['hbm_pressure_frac']:.0%})")
    temp = v.last("hbm_static_temp_bytes")
    headroom = v.last("hbm_min_headroom_bytes")
    if headroom is None:
        headroom = limit - in_use
    if temp and temp > 0 \
            and headroom < cfg["hbm_headroom_temp_frac"] * temp:
        return (f"hbm headroom {headroom:.0f} bytes is below the "
                f"largest compiled program's static temp requirement "
                f"({temp:.0f} bytes)")
    return None


def rule_grad_norm_spike(v, cfg) -> Optional[str]:
    """Exploding-gradient onset: the obs.numerics `grad_norm_total`
    health gauge jumps far above its rolling median.  Silent until the
    numerics health series exists (PADDLE_OBS_NUMERICS armed) and the
    norm clears the noise floor."""
    hit = _spike_vs_median(v.vals("grad_norm_total"),
                           cfg["grad_spike_x"], int(cfg["min_points"]))
    if hit is None:
        return None
    last, med = hit
    if last < cfg["grad_norm_min"]:
        return None
    return (f"grad_norm_total {last:.3g} is {last / med:.1f}x the "
            f"rolling median {med:.3g} (threshold "
            f"{cfg['grad_spike_x']:.1f}x)")


def rule_loss_scale_collapse(v, cfg) -> Optional[str]:
    """AMP dynamic loss scale collapsed: repeated non-finite gradients
    keep halving the scale (`decr_every_n_nan_or_inf`), so the last
    sample sits at a small fraction of the window peak.  The
    `loss_scale` gauge rides obs.numerics' drain of the
    update_loss_scaling output; absent series -> silent."""
    xs = v.vals("loss_scale")
    if len(xs) < int(cfg["min_points"]):
        return None
    peak, last = max(xs), xs[-1]
    if peak >= cfg["loss_scale_min_peak"] \
            and last <= cfg["loss_scale_collapse_frac"] * peak:
        return (f"loss_scale collapsed to {last:g} from a window peak "
                f"of {peak:g} (repeated non-finite grads are shrinking "
                f"the scale; threshold "
                f"{cfg['loss_scale_collapse_frac']:g}x peak)")
    return None


def rule_kv_pressure(v, cfg) -> Optional[str]:
    """Serving KV page pool nearly exhausted.  Under lazy page growth
    (serving/engine.py) admission reserves only what the prompt needs,
    so `serving_kv_pages_in_use` tracks real demand — when it nears
    `serving_kv_pages_capacity`, the next decode-time `extend` starts
    pausing slots (typed kv_pages backpressure) and admission starts
    parking requests.  Both gauges come from PageTable._publish; on a
    host with no serving engine the series are absent and this rule is
    silent by construction."""
    used = v.last("serving_kv_pages_in_use")
    cap = v.last("serving_kv_pages_capacity")
    if used is None or cap is None or cap <= 0:
        return None
    frac = used / cap
    if frac > cfg["kv_pressure_frac"]:
        return (f"serving_kv_pages_in_use {used:.0f} is {frac:.0%} of "
                f"the {cap:.0f}-page pool (threshold "
                f"{cfg['kv_pressure_frac']:.0%}) — decode slots are "
                f"about to hit extend backpressure; shed load or raise "
                f"num_pages")
    return None


RULES: List[Tuple[str, Callable]] = [
    ("step_time_spike", rule_step_time_spike),
    ("mfu_drop", rule_mfu_drop),
    ("non_finite_loss", rule_non_finite_loss),
    ("serving_rejection_spike", rule_serving_rejection_spike),
    ("tenant_rejection_spike", rule_tenant_rejection_spike),
    ("serving_queue_saturation", rule_serving_queue_saturation),
    ("ckpt_stall", rule_ckpt_stall),
    ("feed_starvation", rule_feed_starvation),
    ("collective_bytes_jump", rule_collective_bytes_jump),
    ("host_lost", rule_host_lost),
    ("hbm_pressure", rule_hbm_pressure),
    ("kv_pressure", rule_kv_pressure),
    ("grad_norm_spike", rule_grad_norm_spike),
    ("loss_scale_collapse", rule_loss_scale_collapse),
]


class Watchdog:
    """Per-sample rule evaluation + the flight recorder.

    A firing rule latches health unhealthy (with the rule's reason) and
    writes one flight-record bundle — trace + snapshot + op-profile
    table + the series window — atomically (tmp dir + os.replace, the
    checkpoint publish protocol), rate-limited to one bundle per
    `min_interval_s`, retention-GC'd to the newest `keep` bundles.
    The export callbacks are injected so the module stays stdlib-only;
    a missing callback just leaves that file out of the bundle."""

    def __init__(self, rules=None, thresholds: Optional[dict] = None,
                 artifacts_dir: Optional[str] = None, keep: int = 5,
                 min_interval_s: float = 60.0,
                 trace_cb: Optional[Callable[[str], Any]] = None,
                 snapshot_cb: Optional[Callable[[], dict]] = None,
                 op_profile_cb: Optional[Callable[[], dict]] = None,
                 mem_cb: Optional[Callable[[], dict]] = None,
                 numerics_cb: Optional[Callable[[], dict]] = None,
                 meta_cb: Optional[Callable[[], dict]] = None,
                 clock: Callable[[], float] = time.time):
        self.rules = list(RULES if rules is None else rules)
        self.cfg = dict(DEFAULT_THRESHOLDS)
        self.cfg.update(thresholds or {})
        self.artifacts_dir = artifacts_dir
        self.keep = int(keep)
        self.min_interval_s = float(min_interval_s)
        self.trace_cb = trace_cb
        self.snapshot_cb = snapshot_cb
        self.op_profile_cb = op_profile_cb
        self.mem_cb = mem_cb
        self.numerics_cb = numerics_cb
        # run-configuration metadata stamped into every bundle's
        # reason.json (e.g. the quant_collectives flag): tools diffing
        # two bundles can tell a deliberate mode flip from drift
        self.meta_cb = meta_cb
        self.clock = clock
        # back-reference for external trigger() firings (RESOURCE_
        # EXHAUSTED forensics); filled in by Collector.__init__
        self.collector: Optional["Collector"] = None
        self.healthy = True
        self.reason: Optional[str] = None
        self.fired: List[dict] = []
        self.bundles_written = 0
        self.dumps_rate_limited = 0
        self._last_dump_t: Optional[float] = None
        self._lock = threading.Lock()

    # -- evaluation (watched by hot-path-sync: host tables only) -----------
    def evaluate(self, view) -> List[Tuple[str, str]]:
        """Run every rule over the series view; (name, reason) per
        firing rule.  Pure — no state change, no I/O."""
        out = []
        for name, fn in self.rules:
            try:
                reason = fn(view, self.cfg)
            except Exception:  # noqa: BLE001 - a broken rule must not
                # take down the sampler; surface it as its own firing
                reason = None
            if reason:
                out.append((name, reason))
        return out

    def observe(self, collector: "Collector", now: float) -> List[dict]:
        """One sample tick: evaluate, latch health, maybe dump."""
        fired = self.evaluate(collector.store)
        if not fired:
            return []
        with self._lock:
            self.healthy = False
            self.reason = "; ".join(f"{n}: {r}" for n, r in fired)
            events = [{"rule": n, "reason": r, "t": round(now, 3)}
                      for n, r in fired]
            self.fired.extend(events)
            del self.fired[:-50]
        self._maybe_dump(collector, fired, now)
        return events

    def trigger(self, rule: str, reason: str) -> Optional[str]:
        """External firing seam — the executor's RESOURCE_EXHAUSTED
        catch publishes `mem_oom` here: latch health unhealthy and
        write a flight bundle exactly as if a sampled rule had fired,
        without waiting for the next tick."""
        now = self.clock()
        with self._lock:
            self.healthy = False
            self.reason = f"{rule}: {reason}"
            self.fired.append({"rule": rule, "reason": reason,
                               "t": round(now, 3)})
            del self.fired[:-50]
        return self._maybe_dump(self.collector, [(rule, reason)], now)

    def reset(self) -> None:
        """Operator acknowledgment: flip health back after the anomaly
        is understood (the firing history is kept)."""
        with self._lock:
            self.healthy = True
            self.reason = None

    def health(self) -> Dict[str, Any]:
        with self._lock:
            return {"healthy": self.healthy, "reason": self.reason,
                    "fired": list(self.fired[-20:]),
                    "bundles_written": self.bundles_written,
                    "dumps_rate_limited": self.dumps_rate_limited}

    # -- flight recorder ---------------------------------------------------
    def _maybe_dump(self, collector: Optional["Collector"],
                    fired: List[Tuple[str, str]],
                    now: float) -> Optional[str]:
        if not self.artifacts_dir:
            return None
        with self._lock:
            if self._last_dump_t is not None \
                    and now - self._last_dump_t < self.min_interval_s:
                self.dumps_rate_limited += 1
                return None
            self._last_dump_t = now
        try:
            return self._dump(collector, fired, now)
        except Exception:  # noqa: BLE001 - the recorder must never
            # take down the sampler thread it runs on
            return None

    def _dump(self, collector: Optional["Collector"],
              fired: List[Tuple[str, str]], now: float) -> str:
        name = f"{BUNDLE_PREFIX}{int(now * 1000)}_{fired[0][0]}"
        os.makedirs(self.artifacts_dir, exist_ok=True)
        tmp = os.path.join(self.artifacts_dir, TMP_PREFIX + name)
        os.makedirs(tmp, exist_ok=True)
        errors: Dict[str, str] = {}

        def _write_json(fname: str, cb: Optional[Callable[[], Any]]):
            if cb is None:
                return
            try:
                with open(os.path.join(tmp, fname), "w") as f:
                    json.dump(cb(), f)
            except Exception as e:  # noqa: BLE001 - partial bundle
                # beats no bundle; the gap is recorded in reason.json
                errors[fname] = f"{type(e).__name__}: {e}"

        _write_json("series.json",
                    collector.to_json if collector is not None else None)
        _write_json("snapshot.json", self.snapshot_cb)
        _write_json("op_profile.json", self.op_profile_cb)
        _write_json("memory.json", self.mem_cb)
        _write_json("numerics.json", self.numerics_cb)
        if self.trace_cb is not None:
            try:
                self.trace_cb(os.path.join(tmp, "trace.json"))
            except Exception as e:  # noqa: BLE001
                errors["trace.json"] = f"{type(e).__name__}: {e}"
        meta: Dict[str, Any] = {}
        if self.meta_cb is not None:
            try:
                meta = dict(self.meta_cb() or {})
            except Exception as e:  # noqa: BLE001 - partial bundle
                errors["meta"] = f"{type(e).__name__}: {e}"
        # reason.json LAST — it is the bundle's manifest
        with open(os.path.join(tmp, "reason.json"), "w") as f:
            json.dump({"t": round(now, 3),
                       "fired": [{"rule": n, "reason": r}
                                 for n, r in fired],
                       "health": self.health(),
                       "meta": meta,
                       "errors": errors}, f)
        final = os.path.join(self.artifacts_dir, name)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)  # atomic publish (ckpt idiom)
        with self._lock:
            self.bundles_written += 1
        self._gc()
        return final

    def _gc(self) -> None:
        """Retention (the ckpt._gc idiom): keep the newest `keep`
        published bundles; sweep half-written tmp dirs."""
        try:
            names = os.listdir(self.artifacts_dir)
        except OSError:
            return
        done = sorted(n for n in names if n.startswith(BUNDLE_PREFIX))
        drop = done[:-self.keep] if self.keep > 0 else done
        for n in drop:
            shutil.rmtree(os.path.join(self.artifacts_dir, n),
                          ignore_errors=True)
        for n in names:
            if n.startswith(TMP_PREFIX):
                shutil.rmtree(os.path.join(self.artifacts_dir, n),
                              ignore_errors=True)


def write_standalone_bundle(artifacts_dir: str, rule: str, reason: str,
                            files: Optional[Dict[str, Any]] = None,
                            now: Optional[float] = None
                            ) -> Optional[str]:
    """Minimal flight bundle with no live collector (the executor's
    OOM catch when telemetry is not running): the given JSON payloads
    plus reason.json, published with the same atomic tmp-dir +
    os.replace protocol as any other bundle.
    Returns the bundle path, or None on any failure — forensics never
    raise."""
    if not artifacts_dir:
        return None
    if now is None:
        now = time.time()
    name = f"{BUNDLE_PREFIX}{int(now * 1000)}_{rule}"
    try:
        os.makedirs(artifacts_dir, exist_ok=True)
        tmp = os.path.join(artifacts_dir, TMP_PREFIX + name)
        os.makedirs(tmp, exist_ok=True)
        for fname, payload in (files or {}).items():
            with open(os.path.join(tmp, fname), "w") as f:
                json.dump(payload, f)
        with open(os.path.join(tmp, "reason.json"), "w") as f:
            json.dump({"t": round(now, 3),
                       "fired": [{"rule": rule, "reason": reason}],
                       "errors": {}}, f)
        final = os.path.join(artifacts_dir, name)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)  # atomic publish (ckpt idiom)
        return final
    except Exception:  # noqa: BLE001 - see docstring
        return None


# ---------------------------------------------------------------------------
# Collector
# ---------------------------------------------------------------------------

def default_sample_s() -> float:
    try:
        return float(os.environ.get(SAMPLE_S_ENV, DEFAULT_SAMPLE_S))
    except ValueError:
        return DEFAULT_SAMPLE_S


def default_sources() -> Callable[[], Dict[str, Any]]:
    """The in-process source bundle: profiler counter/timer tables +
    obs.cost gauges + serving latency percentiles.  Requires the
    paddle_tpu package (tests inject scripted sources instead)."""
    from .. import profiler
    from . import cost

    def _sources() -> Dict[str, Any]:
        gauges: Dict[str, float] = {}
        try:
            csnap = cost.snapshot()
            gauges["mfu_pct"] = float(csnap.get("mfu_pct") or 0.0)
            gauges["hbm_bw_pct"] = float(csnap.get("hbm_bw_pct") or 0.0)
            # the hot program's step time: the program with the most
            # dispatches is the training/serving step being watched
            step_ms, best = 0.0, -1
            for p in csnap.get("programs", []):
                d = int(p.get("dispatches") or 0)
                if d > best and (p.get("step_ms") or 0) > 0:
                    best, step_ms = d, float(p["step_ms"])
            gauges["step_ms"] = step_ms
        except Exception:  # noqa: BLE001 - gauges are optional
            pass
        try:
            from ..serving.metrics import latency_stats

            ls = latency_stats()
            if ls:
                gauges["serving_p50_ms"] = float(ls["p50_ms"])
                gauges["serving_p99_ms"] = float(ls["p99_ms"])
        except Exception:  # noqa: BLE001 - no serving traffic
            pass
        try:
            # the memory ledger computes on demand right here — the
            # hbm_*/ledger_* gauges ride THIS sampler, no extra thread
            from . import memprof

            gauges.update(memprof.ledger_gauges())
        except Exception:  # noqa: BLE001 - memory gauges are optional
            pass
        try:
            # training-health gauges (grad_norm_total, update_ratio,
            # loss_scale, per-prefix norms): the numerics drain runs
            # on demand right here — same no-extra-thread contract as
            # the memory ledger above
            from . import numerics

            gauges.update(numerics.health_gauges())
        except Exception:  # noqa: BLE001 - numerics gauges are optional
            pass
        try:
            # quantized-collectives mode as a 0/1 level: the
            # collective_bytes jump rule reads this series to tell a
            # deliberate flag flip (baseline reset) from real traffic
            # growth (docs/spmd.md)
            from ..parallel import quant_collectives as _qc

            gauges["quant_collectives_mode"] = \
                1.0 if _qc.mode() == "int8" else 0.0
        except Exception:  # noqa: BLE001 - gauge is optional
            pass
        return {"counters": profiler.get_int_stats(),
                "timers_ms": profiler.get_time_stats(),
                "gauges": gauges}

    return _sources


class Collector:
    """Background sampler folding the source tables into the store.

    `sources()` returns `{"counters": {name: int}, "timers_ms":
    {name: ms}, "gauges": {name: float}}`.  Counters and accumulator
    timers are cumulative — the collector stores per-sample deltas
    (first sample is the 0 baseline; a reset/restart clamps to the new
    raw value).  Names in GAUGE_STATS / GAUGE_TIMERS and everything
    under "gauges" are levels.  Sampling reads host-side dicts only:
    the dispatch hot path's zero-sync contract holds by construction
    and is lint-watched (hot-path-sync) + profiler-asserted
    (tests/test_telemetry.py)."""

    def __init__(self, sources: Optional[Callable] = None,
                 sample_s: Optional[float] = None,
                 capacity: int = DEFAULT_CAPACITY,
                 max_series: int = DEFAULT_MAX_SERIES,
                 watchdog: Optional[Watchdog] = None,
                 clock: Callable[[], float] = time.time):
        self.sources = sources if sources is not None \
            else default_sources()
        self.sample_s = float(sample_s) if sample_s is not None \
            else default_sample_s()
        self.store = MetricStore(capacity=capacity,
                                 max_series=max_series)
        self.watchdog = watchdog
        if watchdog is not None:
            watchdog.cfg.setdefault("window_ms", 1000.0)
            watchdog.cfg["window_ms"] = max(1.0,
                                            self.sample_s * 1000.0)
            watchdog.collector = self
        self.clock = clock
        self.samples = 0
        self.source_errors = 0
        self.sampler_overhead_ms = 0.0
        # wiring seams (obs.start_telemetry fills these in-process)
        self.overhead_cb: Optional[Callable[[float], None]] = None
        self.snapshot_cb: Optional[Callable[[], dict]] = None
        self.trace_json_cb: Optional[Callable[[], dict]] = None
        self._prev_counters: Dict[str, float] = {}
        self._prev_timers: Dict[str, float] = {}
        self._merged: Optional[dict] = None
        self._merged_t: Optional[float] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # -- sampling (watched by hot-path-sync) -------------------------------
    def sample_once(self) -> List[dict]:
        """Fold one sample into the store; returns watchdog firings."""
        t0 = time.perf_counter()
        try:
            data = self.sources() or {}
        except Exception:  # noqa: BLE001 - a broken source must not
            # kill the sampler thread
            self.source_errors += 1
            return []
        now = self.clock()
        for name, raw in (data.get("counters") or {}).items():
            if _is_gauge_stat(name):
                self.store.record(now, name, GAUGE, raw)
            else:
                self.store.record(now, name, COUNTER,
                                  self._delta(self._prev_counters,
                                              name, raw), cum=raw)
        for name, raw in (data.get("timers_ms") or {}).items():
            if name in GAUGE_TIMERS:
                self.store.record(now, name, GAUGE, raw)
            else:
                self.store.record(now, name, COUNTER,
                                  self._delta(self._prev_timers,
                                              name, raw), cum=raw)
        for name, val in (data.get("gauges") or {}).items():
            self.store.record(now, name, GAUGE, val)
        if self._merged_t is not None:
            self.store.record(now, "merged_age_s", GAUGE,
                              max(0.0, now - self._merged_t))
        fired = []
        if self.watchdog is not None:
            fired = self.watchdog.observe(self, now)
        self.samples += 1
        dt_ms = (time.perf_counter() - t0) * 1e3
        self.sampler_overhead_ms += dt_ms
        if self.overhead_cb is not None:
            self.overhead_cb(dt_ms)
        return fired

    @staticmethod
    def _delta(prev: Dict[str, float], name: str, raw) -> float:
        raw = float(raw)
        last = prev.get(name)
        prev[name] = raw
        if last is None:
            return 0.0  # baseline sample
        d = raw - last
        return d if d >= 0.0 else raw  # counter reset: restart at raw

    def _loop(self) -> None:
        while not self._stop.wait(self.sample_s):
            self.sample_once()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> "Collector":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="telemetry-sampler", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    # -- pod-merged view ---------------------------------------------------
    def refresh_merged(self, gather_fn: Callable[[], dict]) -> None:
        """Cache a pod-merged snapshot.  `gather_fn` is a COLLECTIVE
        (obs.snapshot(all_hosts=True) riding the epoch-boundary gather
        idiom) — the caller guarantees every host calls it; failures
        just keep the previous merged view."""
        try:
            self._merged = gather_fn()
            self._merged_t = self.clock()
        except Exception:  # noqa: BLE001 - observability, not control
            return
        hosts = (self._merged or {}).get("hosts")
        if isinstance(hosts, (list, dict)):
            # level feed for the host_lost watchdog rule: a host that
            # stops contributing shows up as a drop below the peak
            self.store.record(self._merged_t, "hosts_reporting",
                              GAUGE, float(len(hosts)))

    def merged(self) -> Optional[dict]:
        if self._merged is None:
            return None
        return {"t": self._merged_t, **self._merged}

    # -- export ------------------------------------------------------------
    def drops(self) -> int:
        return self.store.points_dropped() + self.store.series_dropped

    def to_json(self) -> Dict[str, Any]:
        doc: Dict[str, Any] = {
            "version": 1,
            "ts": round(self.clock(), 3),
            "sample_s": self.sample_s,
            "samples": self.samples,
            "drops": self.drops(),
            "source_errors": self.source_errors,
            "sampler_overhead_ms": round(self.sampler_overhead_ms, 3),
            "series": self.store.as_dict(),
        }
        if self.watchdog is not None:
            doc["health"] = self.watchdog.health()
        return doc


# ---------------------------------------------------------------------------
# Export renderers
# ---------------------------------------------------------------------------

_PROM_BAD = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    n = _PROM_BAD.sub("_", name)
    return "paddle_tpu_" + (("_" + n) if n[:1].isdigit() else n)


def prometheus_text(collector: Collector) -> str:
    """Prometheus text exposition (v0.0.4): counters as cumulative
    totals, gauges as last level, plus the telemetry self-metrics and
    the health gauge."""
    lines: List[str] = []
    store = collector.store
    for name in store.names():
        s = store.get(name)
        if s is None or not s.points:
            continue
        pn = _prom_name(name)
        if s.kind == COUNTER:
            lines.append(f"# TYPE {pn} counter")
            lines.append(f"{pn} {s.cum:g}")
        else:
            lines.append(f"# TYPE {pn} gauge")
            lines.append(f"{pn} {s.last():g}")
    for pn, val, kind in (
            ("paddle_tpu_telemetry_samples_total",
             collector.samples, "counter"),
            ("paddle_tpu_telemetry_dropped_points_total",
             collector.drops(), "counter"),
            ("paddle_tpu_telemetry_sampler_overhead_ms_total",
             round(collector.sampler_overhead_ms, 3), "counter")):
        lines.append(f"# TYPE {pn} {kind}")
        lines.append(f"{pn} {val:g}")
    if collector.watchdog is not None:
        h = collector.watchdog.health()
        lines.append("# TYPE paddle_tpu_healthy gauge")
        lines.append(f"paddle_tpu_healthy {1 if h['healthy'] else 0}")
        lines.append("# TYPE paddle_tpu_watchdog_fired_total counter")
        lines.append(f"paddle_tpu_watchdog_fired_total "
                     f"{len(collector.watchdog.fired)}")
    return "\n".join(lines) + "\n"


def series_stats(doc: Dict[str, Any]) -> List[dict]:
    """Per-metric min/mean/max/last rows from a telemetry JSON dump
    (one row a series)."""
    rows = []
    for name, s in sorted((doc.get("series") or {}).items()):
        vals = [p[1] for p in s.get("points", [])]
        if not vals:
            continue
        rows.append({"metric": name, "kind": s.get("kind", "?"),
                     "count": len(vals),
                     "min": round(min(vals), 4),
                     "mean": round(sum(vals) / len(vals), 4),
                     "max": round(max(vals), 4),
                     "last": round(vals[-1], 4),
                     "dropped": int(s.get("dropped", 0))})
    return rows


class _ReplayView:
    """The rule view over a saved dump, truncated to the first `upto`
    points of every series — replay walks it forward in time."""

    def __init__(self, series: Dict[str, Any]):
        self._series = {name: [p[1] for p in s.get("points", [])]
                        for name, s in series.items()}
        self.upto: Optional[int] = None

    def names(self) -> List[str]:
        return sorted(self._series)

    def vals(self, name: str) -> List[float]:
        xs = self._series.get(name, [])
        return xs if self.upto is None else xs[:self.upto]

    def last(self, name: str) -> Optional[float]:
        xs = self.vals(name)
        return xs[-1] if xs else None


def replay_rules(doc: Dict[str, Any],
                 thresholds: Optional[dict] = None) -> List[dict]:
    """Which watchdog rules WOULD have fired over a saved series dump,
    walking the samples forward; first firing per rule is reported."""
    cfg = dict(DEFAULT_THRESHOLDS)
    if doc.get("sample_s"):
        cfg["window_ms"] = max(1.0, float(doc["sample_s"]) * 1000.0)
    cfg.update(thresholds or {})
    series = doc.get("series") or {}
    view = _ReplayView(series)
    maxlen = max((len(s.get("points", [])) for s in series.values()),
                 default=0)
    fired: Dict[str, dict] = {}
    for i in range(1, maxlen + 1):
        view.upto = i
        for name, fn in RULES:
            if name in fired:
                continue
            try:
                reason = fn(view, cfg)
            except Exception:  # noqa: BLE001 - tool robustness
                reason = None
            if reason:
                fired[name] = {"rule": name, "reason": reason,
                               "sample": i}
    return list(fired.values())


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------

class _Handler(BaseHTTPRequestHandler):
    """GET-only scrape surface over one Collector.  The handler reads
    host-side ring buffers and cached snapshots ONLY — it must never
    reach for a device array (hot-path-sync watched)."""

    collector: Optional[Collector] = None
    server_version = "paddle-tpu-telemetry/1"

    def log_message(self, fmt, *args):  # noqa: D102 - silence stderr
        pass

    def _send(self, code: int, body: bytes,
              ctype: str = "application/json") -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802 - http.server API
        col = self.collector
        if col is None:
            self._send(503, b'{"error": "no collector attached"}')
            return
        url = urlparse(self.path)
        q = parse_qs(url.query)
        if url.path == "/metrics":
            if q.get("format", [""])[0] == "json":
                self._send(200, json.dumps(col.to_json()).encode())
            else:
                self._send(200, prometheus_text(col).encode(),
                           "text/plain; version=0.0.4")
        elif url.path == "/healthz":
            wd = col.watchdog
            h = wd.health() if wd is not None else {"healthy": True,
                                                    "reason": None}
            self._send(200 if h["healthy"] else 503,
                       json.dumps(h).encode())
        elif url.path == "/snapshot":
            if q.get("all_hosts", [""])[0] in ("1", "true"):
                merged = col.merged()
                if merged is not None:
                    self._send(200, json.dumps(merged).encode())
                    return
                # no epoch boundary yet: fall through to the local view
            if col.snapshot_cb is None:
                self._send(404, b'{"error": "no snapshot source"}')
                return
            try:
                snap = col.snapshot_cb()
            except Exception as e:  # noqa: BLE001 - scrape robustness
                self._send(500, json.dumps(
                    {"error": f"{type(e).__name__}: {e}"}).encode())
                return
            self._send(200, json.dumps(snap).encode())
        elif url.path == "/debug/trace":
            if col.trace_json_cb is None:
                self._send(404, b'{"error": "no trace source"}')
                return
            try:
                doc = col.trace_json_cb()
            except Exception as e:  # noqa: BLE001
                self._send(500, json.dumps(
                    {"error": f"{type(e).__name__}: {e}"}).encode())
                return
            self._send(200, json.dumps(doc).encode())
        else:
            self._send(404, b'{"error": "not found", "endpoints": '
                            b'["/metrics", "/healthz", "/snapshot", '
                            b'"/debug/trace"]}')


class TelemetryServer:
    """stdlib http.server wrapper: one daemon thread, port 0 picks an
    ephemeral port (read it back from `.port`)."""

    def __init__(self, collector: Collector, port: int = 0,
                 host: str = "127.0.0.1"):
        handler = type("_BoundHandler", (_Handler,),
                       {"collector": collector})
        self._httpd = ThreadingHTTPServer((host, int(port)), handler)
        self._httpd.daemon_threads = True
        self.host = host
        self.port = int(self._httpd.server_address[1])
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="telemetry-http",
            daemon=True)

    def start(self) -> "TelemetryServer":
        if not self._thread.is_alive():
            self._thread.start()
        return self

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        if self._thread.is_alive():
            self._thread.join(timeout=5)
