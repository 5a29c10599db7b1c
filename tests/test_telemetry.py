"""Live production telemetry (ISSUE 10): paddle_tpu.obs.telemetry.

Covers the collector's delta/level folding and bounded memory, the
Prometheus + JSON export and the /metrics + /healthz + /snapshot +
/debug/trace endpoint, the anomaly watchdog (rule pos/neg: an injected
step-time spike and an injected NaN both flip /healthz with a reason
and publish a COMPLETE flight-record bundle; a healthy run publishes
none), the flight recorder's rate limit + retention GC, the
PADDLE_OBS_HTTP_PORT auto-attach on train_from_dataset and
serving.Engine, and the zero-sync contract: the sampler adds zero
device->host transfers to the dispatch hot path
(executor_sync_count-asserted, like the async-executor suite).  Also
the serving/metrics.py stat-table sync satellite: every stat name the
module writes must appear in its docstring table.
"""

import json
import os
import re
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import obs, profiler
from paddle_tpu.obs import telemetry
from paddle_tpu.obs.telemetry import (Collector, MetricStore, Watchdog,
                                      prometheus_text, replay_rules,
                                      series_stats)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _scripted(counters=None, timers=None, gauges=None):
    """A sources() callable over mutable dicts the test scripts."""
    counters = counters if counters is not None else {}
    timers = timers if timers is not None else {}
    gauges = gauges if gauges is not None else {}

    def sources():
        return {"counters": dict(counters), "timers_ms": dict(timers),
                "gauges": dict(gauges)}

    return sources, counters, timers, gauges


def _collector(tmp_path=None, sample_s=1.0, capacity=600, **wd_kw):
    """Collector + watchdog over scripted sources and a scripted
    clock; returns (collector, watchdog, counters, timers, gauges,
    tick)."""
    sources, counters, timers, gauges = _scripted()
    clock = {"t": 1000.0}
    wd = Watchdog(artifacts_dir=str(tmp_path) if tmp_path else None,
                  clock=lambda: clock["t"], **wd_kw)
    col = Collector(sources=sources, sample_s=sample_s,
                    capacity=capacity, watchdog=wd,
                    clock=lambda: clock["t"])

    def tick(n=1, dt=1.0):
        fired = []
        for _ in range(n):
            clock["t"] += dt
            fired = col.sample_once()
        return fired

    return col, wd, counters, timers, gauges, tick


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.status, r.read().decode()


def _get_allow_error(port, path):
    try:
        return _get(port, path)
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


# ---------------------------------------------------------------------------
# collector: delta/level folding + bounded memory
# ---------------------------------------------------------------------------

class TestCollector:
    def test_counters_fold_as_deltas(self):
        col, _, counters, _, _, tick = _collector()
        counters["steps_total"] = 100
        tick()
        counters["steps_total"] = 130
        tick()
        counters["steps_total"] = 130
        tick()
        # first sample is the baseline (delta 0), then per-sample deltas
        assert col.store.vals("steps_total") == [0.0, 30.0, 0.0]
        # the raw cumulative value survives for the Prometheus renderer
        assert col.store.get("steps_total").cum == 130.0

    def test_counter_reset_restarts_at_raw(self):
        col, _, counters, _, _, tick = _collector()
        counters["c"] = 50
        tick()
        counters["c"] = 7  # registry reset mid-run
        tick()
        assert col.store.vals("c") == [0.0, 7.0]

    def test_gauges_and_gauge_stats_fold_as_levels(self):
        col, _, counters, timers, gauges, tick = _collector()
        counters["serving_queue_depth"] = 4   # GAUGE_STATS member
        timers["shard_skew_ms"] = 12.5        # GAUGE_TIMERS member
        gauges["mfu_pct"] = 37.0
        tick(2)
        assert col.store.vals("serving_queue_depth") == [4.0, 4.0]
        assert col.store.vals("shard_skew_ms") == [12.5, 12.5]
        assert col.store.last("mfu_pct") == 37.0
        for name in ("serving_queue_depth", "shard_skew_ms", "mfu_pct"):
            assert col.store.get(name).kind == telemetry.GAUGE

    def test_accumulator_timers_fold_as_deltas(self):
        col, _, _, timers, _, tick = _collector()
        timers["dispatch_ms"] = 10.0
        tick()
        timers["dispatch_ms"] = 25.0
        tick()
        assert col.store.vals("dispatch_ms") == [0.0, 15.0]
        assert col.store.get("dispatch_ms").kind == telemetry.COUNTER

    def test_bounded_points_with_counted_drops(self):
        col, _, _, _, gauges, tick = _collector(capacity=4)
        for i in range(10):
            gauges["g"] = float(i)
            tick()
        s = col.store.get("g")
        assert len(s.points) == 4
        assert s.dropped == 6
        assert col.store.vals("g") == [6.0, 7.0, 8.0, 9.0]
        assert col.drops() == 6

    def test_bounded_series_count(self):
        sources, _, _, gauges = _scripted()
        col = Collector(sources=sources, sample_s=1.0, max_series=3)
        for i in range(8):
            gauges[f"g{i}"] = 1.0
        col.sample_once()
        assert len(col.store.names()) == 3
        assert col.store.series_dropped == 5
        assert col.drops() == 5

    def test_non_finite_values_sanitized(self):
        col, _, _, _, gauges, tick = _collector()
        gauges["g"] = float("nan")
        tick()
        gauges["g"] = float("inf")
        tick()
        assert col.store.vals("g") == [0.0, 0.0]

    def test_broken_source_counted_not_fatal(self):
        def sources():
            raise RuntimeError("boom")

        col = Collector(sources=sources, sample_s=1.0)
        assert col.sample_once() == []
        assert col.source_errors == 1 and col.samples == 0

    def test_sampler_thread_and_overhead_timer(self):
        sources, _, _, gauges = _scripted()
        gauges["g"] = 1.0
        col = Collector(sources=sources, sample_s=0.01)
        seen = []
        col.overhead_cb = seen.append
        col.start()
        deadline = time.time() + 5.0
        while col.samples < 3 and time.time() < deadline:
            time.sleep(0.01)
        col.stop()
        assert col.samples >= 3
        assert col.sampler_overhead_ms > 0.0
        assert len(seen) == col.samples  # the gateable overhead seam

    def test_to_json_and_series_stats(self):
        col, _, counters, _, gauges, tick = _collector()
        counters["c"] = 0
        for i in range(4):
            counters["c"] += 10
            gauges["g"] = float(i)
            tick()
        doc = col.to_json()
        assert doc["samples"] == 4 and "health" in doc
        rows = {r["metric"]: r for r in series_stats(doc)}
        assert rows["g"]["min"] == 0.0 and rows["g"]["max"] == 3.0
        assert rows["g"]["last"] == 3.0 and rows["g"]["count"] == 4
        assert rows["c"]["mean"] == 7.5  # 0 baseline + three 10s


# ---------------------------------------------------------------------------
# watchdog rules: pos/neg per rule over scripted series
# ---------------------------------------------------------------------------

def _store(**series):
    st = MetricStore()
    for name, vals in series.items():
        kind = telemetry.GAUGE if name in ("step_ms", "mfu_pct",
                                           "serving_queue_depth") \
            else telemetry.COUNTER
        for i, v in enumerate(vals):
            st.record(float(i), name, kind, v)
    return st


class TestWatchdogRules:
    CFG = dict(telemetry.DEFAULT_THRESHOLDS)

    def test_step_spike_pos_neg(self):
        pos = telemetry.rule_step_time_spike(
            _store(step_ms=[10, 10, 11, 10, 90]), self.CFG)
        assert pos and "step_ms" in pos
        assert telemetry.rule_step_time_spike(
            _store(step_ms=[10, 10, 11, 10, 12]), self.CFG) is None
        # too few points: not armed
        assert telemetry.rule_step_time_spike(
            _store(step_ms=[10, 90]), self.CFG) is None

    def test_mfu_drop_pos_neg(self):
        assert telemetry.rule_mfu_drop(
            _store(mfu_pct=[40, 41, 40, 39, 5]), self.CFG)
        assert telemetry.rule_mfu_drop(
            _store(mfu_pct=[40, 41, 40, 39, 38]), self.CFG) is None
        # below the noise floor: never fires
        assert telemetry.rule_mfu_drop(
            _store(mfu_pct=[0.1, 0.1, 0.1, 0.1, 0.01]),
            self.CFG) is None

    def test_non_finite_loss_pos_neg(self):
        assert telemetry.rule_non_finite_loss(
            _store(nan_inf_hits_total=[0, 2]), self.CFG)
        assert telemetry.rule_non_finite_loss(
            _store(nan_inf_hits_total=[0, 0]), self.CFG) is None

    def test_rejection_spike_pos_neg(self):
        assert telemetry.rule_serving_rejection_spike(
            _store(serving_rejected_total=[0, 20],
                   serving_requests_total=[0, 3]), self.CFG)
        # high traffic, few rejects: rate below threshold
        assert telemetry.rule_serving_rejection_spike(
            _store(serving_rejected_total=[0, 6],
                   serving_requests_total=[0, 100]), self.CFG) is None
        # trickle of rejects below the arm count
        assert telemetry.rule_serving_rejection_spike(
            _store(serving_rejected_total=[0, 2],
                   serving_requests_total=[0, 0]), self.CFG) is None

    def test_tenant_rejection_spike_pos_neg(self):
        # one tenant hammered past its quota: fires and NAMES it
        msg = telemetry.rule_tenant_rejection_spike(
            _store(serving_tenant_ranker_rejected_total=[0, 20],
                   serving_tenant_ranker_requests_total=[0, 3]),
            self.CFG)
        assert msg and "'ranker'" in msg
        # healthy tenant: plenty of traffic, rejects below the rate bar
        assert telemetry.rule_tenant_rejection_spike(
            _store(serving_tenant_ranker_rejected_total=[0, 6],
                   serving_tenant_ranker_requests_total=[0, 100]),
            self.CFG) is None
        # trickle of rejects below the arm count never fires
        assert telemetry.rule_tenant_rejection_spike(
            _store(serving_tenant_ranker_rejected_total=[0, 3],
                   serving_tenant_ranker_requests_total=[0, 0]),
            self.CFG) is None
        # no tenant series at all (single-model serving): rule is inert
        assert telemetry.rule_tenant_rejection_spike(
            _store(serving_rejected_total=[0, 50]), self.CFG) is None

    def test_tenant_rule_fires_while_global_rule_stays_green(self):
        # the fleet-wide rate averages the noisy neighbour away: 30
        # rejects vs 1000 admitted is globally fine, but ALL 30 hit
        # tenant "abuser" — the per-tenant rule must still name it,
        # and of two spiking tenants it reports the WORST
        st = _store(
            serving_rejected_total=[0, 30],
            serving_requests_total=[0, 1000],
            serving_tenant_abuser_rejected_total=[0, 25],
            serving_tenant_abuser_requests_total=[0, 2],
            serving_tenant_bursty_rejected_total=[0, 5],
            serving_tenant_bursty_requests_total=[0, 4],
            serving_tenant_good_rejected_total=[0, 0],
            serving_tenant_good_requests_total=[0, 994])
        assert telemetry.rule_serving_rejection_spike(
            st, self.CFG) is None
        msg = telemetry.rule_tenant_rejection_spike(st, self.CFG)
        assert msg and "'abuser'" in msg and "'bursty'" not in msg
        assert ("tenant_rejection_spike",
                telemetry.rule_tenant_rejection_spike) \
            in telemetry.RULES

    def test_queue_saturation_pos_neg(self):
        assert telemetry.rule_serving_queue_saturation(
            _store(serving_queue_depth=[2, 3, 2, 3, 40]), self.CFG)
        assert telemetry.rule_serving_queue_saturation(
            _store(serving_queue_depth=[2, 3, 2, 3, 4]),
            self.CFG) is None
        # a spike that stays shallow (< queue_min) is not saturation
        assert telemetry.rule_serving_queue_saturation(
            _store(serving_queue_depth=[1, 1, 1, 1, 5]),
            self.CFG) is None

    def test_kv_pressure_pos_neg(self):
        # 60 of 63 pages handed out: past the 90% threshold
        msg = telemetry.rule_kv_pressure(
            _store(serving_kv_pages_in_use=[10, 60],
                   serving_kv_pages_capacity=[63, 63]), self.CFG)
        assert msg and "serving_kv_pages_in_use" in msg
        # healthy pool: below threshold
        assert telemetry.rule_kv_pressure(
            _store(serving_kv_pages_in_use=[10, 20],
                   serving_kv_pages_capacity=[63, 63]),
            self.CFG) is None
        # no serving engine on this host: series absent, rule silent
        assert telemetry.rule_kv_pressure(
            _store(step_ms=[10, 10]), self.CFG) is None
        assert ("kv_pressure", telemetry.rule_kv_pressure) \
            in telemetry.RULES

    def test_ckpt_stall_pos_neg(self):
        assert telemetry.rule_ckpt_stall(
            _store(ckpt_stall_ms=[0, 900]), self.CFG)
        assert telemetry.rule_ckpt_stall(
            _store(ckpt_stall_ms=[0, 100]), self.CFG) is None

    def test_feed_starvation_pos_neg(self):
        assert telemetry.rule_feed_starvation(
            _store(ring_empty_wait_ms=[0, 800]), self.CFG)
        assert telemetry.rule_feed_starvation(
            _store(ring_empty_wait_ms=[0, 100]), self.CFG) is None

    def test_collective_bytes_jump_pos_neg(self):
        assert telemetry.rule_collective_bytes_jump(
            _store(collective_bytes_c_allreduce_sum=[4096, 4096, 4096,
                                                     40960]), self.CFG)
        assert telemetry.rule_collective_bytes_jump(
            _store(collective_bytes_c_allreduce_sum=[4096, 4096, 4096,
                                                     4096]),
            self.CFG) is None

    def test_host_lost_pos_neg(self):
        def st(hosts, ages=()):
            s = MetricStore()
            for i, v in enumerate(hosts):
                s.record(float(i), "hosts_reporting",
                         telemetry.GAUGE, float(v))
            for i, v in enumerate(ages):
                s.record(float(i), "merged_age_s",
                         telemetry.GAUGE, float(v))
            return s

        # a host drops out of the pod-merged snapshot
        pos = telemetry.rule_host_lost(st([2, 2, 1]), self.CFG)
        assert pos and "1 host(s) missing" in pos
        # full pod reporting: silent
        assert telemetry.rule_host_lost(st([2, 2, 2]), self.CFG) is None
        # single-host run: nothing to lose, never fires
        assert telemetry.rule_host_lost(st([1, 1]), self.CFG) is None
        assert telemetry.rule_host_lost(
            st([1, 1], ages=[9999]), self.CFG) is None
        # pod intact but the merged snapshot went stale: the gather
        # stopped reaching this host
        stale = telemetry.rule_host_lost(
            st([2, 2, 2], ages=[1, 2, 400]), self.CFG)
        assert stale and "stale" in stale
        assert telemetry.rule_host_lost(
            st([2, 2, 2], ages=[1, 2, 30]), self.CFG) is None

    def test_broken_rule_is_contained(self):
        wd = Watchdog(rules=[("boom", lambda v, c: 1 / 0),
                             ("ok", lambda v, c: "fired")])
        assert wd.evaluate(_store()) == [("ok", "fired")]


class TestHostLostFeed:
    """refresh_merged / sample_once feed the series rule_host_lost
    reads, so losing a pod host actually pages."""

    def test_collector_records_hosts_and_merge_age(self, tmp_path):
        col, wd, counters, timers, gauges, tick = _collector(tmp_path)
        col.refresh_merged(lambda: {"hosts": {"0": {}, "1": {}}})
        assert col.store.last("hosts_reporting") == 2.0
        tick(3)
        age = col.store.last("merged_age_s")
        assert age is not None and age >= 3.0
        # a failing gather leaves the last good snapshot (and its
        # growing age) in place instead of recording a phantom count
        col.refresh_merged(lambda: 1 / 0)
        assert col.store.last("hosts_reporting") == 2.0


# ---------------------------------------------------------------------------
# watchdog + flight recorder end to end
# ---------------------------------------------------------------------------

BUNDLE_FILES = ("reason.json", "series.json", "snapshot.json",
                "op_profile.json", "trace.json")


def _bundles(d):
    return sorted(n for n in os.listdir(str(d))
                  if n.startswith(telemetry.BUNDLE_PREFIX))


def _full_callbacks(kw):
    kw.setdefault("snapshot_cb", lambda: {"host": 0})
    kw.setdefault("op_profile_cb", lambda: {"tables": {}})
    kw.setdefault("trace_cb",
                  lambda p: json.dump({"traceEvents": []}, open(p, "w")))
    return kw


class TestFlightRecorder:
    def _spike(self, tmp_path, **wd_kw):
        col, wd, _, _, gauges, tick = _collector(
            tmp_path=tmp_path, **_full_callbacks(wd_kw))
        gauges["step_ms"] = 10.0
        tick(6)
        gauges["step_ms"] = 500.0
        return col, wd, gauges, tick

    def test_healthy_run_produces_nothing(self, tmp_path):
        col, wd, _, _, gauges, tick = _collector(
            tmp_path=tmp_path, **_full_callbacks({}))
        gauges["step_ms"] = 10.0
        gauges["mfu_pct"] = 40.0
        tick(20)
        assert wd.healthy and wd.reason is None
        assert not os.listdir(str(tmp_path))
        status = wd.health()
        assert status["healthy"] and status["fired"] == []

    def test_step_spike_flips_health_and_dumps_complete_bundle(
            self, tmp_path):
        col, wd, gauges, tick = self._spike(tmp_path)
        fired = tick()
        assert {f["rule"] for f in fired} == {"step_time_spike"}
        assert not wd.healthy
        assert "step_ms" in wd.reason
        (bundle,) = _bundles(tmp_path)
        assert "step_time_spike" in bundle
        bdir = tmp_path / bundle
        for fname in BUNDLE_FILES:
            assert (bdir / fname).exists(), f"bundle missing {fname}"
        reason = json.loads((bdir / "reason.json").read_text())
        assert reason["fired"][0]["rule"] == "step_time_spike"
        assert reason["errors"] == {}
        series = json.loads((bdir / "series.json").read_text())
        assert series["series"]["step_ms"]["points"][-1][1] == 500.0
        # the dump replays offline through replay_rules
        assert any(f["rule"] == "step_time_spike"
                   for f in replay_rules(series))

    def test_nan_flips_health_and_dumps_bundle(self, tmp_path):
        col, wd, counters, _, _, tick = _collector(
            tmp_path=tmp_path, **_full_callbacks({}))
        counters["nan_inf_hits_total"] = 0
        tick(3)
        counters["nan_inf_hits_total"] = 2
        fired = tick()
        assert {f["rule"] for f in fired} == {"non_finite_loss"}
        assert not wd.healthy and "non-finite" in wd.reason
        (bundle,) = _bundles(tmp_path)
        for fname in BUNDLE_FILES:
            assert (tmp_path / bundle / fname).exists()

    def test_rate_limit_then_gc(self, tmp_path):
        col, wd, gauges, tick = self._spike(tmp_path, keep=2,
                                            min_interval_s=30.0)
        tick()
        assert wd.bundles_written == 1
        # still anomalous next sample: no second bundle inside the window
        tick()
        assert wd.bundles_written == 1 and wd.dumps_rate_limited >= 1
        assert len(_bundles(tmp_path)) == 1
        # past the window, repeatedly: retention keeps the newest `keep`
        for _ in range(3):
            tick(dt=31.0)
        assert wd.bundles_written == 4
        assert len(_bundles(tmp_path)) == 2

    def test_gc_sweeps_tmp_dirs(self, tmp_path):
        leftover = tmp_path / (telemetry.TMP_PREFIX + "crashed")
        leftover.mkdir()
        col, wd, gauges, tick = self._spike(tmp_path)
        tick()
        assert not leftover.exists()
        assert len(_bundles(tmp_path)) == 1

    def test_broken_export_cb_recorded_not_fatal(self, tmp_path):
        col, wd, gauges, tick = self._spike(
            tmp_path, snapshot_cb=lambda: 1 / 0)
        tick()
        (bundle,) = _bundles(tmp_path)
        reason = json.loads(
            (tmp_path / bundle / "reason.json").read_text())
        assert "snapshot.json" in reason["errors"]
        assert (tmp_path / bundle / "series.json").exists()

    def test_reset_restores_health(self, tmp_path):
        col, wd, gauges, tick = self._spike(tmp_path)
        tick()
        assert not wd.healthy
        wd.reset()
        assert wd.healthy and wd.reason is None
        assert wd.health()["fired"]  # history survives the ack


# ---------------------------------------------------------------------------
# export: Prometheus text + HTTP endpoint
# ---------------------------------------------------------------------------

PROM_LINE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]* -?[0-9.e+-]+$")


class TestExport:
    def test_prometheus_text_parses(self):
        col, wd, counters, _, gauges, tick = _collector()
        counters["steps_total"] = 42
        gauges["mfu_pct"] = 37.5
        tick(2)
        text = prometheus_text(col)
        for line in text.strip().splitlines():
            assert line.startswith("# TYPE ") or PROM_LINE.match(line), \
                f"unparseable exposition line: {line!r}"
        assert "# TYPE paddle_tpu_steps_total counter" in text
        assert "paddle_tpu_steps_total 42" in text  # cum, not delta
        assert "# TYPE paddle_tpu_mfu_pct gauge" in text
        assert "paddle_tpu_mfu_pct 37.5" in text
        assert "paddle_tpu_healthy 1" in text
        assert "paddle_tpu_telemetry_samples_total 2" in text

    def test_metric_name_sanitized(self):
        col, _, counters, _, _, tick = _collector()
        counters["weird.name-1/x"] = 3
        tick()
        assert "paddle_tpu_weird_name_1_x" in prometheus_text(col)

    @pytest.fixture
    def served(self):
        col, wd, counters, timers, gauges, tick = _collector()
        col.snapshot_cb = lambda: {"host": 0, "local": True}
        col.trace_json_cb = lambda: {"traceEvents": [1, 2]}
        srv = telemetry.TelemetryServer(col, port=0).start()
        try:
            yield col, wd, counters, gauges, tick, srv.port
        finally:
            srv.stop()

    def test_http_metrics_and_json(self, served):
        col, wd, counters, gauges, tick, port = served
        counters["steps_total"] = 5
        tick()
        status, body = _get(port, "/metrics")
        assert status == 200 and "paddle_tpu_steps_total 5" in body
        status, body = _get(port, "/metrics?format=json")
        doc = json.loads(body)
        assert doc["samples"] == 1 and "steps_total" in doc["series"]

    def test_http_healthz_flips_with_reason(self, served):
        col, wd, counters, gauges, tick, port = served
        gauges["step_ms"] = 10.0
        tick(6)
        status, body = _get(port, "/healthz")
        assert status == 200 and json.loads(body)["healthy"]
        gauges["step_ms"] = 400.0
        tick()
        status, body = _get_allow_error(port, "/healthz")
        doc = json.loads(body)
        assert status == 503 and not doc["healthy"]
        assert "step_ms" in doc["reason"]

    def test_http_snapshot_local_and_merged(self, served):
        col, wd, counters, gauges, tick, port = served
        status, body = _get(port, "/snapshot")
        assert status == 200 and json.loads(body)["local"]
        # no merged view yet: all_hosts falls back to the local one
        status, body = _get(port, "/snapshot?all_hosts=1")
        assert status == 200 and json.loads(body)["local"]
        col.refresh_merged(lambda: {"hosts": {"0": {}, "1": {}}})
        status, body = _get(port, "/snapshot?all_hosts=1")
        assert status == 200
        assert set(json.loads(body)["hosts"]) == {"0", "1"}

    def test_http_trace_and_404(self, served):
        col, wd, counters, gauges, tick, port = served
        status, body = _get(port, "/debug/trace")
        assert status == 200
        assert json.loads(body)["traceEvents"] == [1, 2]
        status, body = _get_allow_error(port, "/nope")
        assert status == 404 and "endpoints" in body


# ---------------------------------------------------------------------------
# in-process wiring: executor + serving auto-attach, epoch refresh
# ---------------------------------------------------------------------------

def _write_slot_files(d, files=2, rows=20, seed=0):
    os.makedirs(d, exist_ok=True)
    rng = np.random.RandomState(seed)
    W = np.arange(1, 9, dtype="float32").reshape(8, 1) / 10.0
    out = []
    for i in range(files):
        p = os.path.join(d, f"part-{i}.txt")
        with open(p, "w") as f:
            for _ in range(rows):
                x = rng.randn(8).astype("float32")
                f.write("8 " + " ".join(f"{v:.6f}" for v in x)
                        + f" 1 {float((x @ W)[0]):.6f}\n")
        out.append(p)
    return out


class TestTrainingAttach:
    def test_train_from_dataset_serves_metrics_and_detaches(
            self, tmp_path, monkeypatch, fresh_programs):
        """Acceptance: a training run with PADDLE_OBS_HTTP_PORT set
        exposes live /metrics (Prometheus-parseable, gauges present)
        and /healthz mid-run, and the session detaches when the pass
        ends."""
        monkeypatch.setenv("PADDLE_OBS_HTTP_PORT", "0")
        monkeypatch.setenv("PADDLE_OBS_SAMPLE_S", "0.02")
        monkeypatch.setenv("PADDLE_OBS_FLIGHT_DIR",
                           str(tmp_path / "flight"))
        files = _write_slot_files(str(tmp_path / "data"))
        main, startup, scope = fresh_programs
        x = fluid.data("x", [-1, 8], "float32")
        y = fluid.data("y", [-1, 1], "float32")
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.loss.square_error_cost(pred, y))
        fluid.optimizer.SGD(0.1).minimize(loss)
        ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_batch_size(10)
        ds.set_use_var([x, y])
        ds.set_filelist(files)
        ds.load_into_memory()
        exe = fluid.Executor()
        exe.run(startup)
        scrapes = {}

        def cb(step, sie, outs):
            handle = obs.telemetry_handle()
            assert handle is not None and handle.port is not None
            if "metrics" not in scrapes:
                handle.collector.sample_once()
                scrapes["metrics"] = _get(handle.port, "/metrics")[1]
                scrapes["healthz"] = _get(handle.port, "/healthz")

        exe.train_from_dataset(main, ds, fetch_list=[loss],
                               step_callback=cb)
        assert "paddle_tpu_executor_run_calls" in scrapes["metrics"] \
            or "paddle_tpu_" in scrapes["metrics"]
        status, body = scrapes["healthz"]
        assert status == 200 and json.loads(body)["healthy"]
        # the pass released its reference: the session is gone
        assert obs.telemetry_handle() is None

    def test_no_env_no_telemetry(self, tmp_path, monkeypatch,
                                 fresh_programs):
        monkeypatch.delenv("PADDLE_OBS_HTTP_PORT", raising=False)
        assert obs.maybe_start_telemetry() is None
        assert obs.telemetry_handle() is None

    def test_refcounted_sharing(self, monkeypatch):
        """A trainer and a server in one process share ONE session;
        it tears down on the LAST release."""
        h1 = obs.start_telemetry(port=-1, sample_s=60.0)
        h2 = obs.start_telemetry(port=0)
        assert h1 is h2
        obs.stop_telemetry()
        assert obs.telemetry_handle() is h1
        obs.stop_telemetry()
        assert obs.telemetry_handle() is None

    def test_bundle_meta_names_live_tenants(self, tmp_path):
        """reason.json meta must list which tenants shared the device
        at dump time — otherwise an incident bundle can't distinguish
        noisy-neighbour from self-inflicted (serving/registry.py)."""
        from paddle_tpu import serving

        h = obs.start_telemetry(port=-1, sample_s=60.0,
                                flight_dir=str(tmp_path))
        try:
            meta = h.watchdog.meta_cb()
            assert "tenants" not in meta  # no fleet: key absent
            cfg = serving.EngineConfig(max_batch_size=4,
                                       max_queue_delay_ms=0.0)
            with serving.ModelRegistry(cfg) as reg:
                reg.register("ranker", lambda x: [x * 2.0], quota=8)
                reg.register("embedder", lambda x: [x + 1.0], quota=8)
                meta = h.watchdog.meta_cb()
                assert meta["tenants"] == ["embedder", "ranker"]
                assert "quant_collectives" in meta
            assert "tenants" not in h.watchdog.meta_cb()
        finally:
            obs.stop_telemetry()

    def test_epoch_refresh_caches_merged_view(self):
        h = obs.start_telemetry(port=-1, sample_s=60.0)
        try:
            assert h.collector.merged() is None
            obs.telemetry_epoch_refresh()
            merged = h.collector.merged()
            assert merged is not None and "cost" in merged
        finally:
            obs.stop_telemetry()


class TestServingAttach:
    def test_engine_serves_metrics_and_detaches(self, monkeypatch):
        from paddle_tpu import serving
        from paddle_tpu.serving import EngineConfig

        monkeypatch.setenv("PADDLE_OBS_HTTP_PORT", "0")
        monkeypatch.setenv("PADDLE_OBS_SAMPLE_S", "0.02")

        def double(xs):
            return [xs[0] * 2.0]

        eng = serving.Engine(double,
                             EngineConfig(max_batch_size=4,
                                          max_queue_delay_ms=1.0))
        try:
            handle = obs.telemetry_handle()
            assert handle is not None and handle.port is not None
            for i in range(6):
                out = eng.infer([np.full((1, 2), float(i), "float32")],
                                timeout=30)
                np.testing.assert_allclose(out[0], 2.0 * i)
            handle.collector.sample_once()
            _, body = _get(handle.port, "/metrics")
            assert "paddle_tpu_serving_requests_total" in body
            assert "paddle_tpu_serving_queue_depth" in body
            status, _ = _get(handle.port, "/healthz")
            assert status == 200
        finally:
            eng.shutdown(drain=False)
        assert obs.telemetry_handle() is None


# ---------------------------------------------------------------------------
# the NaN seam: async check_nan_inf -> nan_inf_hits_total
# ---------------------------------------------------------------------------

class TestNanSeam:
    def test_nan_monitor_feeds_watchdog_counter(self):
        import jax.numpy as jnp

        from paddle_tpu.fluid.executor import _NanMonitor

        profiler.stat_reset("nan_inf_hits_total")
        mon = _NanMonitor()
        mon.submit(jnp.asarray([False, True, True]), ["a", "b", "c"])
        deadline = time.time() + 5.0
        while time.time() < deadline:
            if profiler.get_int_stats().get("nan_inf_hits_total", 0):
                break
            time.sleep(0.01)
        assert profiler.get_int_stats()["nan_inf_hits_total"] == 2
        with pytest.raises(RuntimeError, match="NaN/Inf"):
            mon.drain()
        # and the watchdog rule fires on exactly this counter's delta
        col, wd, counters, _, _, tick = _collector()
        counters["nan_inf_hits_total"] = 0
        tick()
        counters["nan_inf_hits_total"] = 2
        assert {f["rule"] for f in tick()} == {"non_finite_loss"}


# ---------------------------------------------------------------------------
# zero-sync contract: sampling never touches the dispatch hot path
# ---------------------------------------------------------------------------

class TestZeroSync:
    def test_sampler_adds_zero_syncs_to_async_steps(self,
                                                    fresh_programs):
        """Acceptance: ten async executor steps with the live sampler
        + watchdog + Prometheus render interleaved after every one of
        them — executor_sync_count stays ZERO until the caller's own
        sanctioned materialization."""
        main, startup, scope = fresh_programs
        x = fluid.data("x", [-1, 4], "float32")
        yt = fluid.data("yt", [-1, 1], "float32")
        pred = fluid.layers.fc(x, 1, bias_attr=False)
        loss = fluid.layers.reduce_mean(
            fluid.layers.loss.square_error_cost(pred, yt))
        fluid.optimizer.SGD(0.01).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        rng = np.random.RandomState(0)
        X = rng.rand(8, 4).astype("float32")
        Y = rng.rand(8, 1).astype("float32")
        exe.run(main, feed={"x": X, "yt": Y}, fetch_list=[loss],
                return_numpy=False)  # warm the compile cache
        wd = Watchdog(artifacts_dir=None)
        col = Collector(sources=telemetry.default_sources(),
                        sample_s=60.0, watchdog=wd)
        profiler.stat_reset("executor_sync_count")
        handles = None
        for _ in range(10):
            handles = exe.run(main, feed={"x": X, "yt": Y},
                              fetch_list=[loss], return_numpy=False)
            col.sample_once()
            prometheus_text(col)
        assert profiler.get_int_stats().get("executor_sync_count",
                                            0) == 0
        assert col.samples == 10
        # sanity: the counter still works at the sanctioned boundary
        assert np.isfinite(float(handles[0]))
        assert profiler.get_int_stats()["executor_sync_count"] == 1


# ---------------------------------------------------------------------------
# serving/metrics.py stat-table sync (ISSUE 10 satellite)
# ---------------------------------------------------------------------------

class TestServingMetricsDocs:
    def test_every_written_stat_is_documented(self):
        """Every stat name serving/metrics.py writes (stat_add /
        stat_set string literals) appears in its docstring table — the
        drift that hid serving_batch_requests_total cannot recur."""
        from paddle_tpu.serving import metrics as m

        path = os.path.join(REPO_ROOT, "paddle_tpu", "serving",
                            "metrics.py")
        with open(path) as f:
            src = f.read()
        written = set(re.findall(
            r"stat_(?:add|set|max)\(\s*[\"']([a-z0-9_]+)[\"']", src))
        assert written, "no stats written? parser drifted"
        for name in written:
            assert name in (m.__doc__ or ""), \
                f"{name} written by serving/metrics.py but missing " \
                f"from its docstring stat table"

    def test_batch_requests_total_in_table_and_recorded(self):
        from paddle_tpu.serving import metrics as m

        assert "serving_batch_requests_total" in m.__doc__
        profiler.stat_reset("serving_batch_requests_total")
        m.observe_batch(3, 8, 1)
        assert profiler.get_int_stats()[
            "serving_batch_requests_total"] == 3

    def test_latency_stats_values_unchanged_by_lock_fix(self):
        from paddle_tpu.serving import metrics as m

        m.reset_latency("t_lockfix_ms")
        for v in (5.0, 1.0, 9.0, 3.0):
            m.record_latency("t_lockfix_ms", v)
        s = m.latency_stats("t_lockfix_ms")
        assert s["count"] == 4 and s["max_ms"] == 9.0
        assert s["p50_ms"] == 5.0  # index round(0.5*3)=2 of sorted
        assert m.latency_stats("t_never_recorded_ms") is None
