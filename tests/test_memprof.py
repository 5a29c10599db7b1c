"""HBM memory observability (ISSUE 14): paddle_tpu.obs.memprof.

* Static attribution: the transformed toy ResNet block's executable
  temp-buffer peak folds back onto source Program ops — >=80% of temp
  bytes attributed, the remainder in an explicit `unattributed` bin,
  and the normalized rows sum to the profile total exactly.
* Live ledger: scope vars / compile const caches / feed cache / KV
  pages / in-flight ckpt snapshots / feed-ring staged batches, each
  reconciled against (injected) `device.memory_stats()` so
  `bytes_in_use = ledger total + unattributed` with the residual
  explicit; device fields stay None on CPU where memory_stats() is
  absent.
* Telemetry: `hbm_*` / `ledger_*` gauges visible via /metrics with no
  new sampler thread; the `hbm_pressure` rule fires on utilization and
  on headroom < static temp, and is silent by construction when the
  hbm series are absent (single-host CPU).
* OOM forensics: an injected RESOURCE_EXHAUSTED in Executor._dispatch
  publishes a complete flight bundle (memory.json = ledger + the
  failing program's top static temp buffers) through a live watchdog
  AND through the standalone PADDLE_OBS_FLIGHT_DIR path; healthy runs
  publish nothing; non-OOM errors re-raise untouched.
* Satellites: compile/feed-cache LRU eviction drops device residents
  and shrinks the ledger (`compile_cache_evicted_bytes` counted), the
  ckpt snapshot doubling window is a ledger entry for exactly its
  lifetime, KV pages export `serving_kv_pages_in_use`/`serving_kv_bytes`,
  the Chrome-trace export carries the "C" memory counter track, and
  the bench_diff gate regresses on an hbm_peak_bytes rise > 5%.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.fluid as fluid
from paddle_tpu import obs, profiler
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.obs import memprof, telemetry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
import bench_diff  # noqa: E402


@pytest.fixture(autouse=True)
def _restore_memprof_state():
    yield
    paddle_tpu.set_flags({"FLAGS_graph_transforms": "on"})
    memprof.set_device_stats_fn(None)
    memprof.reset_oom()
    # push-entries some tests stage explicitly; pull sources clean up
    # with their owners (WeakSet / live-cache reads)
    for name in ("feed_ring_bytes", "ckpt_snapshot_bytes"):
        memprof.set_entry(name, 0)


def _resnet_block_program():
    """The residual block the NHWC + fold_bn passes were built for:
    conv+bn+relu trunk, conv+bn, conv+bn skip, add, relu, mean."""
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("image", [2, 3, 16, 16], "float32")
        a = fluid.layers.conv2d(x, 8, 3, padding=1, bias_attr=False)
        a = fluid.layers.batch_norm(a, act="relu")
        b = fluid.layers.conv2d(a, 8, 3, padding=1, bias_attr=False)
        b = fluid.layers.batch_norm(b)
        s = fluid.layers.conv2d(x, 8, 1, bias_attr=False)
        s = fluid.layers.batch_norm(s)
        y = fluid.layers.relu(fluid.layers.elementwise_add(s, b))
        out = fluid.layers.reduce_mean(y)
    return main, startup, out


def _tiny_program(shape=(4, 4), name="x"):
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data(name, list(shape), "float32")
        out = fluid.layers.reduce_mean(fluid.layers.relu(x))
    return main, startup, out


def _run_resnet(exe, feed_seed=0):
    """Compile + dispatch the transformed block under `exe`'s caches;
    returns the inference program the profile attributes to."""
    main, startup, out = _resnet_block_program()
    infer = main.clone(for_test=True)
    paddle_tpu.set_flags({"FLAGS_graph_transforms": "on,fold_bn=on"})
    exe.run(startup)
    feed = np.random.RandomState(feed_seed) \
        .randn(2, 3, 16, 16).astype("float32")
    exe.run(infer, feed={"image": feed}, fetch_list=[out.name])
    return infer, out


# ---------------------------------------------------------------------------
# parser units: synthetic HLO, no jax required
# ---------------------------------------------------------------------------

_UNIT_HLO = """
HloModule unit

ENTRY %main (p0: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %t = f32[64]{0} transpose(%p0), metadata={op_name="jit(f)/program#9/block0/op1:transpose/t"}
  %mystery = f32[32]{0} copy(%t)
  ROOT %r = f32[64]{0} add(%t, %t), metadata={op_name="jit(f)/program#9/block0/op2:elementwise_add/add"}
}
"""


class TestProfileMemoryText:
    def test_shape_bytes_and_rows(self):
        prof = memprof.profile_memory_text(_UNIT_HLO, label="unit")
        by_op = {r["op"]: r for r in prof["rows"]}
        # parameter allocates nothing; transpose/add 64*4 each,
        # the metadata-less copy lands in the explicit unattributed bin
        assert "program#9/block0/op1:transpose" in by_op
        assert by_op["program#9/block0/op1:transpose"]["temp_bytes_raw"] \
            == 256.0
        assert by_op["program#9/block0/op2:elementwise_add"][
            "temp_bytes_raw"] == 256.0
        assert by_op[memprof.UNATTRIBUTED]["temp_bytes_raw"] == 128.0
        assert prof["temp_bytes_raw"] == 640.0
        assert prof["attributed_temp_pct"] == pytest.approx(
            512.0 / 640.0 * 100.0)

    def test_memory_analysis_normalizes_rows(self):
        prof = memprof.profile_memory_text(
            _UNIT_HLO, label="unit", memory={"temp_bytes": 320})
        assert prof["temp_bytes"] == 320.0
        assert sum(r["temp_bytes"] for r in prof["rows"]) \
            == pytest.approx(320.0)
        # raw estimates survive alongside the normalized view
        assert prof["temp_bytes_raw"] == 640.0

    def test_instr_prov_overrides_metadata(self):
        prov = {"mystery": "program#9/block0/op1:transpose"}
        prof = memprof.profile_memory_text(_UNIT_HLO, instr_prov=prov)
        by_op = {r["op"]: r for r in prof["rows"]}
        assert memprof.UNATTRIBUTED not in by_op
        assert by_op["program#9/block0/op1:transpose"]["buffers"] == 2
        assert prof["attributed_temp_pct"] == 100.0

    def test_oom_error_signature(self):
        assert memprof.is_oom_error(
            RuntimeError("RESOURCE_EXHAUSTED: Out of memory"))
        assert memprof.is_oom_error(ValueError("ran out of memory!"))
        assert not memprof.is_oom_error(TypeError("bad argument"))


# ---------------------------------------------------------------------------
# static attribution end to end: the transformed toy ResNet block
# ---------------------------------------------------------------------------

class TestStaticAttributionEndToEnd:
    def test_resnet_block_attribution_floor(self):
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            infer, _out = _run_resnet(exe)
            prof = obs.mem_profile(infer)
        assert prof is not None, "compile-cache miss captured no profile"
        assert prof["temp_bytes"] > 0
        # the acceptance floor: >=80% of static temp bytes attributed
        # to named source Program ops
        assert prof["attributed_temp_pct"] >= 80.0
        # every attributed row resolves to THIS program's provenance
        for r in prof["rows"]:
            if r["op"] == memprof.UNATTRIBUTED:
                continue
            assert r["source"]["prog"] == infer.prog_id
        # the residual is explicit: attributed + unattributed == total
        unattr = sum(r["temp_bytes_raw"] for r in prof["rows"]
                     if r["op"] == memprof.UNATTRIBUTED)
        attr = sum(r["temp_bytes_raw"] for r in prof["rows"]
                   if r["op"] != memprof.UNATTRIBUTED)
        assert attr + unattr == pytest.approx(prof["temp_bytes_raw"])
        # normalized rows sum to the executable's own temp total
        assert sum(r["temp_bytes"] for r in prof["rows"]) \
            == pytest.approx(prof["temp_bytes"], rel=1e-6)
        # forensics views built on the same table
        assert memprof.top_buffers(prof), "no top-buffer forensics"
        assert memprof.static_temp_peak_bytes() >= prof["temp_bytes"]

    def test_profile_reachable_by_program_and_label(self):
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            infer, _out = _run_resnet(exe)
            by_prog = obs.mem_profile(infer)
            assert by_prog is not None
            by_label = obs.mem_profile(label=by_prog["label"])
            assert by_label is by_prog
            # snapshot embeds the trimmed table
            snap = obs.snapshot()
            assert by_prog["label"] in snap["memory"]["profiles"]


# ---------------------------------------------------------------------------
# live ledger + reconciliation
# ---------------------------------------------------------------------------

class TestMemoryLedger:
    def test_ledger_covers_scope_and_feed_cache(self):
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            _run_resnet(exe)
            led = obs.memory_ledger()
            assert led["entries"]["scope_bytes"] > 0
            assert led["entries"]["feed_cache_bytes"] > 0
            assert led["total"] == sum(led["entries"].values())

    def test_reconciles_against_injected_device_stats(self):
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            _run_resnet(exe)
            base = obs.memory_ledger()
            in_use = base["total"] + base["static_temp_bytes"] + 12345
            memprof.set_device_stats_fn(lambda: {
                "bytes_in_use": in_use,
                "bytes_limit": 16 << 30,
                "peak_bytes_in_use": in_use + 7,
            })
            led = obs.memory_ledger()
            assert led["bytes_in_use"] == in_use
            # the explicit residual: bytes_in_use = ledger total +
            # (executable temp +) unattributed
            assert led["unattributed"] == in_use - led["total"]
            assert led["peak_bytes"] >= in_use + 7
            assert led["device"]["bytes_limit"] == 16 << 30

    def test_cpu_without_memory_stats_degrades_to_none(self):
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            _run_resnet(exe)
            led = obs.memory_ledger()  # CPU: memory_stats() is absent
        assert led["bytes_in_use"] is None
        assert led["unattributed"] is None
        assert led["device"] is None
        # ...but the ledger itself still explains the framework's bytes
        assert led["total"] > 0
        assert led["peak_bytes"] > 0

    def test_gauges_fold_hbm_series_only_with_stats(self):
        g = memprof.ledger_gauges(record=False)
        assert "ledger_total_bytes" in g
        assert "hbm_bytes_in_use" not in g  # CPU: series absent
        memprof.set_device_stats_fn(lambda: {
            "bytes_in_use": 5000, "bytes_limit": 10000,
            "peak_bytes_in_use": 6000})
        g = memprof.ledger_gauges(record=False)
        assert g["hbm_bytes_in_use"] == 5000.0
        assert g["hbm_limit_bytes"] == 10000.0
        assert g["hbm_peak_bytes"] >= 6000.0


# ---------------------------------------------------------------------------
# telemetry: /metrics series + the hbm_pressure rule
# ---------------------------------------------------------------------------

def _gauge_store(**series):
    st = telemetry.MetricStore()
    for name, vals in series.items():
        for i, v in enumerate(vals):
            st.record(float(i), name, telemetry.GAUGE, float(v))
    return st


class TestHbmPressureRule:
    CFG = dict(telemetry.DEFAULT_THRESHOLDS)

    def test_utilization_pos_neg(self):
        pos = telemetry.rule_hbm_pressure(
            _gauge_store(hbm_bytes_in_use=[9.3e9],
                         hbm_limit_bytes=[1e10]), self.CFG)
        assert pos and "93%" in pos
        assert telemetry.rule_hbm_pressure(
            _gauge_store(hbm_bytes_in_use=[5e9],
                         hbm_limit_bytes=[1e10]), self.CFG) is None

    def test_one_full_device_among_empty_ones_fires(self):
        # sums say 25% used; the fullest device is at 97%
        pos = telemetry.rule_hbm_pressure(
            _gauge_store(hbm_bytes_in_use=[1e10], hbm_limit_bytes=[4e10],
                         hbm_fullest_device_frac=[0.97],
                         hbm_min_headroom_bytes=[3e8]), self.CFG)
        assert pos and "97%" in pos
        # per-device headroom, not the summed one, must hold the temp
        pos = telemetry.rule_hbm_pressure(
            _gauge_store(hbm_bytes_in_use=[1e10], hbm_limit_bytes=[4e10],
                         hbm_fullest_device_frac=[0.8],
                         hbm_min_headroom_bytes=[2e9],
                         hbm_static_temp_bytes=[3e9]), self.CFG)
        assert pos and "static temp" in pos

    def test_device_stats_report_the_fullest_device(self, monkeypatch):
        import jax

        class Dev:
            def __init__(self, used):
                self.used = used

            def memory_stats(self):
                return {"bytes_in_use": self.used,
                        "peak_bytes_in_use": self.used,
                        "bytes_limit": 1000}

        monkeypatch.setattr(jax, "local_devices",
                            lambda: [Dev(900), Dev(100)])
        stats = memprof.device_memory_stats()
        assert stats["bytes_in_use"] == 1000
        assert stats["bytes_limit"] == 2000
        assert stats["fullest_frac"] == 0.9
        assert stats["min_headroom_bytes"] == 100
        g = memprof.ledger_gauges(record=False)
        assert g["hbm_fullest_device_frac"] == 0.9
        assert g["hbm_min_headroom_bytes"] == 100.0

    def test_headroom_below_static_temp_fires(self):
        pos = telemetry.rule_hbm_pressure(
            _gauge_store(hbm_bytes_in_use=[8e9],
                         hbm_limit_bytes=[1e10],
                         hbm_static_temp_bytes=[3e9]), self.CFG)
        assert pos and "static temp" in pos
        assert telemetry.rule_hbm_pressure(
            _gauge_store(hbm_bytes_in_use=[8e9],
                         hbm_limit_bytes=[1e10],
                         hbm_static_temp_bytes=[1e9]),
            self.CFG) is None

    def test_absent_series_is_silent_by_construction(self):
        # single-host CPU: memory_stats() is None, so the hbm_* series
        # never exist and the rule can never fire
        assert telemetry.rule_hbm_pressure(
            _gauge_store(ledger_total_bytes=[1e9]), self.CFG) is None
        assert telemetry.rule_hbm_pressure(
            _gauge_store(hbm_bytes_in_use=[9.9e9]), self.CFG) is None

    def test_cpu_sampler_never_fires_hbm_pressure(self, tmp_path):
        wd = telemetry.Watchdog(artifacts_dir=str(tmp_path))
        col = telemetry.Collector(sources=telemetry.default_sources(),
                                  sample_s=60.0, watchdog=wd)
        for _ in range(6):
            fired = col.sample_once()
            assert not any(f["rule"] == "hbm_pressure" for f in fired)
        assert col.store.last("hbm_bytes_in_use") is None


class TestMetricsEndpoint:
    def test_hbm_and_ledger_series_visible(self, tmp_path):
        memprof.set_device_stats_fn(lambda: {
            "bytes_in_use": 10 << 30, "bytes_limit": 1 << 40,
            "peak_bytes_in_use": 11 << 30})
        handle = obs.start_telemetry(port=0, sample_s=60.0,
                                     flight_dir=str(tmp_path))
        try:
            handle.collector.sample_once()
            import urllib.request
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{handle.port}/metrics",
                    timeout=5) as r:
                body = r.read().decode()
            assert "hbm_bytes_in_use" in body
            assert "hbm_limit_bytes" in body
            assert "hbm_peak_bytes" in body
            assert "ledger_total_bytes" in body
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{handle.port}/healthz",
                    timeout=5) as r:
                health = json.loads(r.read().decode())
            assert health["healthy"]
        finally:
            obs.stop_telemetry()
        # healthy session: the flight dir stays empty
        assert not [n for n in os.listdir(str(tmp_path))
                    if n.startswith(telemetry.BUNDLE_PREFIX)]


# ---------------------------------------------------------------------------
# OOM forensics: injected RESOURCE_EXHAUSTED in Executor._dispatch
# ---------------------------------------------------------------------------

def _arm_oom(exe, message):
    """Replace the most-recently-used cached executable (the inference
    program — the startup program has its own entry) with one that
    raises."""
    entry = list(exe._cache.values())[-1]

    def boom(*_a, **_k):
        raise RuntimeError(message)

    entry.fn_compiled = boom
    entry.fn = boom
    return entry


class TestOOMForensics:
    FEED = {"image": np.zeros((2, 3, 16, 16), "float32")}

    def test_oom_publishes_full_bundle_through_live_watchdog(
            self, tmp_path):
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            infer, out = _run_resnet(exe)
            exe._cache.capacity = 1  # keep exactly the armed entry
            handle = obs.start_telemetry(port=-1, sample_s=60.0,
                                         flight_dir=str(tmp_path))
            try:
                _arm_oom(exe, "RESOURCE_EXHAUSTED: Out of memory "
                              "while trying to allocate 1073741824 "
                              "bytes")
                with pytest.raises(RuntimeError,
                                   match="RESOURCE_EXHAUSTED"):
                    exe.run(infer, feed=self.FEED,
                            fetch_list=[out.name])
                assert not handle.watchdog.healthy
                assert "mem_oom" in handle.watchdog.reason
            finally:
                obs.stop_telemetry()
        (bundle,) = [n for n in os.listdir(str(tmp_path))
                     if n.startswith(telemetry.BUNDLE_PREFIX)]
        assert "mem_oom" in bundle
        bdir = tmp_path / bundle
        for fname in ("reason.json", "series.json", "memory.json"):
            assert (bdir / fname).exists(), f"bundle missing {fname}"
        mem = json.loads((bdir / "memory.json").read_text())
        assert mem["last_oom"]["kind"] == "mem_oom"
        assert "RESOURCE_EXHAUSTED" in mem["last_oom"]["error"]
        assert mem["last_oom"]["ledger"]["entries"]
        assert mem["last_oom"]["top_buffers"], \
            "OOM report lost the failing program's top static buffers"
        assert mem["ledger"]["total"] >= 0 and mem["profiles"]

    def test_oom_without_telemetry_uses_flight_dir(self, tmp_path,
                                                   monkeypatch):
        assert obs.telemetry_handle() is None
        monkeypatch.setenv("PADDLE_OBS_FLIGHT_DIR", str(tmp_path))
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            infer, out = _run_resnet(exe)
            _arm_oom(exe, "RESOURCE_EXHAUSTED: out of memory")
            with pytest.raises(RuntimeError):
                exe.run(infer, feed=self.FEED, fetch_list=[out.name])
        (bundle,) = [n for n in os.listdir(str(tmp_path))
                     if n.startswith(telemetry.BUNDLE_PREFIX)]
        assert "mem_oom" in bundle
        mem = json.loads((tmp_path / bundle / "memory.json")
                         .read_text())
        assert mem["kind"] == "mem_oom"
        assert mem["top_buffers"]
        reason = json.loads((tmp_path / bundle / "reason.json")
                            .read_text())
        assert reason["fired"][0]["rule"] == "mem_oom"

    def test_healthy_run_publishes_nothing(self, tmp_path, monkeypatch):
        monkeypatch.setenv("PADDLE_OBS_FLIGHT_DIR", str(tmp_path))
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            _run_resnet(exe)
        assert not os.listdir(str(tmp_path))
        assert memprof.last_oom() is None

    def test_non_oom_errors_reraise_untouched(self, tmp_path,
                                              monkeypatch):
        monkeypatch.setenv("PADDLE_OBS_FLIGHT_DIR", str(tmp_path))
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            infer, out = _run_resnet(exe)
            _arm_oom(exe, "some unrelated dispatch failure")
            with pytest.raises(RuntimeError, match="unrelated"):
                exe.run(infer, feed=self.FEED, fetch_list=[out.name])
        assert not os.listdir(str(tmp_path))
        assert memprof.last_oom() is None


# ---------------------------------------------------------------------------
# satellite: compile/feed-cache LRU eviction releases device residents
# ---------------------------------------------------------------------------

class TestCacheEviction:
    def test_feed_cache_eviction_shrinks_ledger(self):
        import gc

        gc.collect()  # drop earlier tests' executors from the WeakSet
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            exe._feed_cache.capacity = 1
            main, startup, out = _tiny_program()
            exe.run(startup)
            evicted0 = profiler.get_int_stats() \
                .get("compile_cache_evicted_bytes", 0)
            base = obs.memory_ledger()["entries"] \
                .get("feed_cache_bytes", 0)
            exe.run(main, feed={"x": np.ones((4, 4), "float32")},
                    fetch_list=[out.name])
            one = obs.memory_ledger()["entries"]["feed_cache_bytes"]
            assert one - base == 64  # 4*4 f32, content-hash cached
            exe.run(main, feed={"x": np.full((4, 4), 2.0, "float32")},
                    fetch_list=[out.name])
            led = obs.memory_ledger()["entries"]["feed_cache_bytes"]
            # capacity 1: the second distinct feed EVICTED the first —
            # the ledger holds one buffer, not two
            assert led == one
            evicted = profiler.get_int_stats() \
                .get("compile_cache_evicted_bytes", 0)
            assert evicted - evicted0 >= 64

    def test_entry_eviction_drops_device_references(self):
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            main_a, startup_a, out_a = _tiny_program((4, 4))
            exe.run(startup_a)
            exe.run(main_a, feed={"x": np.ones((4, 4), "float32")},
                    fetch_list=[out_a.name])
            entry = list(exe._cache.values())[-1]  # MRU = main_a's
            assert entry.fn is not None
            exe._cache.capacity = 1
            main_b, startup_b, out_b = _tiny_program((8, 8), name="y")
            exe.run(main_b, feed={"y": np.ones((8, 8), "float32")},
                    fetch_list=[out_b.name])
            # the LRU evicted entry holds NO device references: no jit
            # wrapper, no AOT executable, no const cache
            assert entry.fn is None
            assert entry.fn_compiled is None
            assert entry.const_dev == {}


# ---------------------------------------------------------------------------
# satellite: ckpt snapshot doubling window is a ledger entry
# ---------------------------------------------------------------------------

class TestCkptSnapshotLedger:
    def test_snapshot_bytes_held_exactly_while_in_flight(
            self, tmp_path, monkeypatch):
        import jax.numpy as jnp

        from paddle_tpu.ckpt import CheckpointManager
        from paddle_tpu.ckpt import manager as ckpt_manager

        state = {"w": jnp.ones((64, 32), jnp.float32),
                 "b": jnp.ones((32,), jnp.float32)}
        expected = 64 * 32 * 4 + 32 * 4
        gate = threading.Event()
        orig = ckpt_manager.CheckpointManager._write_job

        def gated(self, *a, **kw):
            gate.wait(timeout=30)
            return orig(self, *a, **kw)

        monkeypatch.setattr(ckpt_manager.CheckpointManager,
                            "_write_job", gated)
        assert memprof.get_entry("ckpt_snapshot_bytes") == 0
        m = CheckpointManager(str(tmp_path))
        m.save_async(state, step=1)
        # the writer is gated: the snapshot's device copy — one extra
        # copy of the state, the doubling window — is on the ledger
        assert memprof.get_entry("ckpt_snapshot_bytes") == expected
        led = obs.memory_ledger()
        assert led["entries"]["ckpt_snapshot_bytes"] == expected
        gate.set()
        m.wait()
        assert memprof.get_entry("ckpt_snapshot_bytes") == 0


# ---------------------------------------------------------------------------
# satellite: KV pages in the ledger + serving metrics
# ---------------------------------------------------------------------------

class TestKVCacheLedger:
    def test_pool_bytes_and_in_use_pages_exported(self):
        from paddle_tpu.serving.kv_cache import PagedKVCache

        cache = PagedKVCache(num_pages=16, page_size=4, num_heads=2,
                             head_dim=4)
        pool = int(cache.k.nbytes) + int(cache.v.nbytes)
        led = obs.memory_ledger()
        assert led["entries"]["kv_cache_bytes"] == pool
        cache.table.allocate("req", 9)  # ceil(9/4) = 3 pages
        stats = profiler.get_int_stats()
        assert stats["serving_kv_pages_in_use"] == 3
        per_page = pool // 16
        assert stats["serving_kv_bytes"] == 3 * per_page
        cache.table.free("req")
        stats = profiler.get_int_stats()
        assert stats["serving_kv_pages_in_use"] == 0

    def test_kv_bytes_documented_in_metrics_table(self):
        import paddle_tpu.serving.metrics as smetrics

        assert "serving_kv_bytes" in smetrics.__doc__
        assert "serving_kv_pages_in_use" in smetrics.__doc__


# ---------------------------------------------------------------------------
# satellite: feed DeviceRing staged batches
# ---------------------------------------------------------------------------

class TestFeedRingLedger:
    def test_staged_batches_accounted_put_get_close(self):
        import paddle_tpu.dataset.feed_pipeline as fp

        ring = fp.DeviceRing(depth=2)
        staged = {"x": np.ones((4, 4), "float32")}
        assert memprof.get_entry("feed_ring_bytes") == 0
        ring.put((staged, 0))
        assert memprof.get_entry("feed_ring_bytes") == 64
        ring.put(({"x": np.ones((2, 4), "float32")}, 0))
        assert memprof.get_entry("feed_ring_bytes") == 64 + 32
        item = ring.get()
        assert item[0] is staged
        assert memprof.get_entry("feed_ring_bytes") == 32
        ring.close()  # drains the remaining slot
        assert memprof.get_entry("feed_ring_bytes") == 0

    def test_sentinels_and_exceptions_weigh_nothing(self):
        import paddle_tpu.dataset.feed_pipeline as fp

        ring = fp.DeviceRing(depth=2)
        ring.put(ValueError("forwarded"))
        ring.put_end()
        assert memprof.get_entry("feed_ring_bytes") == 0
        ring.close()


# ---------------------------------------------------------------------------
# surfaces: Chrome counter track + bench_diff gate
# ---------------------------------------------------------------------------

class TestTraceCounterTrack:
    def test_export_trace_carries_memory_counter_events(self, tmp_path):
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            obs.enable(reset=True)
            try:
                _run_resnet(exe)
                obs.memory_ledger()  # records a counter sample
                path = str(tmp_path / "trace.json")
                assert obs.export_trace(path) > 0
            finally:
                obs.disable()
        doc = json.loads(open(path).read())
        counters = [e for e in doc["traceEvents"]
                    if e.get("ph") == "C" and e.get("name") == "memory"]
        assert counters, "no memory counter track in the trace"
        assert any("scope_bytes" in e["args"] for e in counters)


class TestBenchDiffGate:
    def test_hbm_peak_rise_regresses_wiggle_passes(self):
        base = bench_diff._synthetic(46.0, 100.0)
        rise = bench_diff._synthetic(
            46.0, 100.0, hbm_peak=int(1.10 * (1 << 30)))
        rows = {r["metric"]: r for r in bench_diff.diff(base, rise)}
        assert rows["hbm_peak_bytes"]["regressed"]
        wiggle = bench_diff._synthetic(
            46.0, 100.0, hbm_peak=int(1.03 * (1 << 30)))
        rows = {r["metric"]: r for r in bench_diff.diff(base, wiggle)}
        assert not rows["hbm_peak_bytes"]["regressed"]

    def test_extract_reads_detail_memory(self):
        doc = bench_diff._synthetic(46.0, 100.0, hbm_peak=123456)
        assert bench_diff.extract_metrics(doc)["hbm_peak_bytes"] \
            == 123456.0
