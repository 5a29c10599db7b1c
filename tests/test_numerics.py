"""Per-op numeric-health attribution (ISSUE 15): paddle_tpu.obs.numerics.

* Stats mode (`PADDLE_OBS_NUMERICS=on`): the instrumented lowering
  appends fused device-side [nan, inf, absmax, l2] reductions per
  float op output plus the training-health rows (grad/param norms,
  update_ratio); everything rides the step's one stacked stats array
  and drains off the hot path — zero added host syncs.
* Zero cost when off: the compiled step's HLO is byte-identical with
  the env var absent vs "off" (the mode joins the compile-cache
  signature, so a flip is a clean recompile), and the dispatch loop's
  executor_sync_count stays flat.
* First-NaN bisection (ACCEPTANCE): a toy conv+bn model with an
  injected log-of-negative mid-network, run under
  FLAGS_graph_transforms="on,fold_bn=on" in bisect mode, raises
  through the async NaN monitor AND the replay names the exact
  injecting `log` op — provenance (with [pass=...] tags visible on the
  transformed neighbors), op_callstack, input stats — and publishes a
  `non_finite_loss` flight bundle whose numerics.json carries the
  complete report, with no sampler thread running.
* Telemetry: grad_norm_total / update_ratio / loss_scale visible in
  the /metrics Prometheus render; `grad_norm_spike` and
  `loss_scale_collapse` watchdog rules pos/neg; a live-collector spike
  publishes a bundle that includes numerics.json.
* Satellites: AMP loss_scale + decrement counter exported and
  documented, and every stat the module writes appears in its
  docstring table.
"""

import glob
import json
import os
import re
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.fluid as fluid
from paddle_tpu import obs, profiler
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.fluid.executor import Scope, _NanMonitor, scope_guard
from paddle_tpu.obs import numerics, telemetry

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = dict(telemetry.DEFAULT_THRESHOLDS)


@pytest.fixture(autouse=True)
def _numerics_state(monkeypatch):
    monkeypatch.delenv("PADDLE_OBS_NUMERICS", raising=False)
    monkeypatch.delenv("PADDLE_OBS_FLIGHT_DIR", raising=False)
    numerics.reset()
    yield
    # _compiled_step_hlo writes os.environ directly (monkeypatch can't
    # see it) — scrub here so no mode leaks into later test files
    os.environ.pop("PADDLE_OBS_NUMERICS", None)
    numerics.reset()
    paddle_tpu.set_flags({"FLAGS_graph_transforms": "on",
                          "FLAGS_check_nan_inf": False,
                          "FLAGS_op_callstack": False})


def _train_net():
    """fc regression + SGD inside the caller's active program guard."""
    x = fluid.data("x", [-1, 4], "float32")
    yt = fluid.data("yt", [-1, 1], "float32")
    pred = fluid.layers.fc(x, 1, name="fc")
    loss = fluid.layers.reduce_mean(
        fluid.layers.loss.square_error_cost(pred, yt))
    fluid.optimizer.SGD(0.01).minimize(loss)
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 4).astype("float32"),
            "yt": rng.rand(8, 1).astype("float32")}
    return loss, feed


def _entry(exe):
    return exe._cache.get(next(iter(exe._cache)))


def _gauge_store(**series):
    st = telemetry.MetricStore()
    for name, vals in series.items():
        for i, v in enumerate(vals):
            st.record(float(i), name, telemetry.GAUGE, float(v))
    return st


def _wait_for(pred, timeout=10.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(0.02)
    return pred()


# ---------------------------------------------------------------------------
# pure units: mode + provenance parsing
# ---------------------------------------------------------------------------

class TestUnits:
    def test_parse_mode_normalizes(self):
        assert numerics.parse_mode("ON") == "on"
        assert numerics.parse_mode("stats") == "on"
        assert numerics.parse_mode("1") == "on"
        assert numerics.parse_mode("Bisect") == "bisect"
        assert numerics.parse_mode(None) == "off"
        assert numerics.parse_mode("garbage") == "off"

    def test_provenance_round_trip_with_pass_tags(self):
        p = numerics.parse_provenance(
            "program#3/block0/op7:conv2d[pass=fold_bn,layout_nhwc]")
        assert p == {"prog": 3, "block": 0, "op": 7, "type": "conv2d",
                     "passes": ["fold_bn", "layout_nhwc"]}
        plain = numerics.parse_provenance("program#1/block2/op0:log")
        assert plain["type"] == "log" and plain["passes"] == []
        assert numerics.parse_provenance("not a provenance") is None


# ---------------------------------------------------------------------------
# stats mode: per-op rows + training-health gauges, no added syncs
# ---------------------------------------------------------------------------

class TestStatsMode:
    def test_health_and_op_rows_collected(self, fresh_programs,
                                          monkeypatch):
        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "on")
        main, startup, scope = fresh_programs
        loss, feed = _train_net()
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss.name])
        gauges = numerics.health_gauges()
        for name in ("grad_norm_total", "grad_norm_fc",
                     "param_norm_total", "update_ratio"):
            assert gauges.get(name, 0.0) > 0.0, name
        doc = numerics.numerics_doc()
        assert doc["steps_drained"] >= 3  # startup dispatch rides too
        assert doc["nonfinite_ops_total"] == 0
        assert doc["ops"], "no per-op rows collected"
        for row in doc["ops"]:
            assert numerics.PROVENANCE_RE.search(row["provenance"]), \
                row["provenance"]
            assert row["nan_count"] == 0 and row["inf_count"] == 0

    def test_stats_on_adds_zero_hot_path_syncs(self, fresh_programs,
                                               monkeypatch):
        """The stacked stats array is fetched asynchronously: a
        dispatch-only loop with collection armed performs ZERO
        device->host transfers; the drain happens at the gauges read
        and does not book executor_sync_count either (that counter is
        the fetch-path contract)."""
        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "on")
        main, startup, scope = fresh_programs
        loss, feed = _train_net()
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss.name],
                return_numpy=False)  # warm the compile cache
        profiler.stat_reset("executor_sync_count")
        for _ in range(5):
            exe.run(main, feed=feed, fetch_list=[loss.name],
                    return_numpy=False)
        assert profiler.get_int_stats().get("executor_sync_count",
                                            0) == 0
        assert numerics.health_gauges().get("grad_norm_total",
                                            0.0) > 0.0
        assert profiler.get_int_stats().get("executor_sync_count",
                                            0) == 0

    def test_mode_joins_compile_signature(self, monkeypatch):
        from paddle_tpu import transforms

        monkeypatch.delenv("PADDLE_OBS_NUMERICS", raising=False)
        sig_unset = transforms.enabled_signature()
        assert not any("numerics" in str(t) for t in sig_unset)
        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "off")
        assert transforms.enabled_signature() == sig_unset
        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "on")
        assert "numerics=on" in transforms.enabled_signature()
        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "bisect")
        assert "numerics=bisect" in transforms.enabled_signature()


# ---------------------------------------------------------------------------
# zero overhead when off: byte-identical HLO + flat sync counters
# ---------------------------------------------------------------------------

def _compiled_step_hlo(mode_env):
    """Compile a tiny no-param program under `mode_env` and return
    (entry, lowered HLO text of the compiled step)."""
    if mode_env is None:
        os.environ.pop("PADDLE_OBS_NUMERICS", None)
    else:
        os.environ["PADDLE_OBS_NUMERICS"] = mode_env
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [2, 4], "float32")
        loss = fluid.layers.reduce_mean(fluid.layers.scale(x, 2.0))
    with scope_guard(Scope()):
        exe = fluid.Executor()
        feed = {"x": np.ones((2, 4), np.float32)}
        exe.run(main, feed=feed, fetch_list=[loss.name],
                return_numpy=False)
        entry = _entry(exe)
        lowered = entry.fn.lower({}, {}, dict(feed), 0)
        return entry, lowered.as_text()


class TestZeroOverheadOff:
    def test_off_hlo_byte_identical_and_uninstrumented(self):
        e_unset, t_unset = _compiled_step_hlo(None)
        e_off, t_off = _compiled_step_hlo("off")
        e_on, t_on = _compiled_step_hlo("on")
        # env absent vs explicit "off": the compiled step is the SAME
        # program, byte for byte — the feature leaves no residue
        assert t_unset == t_off
        assert "nan" not in t_off.lower()  # no isnan/reduction residue
        assert e_off.numerics_mode == "off"
        assert list(e_off.numerics_keys) == []
        assert e_off.lowered_block is None
        # armed mode DOES change the program (and the cache signature)
        assert t_on != t_off and len(t_on) > len(t_off)
        assert len(e_on.numerics_keys) == 2  # scale + reduce_mean outs

    def test_off_keeps_sync_counters_flat(self, fresh_programs,
                                          monkeypatch):
        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "off")
        main, startup, scope = fresh_programs
        loss, feed = _train_net()
        exe = fluid.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss.name],
                return_numpy=False)
        profiler.stat_reset("executor_sync_count")
        for _ in range(5):
            exe.run(main, feed=feed, fetch_list=[loss.name],
                    return_numpy=False)
        assert profiler.get_int_stats().get("executor_sync_count",
                                            0) == 0
        assert numerics.health_gauges() == {}  # nothing collected


# ---------------------------------------------------------------------------
# ACCEPTANCE: first-NaN bisection through the transformed program
# ---------------------------------------------------------------------------

def _injected_nan_program():
    """conv+bn (foldable) trunk with a log-of-negative injected
    mid-network: every dispatch produces NaN at exactly one op."""
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        img = fluid.data("image", [2, 3, 8, 8], "float32")
        c = fluid.layers.conv2d(img, 4, 3, padding=1, bias_attr=False)
        c = fluid.layers.batch_norm(c, act="relu", is_test=True)
        flat = fluid.layers.reduce_mean(c, dim=[1, 2, 3],
                                        keep_dim=False)
        bad = fluid.layers.log(
            fluid.layers.scale(flat, -1.0, bias=-1.0))
        out = fluid.layers.reduce_mean(bad)
    feed = np.abs(np.random.RandomState(0)
                  .randn(2, 3, 8, 8)).astype("float32")
    return main, startup, out, feed


class TestBisectionAcceptance:
    def test_first_nan_bisected_through_fold_bn(self, monkeypatch,
                                                tmp_path):
        """The headline acceptance path: bisect mode + fold_bn/NHWC
        transforms + async NaN monitor + standalone flight bundle (no
        sampler thread anywhere)."""
        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "bisect")
        monkeypatch.setenv("PADDLE_OBS_FLIGHT_DIR", str(tmp_path))
        paddle_tpu.set_flags({"FLAGS_check_nan_inf": True,
                              "FLAGS_graph_transforms": "on,fold_bn=on",
                              "FLAGS_op_callstack": True})
        main, startup, out, feed = _injected_nan_program()
        infer = main.clone(for_test=True)
        with scope_guard(Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            with pytest.raises(RuntimeError,
                               match="NaN/Inf detected.*at step 1"):
                exe.run(infer, feed={"image": feed},
                        fetch_list=[out.name])
                exe.sync()

        # the monitor thread runs the bisection asynchronously
        b = _wait_for(lambda: numerics.numerics_doc()["bisection"])
        assert b and b.get("found"), f"bisection missing: {b}"
        op = b["op"]
        assert op["type"] == "log"
        prov = numerics.parse_provenance(op["provenance"])
        assert prov and prov["type"] == "log"
        assert op["var"].startswith("log")
        assert op["nan_count"] > 0
        assert op["op_callstack"], "construction stack missing"
        ins = op["inputs"]
        assert ins and all(i["nan_count"] == 0 for i in ins), \
            "the log op's INPUTS were finite — it is the injector"
        doc = numerics.numerics_doc()
        assert doc["first_nonfinite_step"] == 1
        # the replay ran the TRANSFORMED program: pass tags visible
        tagged = [r["provenance"] for r in doc["ops"]
                  if "[pass=" in r["provenance"]]
        assert any("fold_bn" in t for t in tagged), tagged
        assert profiler.get_int_stats().get("nan_inf_first_step") == 1
        assert profiler.get_int_stats().get(
            "numerics_bisect_runs_total", 0) >= 1

        # standalone flight bundle: complete numerics.json, published
        # without any telemetry session running
        paths = _wait_for(lambda: glob.glob(
            str(tmp_path / "flight_*_non_finite_loss" /
                "numerics.json")))
        assert paths, os.listdir(str(tmp_path))
        with open(paths[0]) as f:
            bundle_doc = json.load(f)
        assert bundle_doc["bisection"]["op"]["provenance"] == \
            op["provenance"]
        assert bundle_doc["bisection"]["op"]["type"] == "log"
        assert bundle_doc["mode"] == "bisect"
        assert bundle_doc["last_hit"]["step"] == 1
        assert bundle_doc["last_hit"]["hits"]
        with open(os.path.join(os.path.dirname(paths[0]),
                               "reason.json")) as f:
            assert json.load(f)["fired"][0]["rule"] == \
                "non_finite_loss"

    def test_bisect_nonfinite_direct_api(self, fresh_programs,
                                         monkeypatch):
        """obs.bisect_nonfinite(program, feed) works offline — no
        executor, no monitor, no flags."""
        main, startup, scope = fresh_programs
        x = fluid.data("x", [2, 4], "float32")
        h = fluid.layers.scale(x, -1.0, bias=-0.5)
        bad = fluid.layers.log(h)
        fluid.layers.reduce_mean(bad)
        rep = obs.bisect_nonfinite(
            main, feed={"x": np.ones((2, 4), np.float32)})
        assert rep["found"] and rep["op"]["type"] == "log"
        assert numerics.numerics_doc()["bisection"] is rep or \
            numerics.numerics_doc()["bisection"]["op"]["var"] == \
            rep["op"]["var"]

    def test_healthy_run_publishes_nothing(self, fresh_programs,
                                           monkeypatch, tmp_path):
        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "bisect")
        monkeypatch.setenv("PADDLE_OBS_FLIGHT_DIR", str(tmp_path))
        paddle_tpu.set_flags({"FLAGS_check_nan_inf": True})
        main, startup, scope = fresh_programs
        loss, feed = _train_net()
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss.name])
        exe.sync()
        time.sleep(0.1)
        assert not glob.glob(str(tmp_path / "flight_*"))
        assert numerics.numerics_doc()["bisection"] is None
        assert numerics.numerics_doc()["first_nonfinite_step"] is None


# ---------------------------------------------------------------------------
# AMP observability: loss_scale + decrement counter
# ---------------------------------------------------------------------------

class TestAmpObservability:
    def test_loss_scale_and_decrements_exported(self, fresh_programs,
                                                monkeypatch):
        from paddle_tpu.fluid.contrib.mixed_precision import decorate

        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "on")
        main, startup, scope = fresh_programs
        x = fluid.data("x", [-1, 4], "float32")
        x.stop_gradient = True
        pred = fluid.layers.fc(x, 2, bias_attr=False)
        loss = fluid.layers.reduce_mean(pred)
        opt = decorate(fluid.optimizer.Adam(0.1), dtype="float16",
                       init_loss_scaling=8.0, decr_every_n_nan_or_inf=1)
        opt.minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        ones = {"x": np.ones((2, 4), "float32")}
        exe.run(main, feed=ones, fetch_list=[loss.name])
        exe.run(main, feed={"x": np.full((2, 4), np.inf, "float32")},
                fetch_list=[loss.name])  # overflow: scale 8 -> 4
        exe.run(main, feed=ones, fetch_list=[loss.name])
        doc = numerics.numerics_doc()
        assert doc["loss_scale"] == 4.0
        assert doc["loss_scale_decr_total"] == 1
        stats = profiler.get_int_stats()
        assert stats.get("loss_scale") == 4
        assert stats.get("loss_scale_decr_total") == 1
        # documented + classified as a level, not a counter
        assert "loss_scale" in (numerics.__doc__ or "")
        assert "loss_scale" in telemetry.GAUGE_STATS


# ---------------------------------------------------------------------------
# watchdog rules: grad_norm_spike + loss_scale_collapse
# ---------------------------------------------------------------------------

class TestHealthRules:
    def test_rules_registered(self):
        names = [n for n, _ in telemetry.RULES]
        assert "grad_norm_spike" in names
        assert "loss_scale_collapse" in names

    def test_grad_norm_spike_pos_neg(self):
        pos = telemetry.rule_grad_norm_spike(
            _gauge_store(grad_norm_total=[1.0, 1.1, 0.9, 1.0, 30.0]),
            CFG)
        assert pos and "grad_norm_total" in pos
        assert telemetry.rule_grad_norm_spike(
            _gauge_store(grad_norm_total=[1.0, 1.1, 0.9, 1.0, 1.2]),
            CFG) is None
        # absent series (numerics not armed) -> silent by construction
        assert telemetry.rule_grad_norm_spike(
            _gauge_store(step_time_ms=[5.0] * 6), CFG) is None

    def test_loss_scale_collapse_pos_neg(self):
        pos = telemetry.rule_loss_scale_collapse(
            _gauge_store(loss_scale=[32768, 16384, 1024, 64, 1]), CFG)
        assert pos and "collapsed" in pos
        # a steady small scale is not a collapse
        assert telemetry.rule_loss_scale_collapse(
            _gauge_store(loss_scale=[8, 8, 8, 8, 8]), CFG) is None
        # healthy growth is not a collapse
        assert telemetry.rule_loss_scale_collapse(
            _gauge_store(loss_scale=[8, 16, 32, 64, 128]), CFG) is None
        # too few samples: not armed yet
        assert telemetry.rule_loss_scale_collapse(
            _gauge_store(loss_scale=[32768, 1]), CFG) is None

    def test_loss_scale_collapse_bundle_pos_neg(self, tmp_path):
        """A collapsing scale series publishes a flight bundle with
        numerics.json; a steady scale publishes nothing."""
        def run(series, sub):
            gauges = {}

            def sources():
                return {"counters": {}, "timers_ms": {},
                        "gauges": dict(gauges)}

            clock = {"t": 1000.0}
            art = tmp_path / sub
            wd = telemetry.Watchdog(artifacts_dir=str(art),
                                    clock=lambda: clock["t"],
                                    numerics_cb=numerics.numerics_doc)
            col = telemetry.Collector(sources=sources, sample_s=1.0,
                                      watchdog=wd,
                                      clock=lambda: clock["t"])
            fired = []
            for v in series:
                gauges["loss_scale"] = float(v)
                clock["t"] += 1.0
                fired = col.sample_once()
            return fired, art

        fired, art = run([32768, 16384, 1024, 64, 1], "pos")
        assert any(f["rule"] == "loss_scale_collapse" for f in fired)
        assert glob.glob(str(art / "flight_*_loss_scale_collapse" /
                             "numerics.json"))
        fired, art = run([32768] * 5, "neg")
        assert not any(f["rule"] == "loss_scale_collapse"
                       for f in fired)
        assert not glob.glob(str(art / "flight_*"))

    def test_spike_bundle_includes_numerics_json(self, tmp_path):
        """A live collector whose grad_norm_total spikes publishes a
        flight bundle that carries numerics.json (the watchdog's
        numerics_cb seam)."""
        gauges = {"grad_norm_total": 1.0}

        def sources():
            return {"counters": {}, "timers_ms": {},
                    "gauges": dict(gauges)}

        clock = {"t": 1000.0}
        wd = telemetry.Watchdog(artifacts_dir=str(tmp_path),
                                clock=lambda: clock["t"],
                                numerics_cb=numerics.numerics_doc)
        col = telemetry.Collector(sources=sources, sample_s=1.0,
                                  watchdog=wd,
                                  clock=lambda: clock["t"])
        fired = []
        for _ in range(5):
            clock["t"] += 1.0
            fired = col.sample_once()
        assert not any(f["rule"] == "grad_norm_spike" for f in fired)
        gauges["grad_norm_total"] = 50.0
        clock["t"] += 1.0
        fired = col.sample_once()
        assert any(f["rule"] == "grad_norm_spike" for f in fired)
        bundles = glob.glob(str(tmp_path / "flight_*" /
                                "numerics.json"))
        assert bundles, os.listdir(str(tmp_path))
        with open(bundles[0]) as f:
            assert "ops" in json.load(f)


# ---------------------------------------------------------------------------
# /metrics: the health series are scrapeable
# ---------------------------------------------------------------------------

class TestMetricsEndpoint:
    def test_health_series_visible_in_prometheus(self, fresh_programs,
                                                 monkeypatch,
                                                 tmp_path):
        from paddle_tpu.fluid.contrib.mixed_precision import decorate

        monkeypatch.setenv("PADDLE_OBS_NUMERICS", "on")
        main, startup, scope = fresh_programs
        x = fluid.data("x", [-1, 4], "float32")
        x.stop_gradient = True
        pred = fluid.layers.fc(x, 2, bias_attr=False)
        loss = fluid.layers.reduce_mean(pred)
        opt = decorate(fluid.optimizer.Adam(0.1), dtype="float16",
                       init_loss_scaling=8.0)
        opt.minimize(loss)
        exe = fluid.Executor()
        exe.run(startup)
        for _ in range(2):
            exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                    fetch_list=[loss.name])
        handle = obs.start_telemetry(port=0, sample_s=60.0,
                                     flight_dir=str(tmp_path))
        try:
            handle.collector.sample_once()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{handle.port}/metrics",
                    timeout=5) as r:
                body = r.read().decode()
        finally:
            obs.stop_telemetry()
        for name in ("paddle_tpu_grad_norm_total",
                     "paddle_tpu_update_ratio",
                     "paddle_tpu_param_norm_total",
                     "paddle_tpu_loss_scale"):
            assert name in body, f"{name} missing from /metrics"


# ---------------------------------------------------------------------------
# NaN-monitor upgrade: named vars, step context, first-step stat
# ---------------------------------------------------------------------------

class TestNanMonitorUpgrade:
    def test_hit_names_vars_and_records_first_step(self):
        import jax.numpy as jnp

        profiler.stat_reset("nan_inf_first_step")
        mon = _NanMonitor()
        mon.submit(jnp.asarray([False, True]), ["ok_var", "bad_var"],
                   context={"step": 7, "label": "train",
                            "record": None})
        assert _wait_for(lambda: profiler.get_int_stats()
                         .get("nan_inf_first_step"), timeout=5.0) == 7
        with pytest.raises(RuntimeError, match="bad_var.*at step 7"):
            mon.drain()
        hit = numerics.numerics_doc()["last_hit"]
        assert hit["step"] == 7 and "bad_var" in hit["hits"]
        # a second hit does NOT move the first-step latch
        mon.submit(jnp.asarray([True]), ["later_var"],
                   context={"step": 9, "label": "train",
                            "record": None})
        _wait_for(lambda: numerics.numerics_doc()["last_hit"]["step"]
                  == 9, timeout=5.0)
        assert profiler.get_int_stats().get("nan_inf_first_step") == 7
        with pytest.raises(RuntimeError):
            mon.drain()


# ---------------------------------------------------------------------------
# stat table: every written stat is documented
# ---------------------------------------------------------------------------

class TestStatTable:
    def test_every_written_stat_is_documented(self):
        path = os.path.join(REPO_ROOT, "paddle_tpu", "obs",
                            "numerics.py")
        with open(path) as f:
            src = f.read()
        written = set(re.findall(
            r"stat_(?:add|set|max)\(\s*[\"']([a-z0-9_]+)[\"']", src))
        assert written, "no stats written? parser drifted"
        for name in written:
            assert name in (numerics.__doc__ or ""), \
                f"{name} written by obs/numerics.py but missing from " \
                f"its docstring stat table"
