"""Measured device-time profiling tests (ISSUE 12): obs.devprof.

* Wire format: synthetic xplane bytes round-trip through the stdlib
  encoder/parser with units and stat types intact.
* Join: containers excluded from the measured denominator, the tiered
  (exact/order/base) resolution survives runtime thunk renumbering,
  unknown thunks land in an EXPLICIT unattributed bin, nested run
  markers dedup and pair with dispatches by order, and the device
  clock rebases onto the host timeline.
* End-to-end (acceptance): a profiled window over the transformed toy
  ResNet block attributes >=80% of measured device time to source
  Program ops, and `obs.export_trace` emits >=1 device track
  flow-linked from the `executor.dispatch` span — asserted against the
  real jax.profiler capture under JAX_PLATFORMS=cpu.
* The PR-7 orphaned-flow suppression still holds with device events
  merged in.
"""

import os
import sys

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.obs import devprof, opprof
from paddle_tpu.obs.tracing import Tracer

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
import tracetool  # noqa: E402


@pytest.fixture(autouse=True)
def _restore_flag():
    yield
    paddle_tpu.set_flags({"FLAGS_graph_transforms": "on"})


def _resnet_block_program():
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


# ---------------------------------------------------------------------------
# wire format (no jax touched)
# ---------------------------------------------------------------------------

class TestWireFormat:
    def test_roundtrip_preserves_events_and_stat_types(self):
        planes = [{"name": "/device:X", "lines": [
            {"name": "thunks", "timestamp_ns": 12345, "events": [
                {"name": "dot.4", "offset_ps": 1_000_000,
                 "duration_ps": 2_000_000,
                 "stats": {"program_id": 9, "occupancy": 0.25,
                           "hlo_op": "dot.4"}},
            ]},
        ]}]
        space = devprof.parse_xplane_bytes(devprof.encode_xspace(planes))
        assert len(space["planes"]) == 1
        line = space["planes"][0]["lines"][0]
        assert line["name"] == "thunks"
        assert line["timestamp_ns"] == 12345
        ev = line["events"][0]
        assert ev["name"] == "dot.4"
        assert ev["offset_ps"] == 1_000_000
        assert ev["duration_ps"] == 2_000_000
        assert ev["stats"] == {"program_id": 9, "occupancy": 0.25,
                               "hlo_op": "dot.4"}

    def test_parse_dir_walks_profile_session_layout(self, tmp_path):
        d = tmp_path / "plugins" / "profile" / "2026_08_05"
        d.mkdir(parents=True)
        planes = [{"name": "p", "lines": [
            {"name": "l", "timestamp_ns": 1, "events": [
                {"name": "e", "offset_ps": 0, "duration_ps": 1,
                 "stats": {}}]}]}]
        (d / "host.xplane.pb").write_bytes(
            devprof.encode_xspace(planes))
        space = devprof.parse_xplane_dir(str(tmp_path))
        assert space["files"] == 1
        assert space["planes"][0]["lines"][0]["events"][0]["name"] == "e"

    def test_garbage_bytes_raise_cleanly(self):
        with pytest.raises(ValueError):
            devprof.parse_xplane_bytes(b"\x07\x01garbage")


# ---------------------------------------------------------------------------
# join on synthetic planes
# ---------------------------------------------------------------------------

def _selftest_profile():
    return opprof.profile_hlo_text(
        tracetool._SELFTEST_HLO, label="synthetic",
        cost={"flops": 2.0 * 64 * 64 * 128, "bytes_accessed": 1e4})


def _synthetic_space():
    """One host line (nested run markers x2 runs) + one thunk line with
    renumbered leaves + one unmatched line that must be skipped."""
    return {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python", "timestamp_ns": 1000, "events": [
            {"name": devprof.RUN_MARKER, "offset_ps": 0,
             "duration_ps": 5_000_000, "stats": {}},
            {"name": devprof.RUN_MARKER, "offset_ps": 50_000,
             "duration_ps": 4_000_000, "stats": {}},
            {"name": devprof.RUN_MARKER, "offset_ps": 10_000_000,
             "duration_ps": 5_000_000, "stats": {}},
        ]},
        {"name": "tf_XLATfrtCpuClient/3", "timestamp_ns": 1000,
         "events": [
             {"name": "ThunkExecutor::Execute (wait for completion)",
              "offset_ps": 0, "duration_ps": 9_000_000, "stats": {}},
             {"name": "dot.10", "offset_ps": 200_000,
              "duration_ps": 4_000_000, "stats": {"program_id": 7}},
             {"name": "relu_fusion", "offset_ps": 4_400_000,
              "duration_ps": 3_000_000, "stats": {"program_id": 7}},
             {"name": "all-reduce.3", "offset_ps": 7_600_000,
              "duration_ps": 2_000_000, "stats": {"program_id": 7}},
             {"name": "custom-call.9", "offset_ps": 9_800_000,
              "duration_ps": 1_000_000, "stats": {"program_id": 7}},
         ]},
        {"name": "unrelated-daemon", "timestamp_ns": 1000, "events": [
            {"name": "Sleep", "offset_ps": 0, "duration_ps": 50_000_000,
             "stats": {}}]},
    ]}]}


class TestJoin:
    def test_join_tiers_and_explicit_unattributed(self):
        profiles = {"synthetic": _selftest_profile()}
        disp = [(1, "synthetic", 10.0), (2, "synthetic", 10.001)]
        join = devprof.join_events(_synthetic_space(), profiles, disp)
        # containers and the skipped daemon line never enter the
        # measured denominator
        assert join["measured_ns"] == 10_000.0
        assert [s["line"] for s in join["skipped_lines"]] \
            == ["/host:CPU/unrelated-daemon"]
        ops = join["ops"]
        # renumbered dot.10 aligns to dot.4 by suffix rank (order tier)
        assert ops["program#7/block0/op1:mul"]["time_ns"] == 4_000.0
        assert ops["program#7/block0/op1:mul"]["match"] == "order"
        # unchanged name resolves exactly
        relu = ops["program#7/block0/op2:relu[pass=layout_optimize]"]
        assert relu["match"] == "exact"
        # the unknown thunk is binned EXPLICITLY, never silently spread
        assert ops[devprof.UNATTRIBUTED]["time_ns"] == 1_000.0
        assert ops[devprof.UNATTRIBUTED]["match"] == "none"
        assert join["attributed_pct"] == pytest.approx(90.0)

    def test_run_dedup_order_pairing_and_rebase(self):
        profiles = {"synthetic": _selftest_profile()}
        disp = [(5, "synthetic", 20.0), (6, "synthetic", 20.001)]
        join = devprof.join_events(_synthetic_space(), profiles, disp)
        # 3 raw markers -> 2 runs (the nested duplicate collapses), and
        # the i-th run pairs with the i-th dispatch BY ORDER (the
        # xplane epoch differs from perf_counter's)
        assert join["runs"] == 2
        assert join["run_seqs"] == [5, 6]
        # rebase anchors the first marker at its dispatch timestamp
        markers = [t for t in join["trace_events"]
                   if t["name"] == devprof.RUN_MARKER]
        assert markers[0]["ts_ns"] == pytest.approx(20.0 * 1e9)

    def test_roofline_bounds(self):
        profiles = {"synthetic": _selftest_profile()}
        join = devprof.join_events(_synthetic_space(), profiles,
                                   [(1, "synthetic", 1.0)])
        roof = devprof.compute_roofline(join, profiles, "cpu-fallback",
                                        pf=2e11, pb=5e10)
        rops = {r["op"]: r for r in roof["ops"]}
        dot = rops["program#7/block0/op1:mul"]
        assert dot["bound"] == "compute-bound" and dot["mfu_pct"] > 0
        assert rops[devprof.UNATTRIBUTED]["bound"] == devprof.UNATTRIBUTED
        assert "layout_optimize" in rops[
            "program#7/block0/op2:relu[pass=layout_optimize]"]["passes"]
        # shares sum to ~100 over the measured denominator
        assert sum(r["share_pct"] for r in roof["ops"]) \
            == pytest.approx(100.0, abs=0.1)

    def test_env_knob_parsing(self, monkeypatch):
        for raw, want in (("", None), ("0", None), ("off", None),
                          ("false", None), ("1", 3), ("on", 3),
                          ("true", 3), ("7", 7)):
            monkeypatch.setenv("PADDLE_OBS_DEVPROF", raw)
            assert devprof.devprof_env_steps() == want, raw


# ---------------------------------------------------------------------------
# end-to-end: real capture under JAX_PLATFORMS=cpu (acceptance)
# ---------------------------------------------------------------------------

class TestDevprofEndToEnd:
    def _capture(self, label, runs=3):
        main, startup, out = _resnet_block_program()
        infer = main.clone(for_test=True)
        paddle_tpu.set_flags(
            {"FLAGS_graph_transforms": "on,fold_bn=on"})
        feed = {"image": np.random.RandomState(0).randn(
            2, 3, 16, 16).astype("float32")}
        obs.enable(reset=True)
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            # compile (cache miss) OUTSIDE the window: the capture
            # holds steady-state dispatches only
            exe.run(infer, feed=feed, fetch_list=[out.name])
            with obs.profile_window(label=label):
                for _ in range(runs):
                    exe.run(infer, feed=feed, fetch_list=[out.name])
        res = devprof.last_result()
        assert res is not None and res.get("error") is None, \
            f"capture failed: {res and res.get('error')}"
        return infer, res

    def test_window_attributes_measured_device_time(self):
        infer, res = self._capture("e2e.attribution")
        # ACCEPTANCE: >=80% of measured device time resolves to source
        # Program ops of the transformed toy ResNet
        assert res["attributed_pct"] >= 80.0, res["ops"].keys()
        assert res["measured_ms"] > 0.0 and res["events"] > 0
        # any remainder is binned explicitly, never silently dropped
        if res["attributed_pct"] < 100.0:
            assert devprof.UNATTRIBUTED in res["ops"]
        # time landed on ops of THIS program, tagged with their passes
        assert infer.prog_id in res["prog_ids"]
        roof = res["roofline"]
        assert roof["ops"] and all(
            r["bound"] in ("compute-bound", "memory-bound",
                           "relayout-bound", "unknown",
                           devprof.UNATTRIBUTED)
            for r in roof["ops"])
        assert any(r["passes"] for r in roof["ops"])
        # every window dispatch was logged and runs were seen
        assert len(res["dispatches"]) == 3 and res["runs"] >= 1
        # the capture published its gauges for telemetry/bench_diff
        from paddle_tpu import profiler
        assert profiler.get_int_stats().get(
            "devprof_attributed_pct") == int(round(res["attributed_pct"]))
        assert obs.snapshot()["devprof"]["windows"]

    def test_export_trace_device_tracks_and_flow_links(self, tmp_path):
        self._capture("e2e.trace")
        path = str(tmp_path / "unified.trace.json")
        obs.export_trace(path)
        doc = tracetool.load_trace(path)
        evs = doc["traceEvents"]
        # ACCEPTANCE: >=1 device track, flow-linked from the host
        # executor.dispatch span
        dev_tracks = {e["tid"]: e["args"]["name"] for e in evs
                      if e.get("ph") == "M"
                      and str(e.get("args", {}).get("name", "")
                              ).startswith("device:")}
        assert dev_tracks, "no device track in the unified trace"
        s_evs = [e for e in evs if e.get("ph") == "s"
                 and str(e.get("id", "")).startswith("devprof:")]
        f_evs = {e["id"]: e for e in evs if e.get("ph") == "f"
                 and str(e.get("id", "")).startswith("devprof:")}
        assert s_evs and all(e["id"] in f_evs for e in s_evs)
        # every arrow starts ON the dispatch span's thread and ends on
        # a device track
        disp_tids = {e["tid"] for e in evs if e.get("ph") == "X"
                     and (e.get("args") or {}).get("devprof_seq")
                     is not None and e.get("cat") != "devprof"}
        assert disp_tids
        for s in s_evs:
            assert s["tid"] in disp_tids
            assert f_evs[s["id"]]["tid"] in dev_tracks
            assert f_evs[s["id"]]["bp"] == "e"
        assert doc["otherData"]["devprof"]["flows_linked"] >= 1
        # tracetool consumes the same file: device tracks are threads,
        # and the embedded snapshot yields the roofline table
        s = tracetool.summarize(doc)
        assert any(str(t["name"]).startswith("device:")
                   for t in s["threads"])
        roofs = tracetool.find_rooflines(path)
        assert roofs
        assert tracetool.roofline_cmd(path, 5, False) == 0

    def test_obs_roofline_api_matches_program(self):
        infer, res = self._capture("e2e.roofline")
        roof = obs.roofline(infer)
        assert roof is not None
        assert roof["attributed_pct"] == pytest.approx(
            res["attributed_pct"], abs=1e-6)
        assert obs.roofline(label="e2e.roofline") is not None
        assert obs.roofline(label="no-such-window") is None


# ---------------------------------------------------------------------------
# orphaned-flow suppression (PR 7) survives the device merge
# ---------------------------------------------------------------------------

class TestOrphansWithDeviceEvents:
    def test_orphan_still_suppressed_and_devprof_flows_intact(self):
        tr = Tracer(capacity=2)
        tr.enable()
        good = tr.new_flow()
        with tr.span("keep.a", flow=good):
            pass
        with tr.span("executor.dispatch", flow=good) as sp:
            sp.set_attr("devprof_seq", 41)
        orphan = tr.new_flow()
        with tr.span("lost.start", flow=orphan):
            pass
        assert tr.dropped == 1
        tr.capacity = 3
        tr.add_span("lost.finish", 0.0, 1e-4, flow=orphan)
        doc = tr.chrome_trace()
        result = {"label": "t", "attributed_pct": 100.0,
                  "trace_events": [
                      {"name": devprof.RUN_MARKER, "ts_ns": 1e9,
                       "dur_ns": 1e6, "track": "dev", "container": True,
                       "seq": 41},
                      {"name": "dot.1", "ts_ns": 1e9, "dur_ns": 5e5,
                       "track": "dev", "op": "program#1/block0/op0:mul",
                       "container": False},
                  ]}
        devprof.merge_chrome_trace(doc, result)
        flow_ids = {e["id"] for e in doc["traceEvents"]
                    if e.get("cat") == "flow"}
        assert good in flow_ids          # host flow intact
        assert orphan not in flow_ids    # PR-7 suppression holds
        assert "devprof:41" in flow_ids  # device arrow drawn
        assert doc["otherData"]["orphaned_flows"] == 1
        assert doc["otherData"]["devprof"]["flows_linked"] == 1

