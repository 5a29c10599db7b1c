"""Measured device time under the program's own names: obs.devprof.

* Names in the HLO: `Layer.__call__` scopes and the flash kernels'
  names, seen in lowered text.
* `devprof.device_time` on a recorded v5e trace (tests/data/devprof):
  the table sums to the op time, the named share, the flash kernels'
  time equal to their events'.
* `profiler.stage`: `pt.executor.*` annotations in a `jax.profiler`
  trace nobody but JAX opened, the timers advancing as before, and
  nothing left behind outside a session.
* The CPU backend's thunk join: containers excluded from the measured
  denominator, unknown thunks land in an EXPLICIT unattributed bin.
* End-to-end (acceptance): a profiled window over the transformed toy
  ResNet block attributes >=80% of measured device time to source
  Program ops — asserted against the real jax.profiler capture under
  JAX_PLATFORMS=cpu.
"""

import gzip
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.fluid as fluid
from paddle_tpu import obs
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu import profiler
from paddle_tpu.obs import devprof, opprof

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
# names in the HLO (trace time only; nothing here runs a step)
# ---------------------------------------------------------------------------

class TestNamesInHlo:
    def test_layer_call_scopes_in_lowered_text(self):
        from paddle_tpu import nn
        from paddle_tpu.jit import functional_call, functional_state

        class Block(nn.Layer):
            def __init__(self):
                super().__init__()
                self.layers = nn.LayerList([
                    nn.TransformerEncoderLayer(16, 2, 32, dropout=0.0)
                    for _ in range(2)])

            def forward(self, x):
                for layer in self.layers:
                    x = layer(x)
                return x

        paddle_tpu.seed(0)
        model = Block()
        params = functional_state(model)

        def loss(p, x):
            out, _ = functional_call(model, p, x)
            return out.sum()

        text = jax.jit(jax.grad(loss)).lower(
            params, jnp.ones((2, 8, 16), jnp.float32)).as_text(
                debug_info=True)
        names = {opprof.scope_name(n)
                 for n in re.findall(r'loc\("([^"]+)"', text)}
        paths = {(phase, path) for phase, path in filter(None, names)}
        # a root layer is its lower-cased class name; a LayerList's
        # children carry the list's name and their index
        for want in ("block/layers/0/self_attn/q_proj",
                     "block/layers/1/self_attn/out_proj",
                     "block/layers/1/ffn", "block/layers/0/norm1"):
            assert ("fwd", want) in paths, want
            assert ("bwd", want) in paths, want

    @pytest.mark.parametrize("registered, want", [
        ("attribute", "child"), ("add_sublayer", "named"),
        ("list", "items/1"), ("list_appended_later", "items/2"),
        ("sequential", "1"), ("root", "linear")])
    def test_scope_name_is_the_registered_name(self, registered, want):
        from paddle_tpu import nn

        leaf = nn.Linear(2, 2)
        parent = nn.Layer()
        if registered == "attribute":
            parent.child = leaf
        elif registered == "add_sublayer":
            parent.add_sublayer("named", leaf)
        elif registered == "list":
            parent.items = nn.LayerList([nn.Linear(2, 2), leaf])
        elif registered == "list_appended_later":
            parent.items = nn.LayerList([nn.Linear(2, 2), nn.Linear(2, 2)])
            parent.items.append(leaf)
        elif registered == "sequential":
            parent.seq = nn.Sequential(nn.Linear(2, 2), leaf)
        from paddle_tpu.nn.layer.layers import Tensor

        text = jax.jit(lambda x: leaf(Tensor(x))._value).lower(
            jnp.ones((1, 2))).as_text(debug_info=True)
        assert f'/{want}/dot_general"' in text

    def test_flash_kernels_are_named(self):
        from paddle_tpu.ops.pallas import attention

        q = jnp.zeros((2, 128, 2, 64), jnp.bfloat16)

        def loss(q, k, v):
            return attention.flash_attention(
                q, k, v, interpret=True).astype(jnp.float32).sum()

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).as_text(debug_info=True)
        for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
            assert re.search(rf'[/"]{name}/', text), name
        # the names the accepted benchmark matches stay
        assert "_flash_forward" in text and "_flash_backward" in text


# ---------------------------------------------------------------------------
# the measured join on a recorded v5e trace (tests/data/devprof/record.py)
# ---------------------------------------------------------------------------

DATA = os.path.join(REPO_ROOT, "tests", "data", "devprof")
STEPS = 4          # record.py's window


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("devprof") / "toy_bert.xplane.pb")
    with gzip.open(os.path.join(DATA, "toy_bert.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        dst.write(src.read())
    with gzip.open(os.path.join(DATA, "toy_bert.hlo.txt.gz"), "rt") as f:
        text = f.read()
    return path, text


class _Executable:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        return self._text


class TestDeviceTimeOnChipTrace:
    def _events(self, path, window="window"):
        trace = devprof.read_trace(path)
        (ops,) = [d["ops"] for d in trace["devices"].values()]
        lo, hi = [s[1:] for s in trace["host"] if s[0] == window][-1]
        return [(n, s, e) for n, s, e in ops if s >= lo and e <= hi], lo, hi

    def test_table_sums_to_the_op_time(self, recorded):
        path, text = recorded
        table = devprof.device_time(path, [_Executable(text)],
                                    window_ns="window")
        events, lo, hi = self._events(path)
        assert table["chips"] == 1 and table["window_ns"] == (lo, hi)
        # no event of this trace nests in another: the op time is the
        # plain sum of the durations
        total = sum(e - s for _, s, e in events) / 1e9
        assert table["op_s"] == pytest.approx(total, rel=1e-9)
        assert (sum(table["by_name"].values())
                + sum(table["unattributed"].values())
                == pytest.approx(table["op_s"], rel=1e-9))
        assert table["programs"] == {"jit_step": 0}
        busy = sum(e - s for s, e in table["busy"]["/device:TPU:0"]) / 1e9
        assert busy == pytest.approx(total, rel=1e-9)

    def test_named_share_and_phases(self, recorded):
        path, text = recorded
        table = devprof.device_time(path, {"step": _Executable(text)},
                                    window_ns="window")
        named = sum(table["by_name"].values())
        assert named / table["op_s"] >= 0.85
        by_phase = {}
        for (phase, _), s in table["by_name"].items():
            by_phase[phase] = by_phase.get(phase, 0.0) + s
        assert set(by_phase) == {"fwd", "bwd", "optimizer", "loss"}
        assert by_phase["bwd"] > by_phase["fwd"] > by_phase["loss"] > 0
        # what is left is listed by instruction family, never dropped
        assert all(re.fullmatch(r"[\w\-]+", k)
                   for k in table["unattributed"])

    def test_flash_time_is_the_kernel_events(self, recorded):
        path, text = recorded
        table = devprof.device_time(path, [_Executable(text)],
                                    window_ns="window")
        # independently: the Mosaic calls of the text, by their op_name
        kernels = {}
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            name = re.match(r"\s*%?([\w.\-]+)\s*=", line).group(1)
            kernels[name] = re.search(
                r"/(flash_\w+)/pallas_call", line).group(1)
        assert sorted(set(kernels.values())) == [
            "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]
        events, _, _ = self._events(path)
        want = {}
        for n, s, e in events:
            if n in kernels:
                want[kernels[n]] = want.get(kernels[n], 0.0) + (e - s) / 1e9
        got = {}
        for (phase, path_), s in table["by_name"].items():
            leaf = path_.rsplit("/", 1)[-1]
            if leaf in want:
                assert "/self_attn/" in path_
                assert phase == ("fwd" if leaf == "flash_fwd" else "bwd")
                got[leaf] = got.get(leaf, 0.0) + s
        assert got == pytest.approx(want, rel=1e-9)
        # 3 kernels x 2 layers a step; the device's clock runs ~1.3 ms
        # ahead of the host's in this trace, so the host's window holds
        # three of the four steps dispatched inside it
        assert sum(1 for n, _, _ in events if n in kernels) == 3 * 2 * 3

    def test_window_and_annotations(self, recorded):
        path, text = recorded
        whole = devprof.device_time(path, [_Executable(text)])
        table = devprof.device_time(path, [_Executable(text)],
                                    window_ns="window")
        # the lead-in step lies outside the window
        assert whole["op_s"] > table["op_s"] * (STEPS + 0.5) / STEPS
        lo, hi = table["window_ns"]
        half = devprof.device_time(path, [_Executable(text)],
                                   window_ns=(lo, (lo + hi) / 2))
        assert 0 < half["op_s"] < table["op_s"]
        spans = table["host_spans"]
        assert len(spans["pt.executor.dispatch"]) == STEPS
        assert len(spans["pt.executor.sync"]) == 1

    def test_no_executable_everything_unattributed(self, recorded):
        path, _ = recorded
        table = devprof.device_time(path, {}, window_ns="window")
        assert table["by_name"] == {}
        assert sum(table["unattributed"].values()) == pytest.approx(
            table["op_s"])
        assert table["programs"] == {"jit_step": None}


# ---------------------------------------------------------------------------
# profiler.stage: the Executor's stages in the profiler's own trace
# ---------------------------------------------------------------------------

def _fc_program():
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [4, 8], "float32")
        out = fluid.layers.reduce_mean(fluid.layers.fc(x, 4))
    return main, startup, out


class TestStage:
    def test_executor_stages_in_a_plain_jax_profiler_trace(self, tmp_path):
        main, startup, out = _fc_program()
        feed = {"x": np.ones((4, 8), "float32")}
        with scope_guard(Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[out.name])   # compile
            before = profiler.get_time_stats()
            syncs = profiler.get_int_stats().get("executor_sync_count", 0)
            # nobody but JAX opens this trace: no paddle_tpu.profiler
            jax.profiler.start_trace(str(tmp_path))
            try:
                for _ in range(3):
                    exe.run(main, feed=feed, fetch_list=[out.name])
            finally:
                jax.profiler.stop_trace()
        after = profiler.get_time_stats()
        for timer in ("host_feed_ms", "dispatch_ms", "sync_ms"):
            assert after[timer] > before.get(timer, 0.0), timer
        assert after.get("compile_ms") == before.get("compile_ms")
        assert profiler.get_int_stats()["executor_sync_count"] == syncs + 3
        host = devprof.read_trace(devprof.find_xplane(str(tmp_path)))["host"]
        counts = {}
        for name, _, _ in host:
            if name.startswith(profiler.ANNOTATION_PREFIX):
                counts[name] = counts.get(name, 0) + 1
        assert counts == {"pt.executor.feed": 3, "pt.executor.dispatch": 3,
                          "pt.executor.sync": 3}
        # a CPU trace has no device plane: no table comes of it
        assert devprof.device_time(devprof.find_xplane(str(tmp_path)),
                                   opprof.profiles()) is None

    def test_first_dispatch_is_booked_as_compile(self):
        main, startup, out = _fc_program()
        with scope_guard(Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            before = profiler.get_time_stats()
            exe.run(main, feed={"x": np.ones((4, 8), "float32")},
                    fetch_list=[out.name])
        after = profiler.get_time_stats()
        assert after["compile_ms"] > before.get("compile_ms", 0.0)

    def test_outside_a_session_nothing_is_left_behind(self):
        obs.disable()
        obs.reset()
        syncs = profiler.get_int_stats().get("executor_sync_count", 0)
        before = profiler.get_time_stats().get("host_feed_ms", 0.0)
        with profiler.stage("executor.feed", "host_feed_ms"):
            pass
        with profiler.stage("executor.idle"):       # no timer
            pass
        assert len(obs.TRACER) == 0
        assert profiler.get_int_stats().get(
            "executor_sync_count", 0) == syncs
        assert profiler.get_time_stats()["host_feed_ms"] >= before
        assert "executor.idle" not in profiler.get_time_stats()

    def test_stage_records_a_span_when_tracing_is_on(self):
        obs.enable(reset=True)
        try:
            with profiler.stage("executor.feed", "host_feed_ms"):
                pass
            assert [r[0] for r in obs.TRACER.records()] == ["executor.feed"]
        finally:
            obs.disable()
            obs.reset()


# ---------------------------------------------------------------------------
# join on synthetic planes
# ---------------------------------------------------------------------------

def _selftest_profile():
    return opprof.profile_hlo_text(
        tracetool._SELFTEST_HLO, label="synthetic",
        cost={"flops": 2.0 * 64 * 64 * 128, "bytes_accessed": 1e4})


def _synthetic_space():
    """One thunk line + one unmatched line that must be skipped."""
    return {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "tf_XLATfrtCpuClient/3", "events": [
             {"name": "ThunkExecutor::Execute (wait for completion)",
              "offset_ps": 0, "duration_ps": 9_000_000, "stats": {}},
             {"name": "dot.4", "offset_ps": 200_000,
              "duration_ps": 4_000_000, "stats": {"program_id": 7}},
             {"name": "relu_fusion", "offset_ps": 4_400_000,
              "duration_ps": 3_000_000, "stats": {"program_id": 7}},
             {"name": "all-reduce", "offset_ps": 7_600_000,
              "duration_ps": 2_000_000, "stats": {"program_id": 7}},
             {"name": "custom-call.9", "offset_ps": 9_800_000,
              "duration_ps": 1_000_000, "stats": {"program_id": 7}},
         ]},
        {"name": "unrelated-daemon", "events": [
            {"name": "Sleep", "offset_ps": 0, "duration_ps": 50_000_000,
             "stats": {}}]},
    ]}]}


class TestJoin:
    def test_join_tiers_and_explicit_unattributed(self):
        profiles = {"synthetic": _selftest_profile()}
        join = devprof.join_events(_synthetic_space(), profiles, runs=2)
        assert join["runs"] == 2
        # containers and the skipped daemon line never enter the
        # measured denominator
        assert join["measured_ns"] == 10_000.0
        assert [s["line"] for s in join["skipped_lines"]] \
            == ["/host:CPU/unrelated-daemon"]
        ops = join["ops"]
        # an event is named by its instruction: the join is exact
        assert ops["program#7/block0/op1:mul"]["time_ns"] == 4_000.0
        assert ops["program#7/block0/op2:relu[pass=layout_optimize]"][
            "time_ns"] == 3_000.0
        # the unknown thunk is binned EXPLICITLY, never silently spread
        assert ops[devprof.UNATTRIBUTED]["time_ns"] == 1_000.0
        assert join["attributed_pct"] == pytest.approx(90.0)

    def test_roofline_bounds(self):
        profiles = {"synthetic": _selftest_profile()}
        join = devprof.join_events(_synthetic_space(), profiles)
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

    def test_obs_roofline_api_matches_program(self):
        infer, res = self._capture("e2e.roofline")
        roof = obs.roofline(infer)
        assert roof is not None
        assert roof["attributed_pct"] == pytest.approx(
            res["attributed_pct"], abs=1e-6)
        assert obs.roofline(label="e2e.roofline") is not None
        assert obs.roofline(label="no-such-window") is None
