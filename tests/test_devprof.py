"""Measured device time under the program's own names: obs.devprof.

* Names in the HLO: `Layer.__call__` scopes and the flash kernels'
  names, seen in lowered text.
* `devprof.device_time` on a recorded v5e trace (tests/data/devprof):
  the table sums to the op time, the named share, the flash kernels'
  time equal to their events'.
* `devprof.device_time` on synthetic traces, for what the one-chip
  recording does not hold: several chips, nested events, clipping at
  the window's edge, several executables, a window named by a host
  span, unmatched instructions.
* `profiler.stage`: `pt.executor.*` annotations in a `jax.profiler`
  trace nobody but JAX opened, the timers advancing as before, and
  nothing left behind outside a session.
"""

import gzip
import os
import re

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
# device_time on synthetic traces (`read_trace`'s documented shape)
# ---------------------------------------------------------------------------

PLANE = "/device:TPU:0"


def _plane(ops, modules=(("jit_step", 0, 1000),)):
    return {"ops": list(ops), "modules": list(modules)}


def _exe(instr_name, module="jit_step"):
    """An opprof profile as `device_time` reads it."""
    return {"module": module, "instr_name": instr_name}


def _ns(n):
    return n / 1e9


# case -> (devices, host events, executables, window_ns, expected fields)
SYNTHETIC = {
    # each chip's time, then the mean over chips
    "two_chips_mean": (
        {PLANE: _plane([("fusion.1", 0, 100)]),
         "/device:TPU:1": _plane([("fusion.1", 0, 300)])},
        [], {"step": _exe({"fusion.1": ("fwd", "mlp")})}, None,
        {"chips": 2, "window_ns": (0, 300), "op_s": _ns(200),
         "by_name": {("fwd", "mlp"): _ns(200)}, "unattributed": {}}),
    # a while loop's own time leaves out the body ops nested in it
    "while_excludes_its_body": (
        {PLANE: _plane([("while.1", 0, 100), ("fusion.2", 10, 30),
                        ("fusion.3", 50, 80)])},
        [], {"step": _exe({"while.1": ("fwd", "loop"),
                           "fusion.2": ("fwd", "loop/a"),
                           "fusion.3": ("fwd", "loop/b")})}, None,
        {"chips": 1, "op_s": _ns(100),
         "by_name": {("fwd", "loop"): _ns(50), ("fwd", "loop/a"): _ns(20),
                     ("fwd", "loop/b"): _ns(30)},
         "busy": {PLANE: [(0, 100)]}}),
    # ops across the window's edges are clipped to it; ops outside drop
    "edge_ops_are_clipped": (
        {PLANE: _plane([("fusion.1", 0, 100), ("fusion.2", 120, 160),
                        ("fusion.3", 190, 260), ("fusion.4", 300, 350)])},
        [], {"step": _exe({"fusion.1": ("fwd", "a"),
                           "fusion.2": ("fwd", "b"),
                           "fusion.3": ("fwd", "c"),
                           "fusion.4": ("fwd", "d")})}, (50, 200),
        {"window_ns": (50, 200), "op_s": _ns(100),
         "by_name": {("fwd", "a"): _ns(50), ("fwd", "b"): _ns(40),
                     ("fwd", "c"): _ns(10)},
         "busy": {PLANE: [(50, 100), (120, 160), (190, 200)]}}),
    # one instruction name in two executables: each event is joined
    # through the map of the module that ran it
    "modules_join_their_own_map": (
        {PLANE: _plane([("fusion.1", 10, 50), ("fusion.1", 110, 170)],
                       modules=[("jit_a", 0, 100), ("jit_b", 100, 200)])},
        [], {"A": _exe({"fusion.1": ("fwd", "a")}, module="jit_a"),
             "B": _exe({"fusion.1": ("fwd", "b")}, module="jit_b")}, None,
        {"by_name": {("fwd", "a"): _ns(40), ("fwd", "b"): _ns(60)},
         "programs": {"jit_a": "A", "jit_b": "B"}}),
    # a window named by a host span is that span's LAST occurrence
    "window_is_the_last_host_span": (
        {PLANE: _plane([("fusion.1", 50, 60), ("fusion.2", 250, 270)])},
        [("pt.window", 0, 100), ("pt.window", 200, 300),
         ("python", 210, 220)],
        {"step": _exe({"fusion.1": ("fwd", "early"),
                       "fusion.2": ("fwd", "late")})}, "pt.window",
        {"window_ns": (200, 300), "op_s": _ns(20),
         "by_name": {("fwd", "late"): _ns(20)},
         "host_spans": {"pt.window": [(0, 100), (200, 300)]}}),
    # what no map names lands in `unattributed` by instruction family
    "unmatched_by_family": (
        {PLANE: _plane([("fusion.1400", 0, 40), ("custom-call.7", 40, 50),
                        ("dot.2", 50, 80)])},
        [], {"step": _exe({"custom-call.7": opprof.UNATTRIBUTED,
                           "dot.2": ("bwd", "proj")})}, None,
        {"op_s": _ns(80), "by_name": {("bwd", "proj"): _ns(30)},
         "unattributed": {"fusion": _ns(40), "custom-call": _ns(10)}}),
}


class TestDeviceTimeOnSyntheticTraces:
    @pytest.mark.parametrize("case", sorted(SYNTHETIC))
    def test_device_time(self, case, monkeypatch):
        devices, host, executables, window_ns, want = SYNTHETIC[case]
        trace = {"devices": devices,
                 "host": sorted(host, key=lambda s: s[1])}
        monkeypatch.setattr(devprof, "read_trace", lambda path: trace)
        table = devprof.device_time("synthetic.xplane.pb", executables,
                                    window_ns=window_ns)
        assert (sum(table["by_name"].values())
                + sum(table["unattributed"].values())
                == pytest.approx(table["op_s"], rel=1e-9))
        for key, value in want.items():
            if key in ("op_s", "by_name", "unattributed"):
                assert table[key] == pytest.approx(value, rel=1e-9), key
            else:
                assert table[key] == value, key


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
