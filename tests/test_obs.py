"""paddle_tpu.obs (ISSUE 6): span tracing, flow links, cost gauges.

Covers the tentpole's acceptance criteria: a combined 3-step-train +
serving-request trace shows flow-linked spans across >= 3 threads, the
live mfu_pct gauge derives from cached XLA cost_analysis on CPU, and
disabled-mode tracing leaves the hot-path counters untouched.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import obs, profiler
from paddle_tpu.fluid import framework
from paddle_tpu.fluid.executor import Scope, scope_guard
from paddle_tpu.obs.tracing import NULL_SPAN, Tracer

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))
import tracetool  # noqa: E402


@pytest.fixture
def clean_tracer():
    """Fresh disabled tracer state around each test."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _simple_program():
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup):
        x = fluid.data("x", [-1, 4], "float32")
        y = fluid.layers.fc(x, 2)
        loss = fluid.layers.reduce_mean(y)
    return main, startup, loss


# ---------------------------------------------------------------------------
# span semantics
# ---------------------------------------------------------------------------

class TestSpans:
    def test_disabled_returns_null_span(self, clean_tracer):
        s = obs.span("anything")
        assert s is NULL_SPAN
        with s:
            pass
        assert len(obs.TRACER) == 0
        obs.add_span("retro", 0.0, 1.0)
        assert len(obs.TRACER) == 0

    def test_spans_nest_and_close_under_exceptions(self, clean_tracer):
        obs.enable()
        with pytest.raises(ValueError):
            with obs.span("outer"):
                with obs.span("inner"):
                    raise ValueError("boom")
        recs = obs.TRACER.records()
        names = [r[0] for r in recs]
        assert names == ["inner", "outer"]  # inner closes first
        # nesting: inner lies within outer on the same thread
        (i_name, i_tid, _, i_t0, i_dur, _, _) = recs[0]
        (o_name, o_tid, _, o_t0, o_dur, _, _) = recs[1]
        assert i_tid == o_tid
        assert o_t0 <= i_t0 and i_t0 + i_dur <= o_t0 + o_dur + 1e-9
        # the stack unwound completely
        assert obs.current_span() is None

    def test_leaked_child_closes_with_parent(self, clean_tracer):
        obs.enable()
        with obs.span("parent"):
            # simulate a begin-without-end leak (the span-leak lint
            # flags this shape in product code)
            child = obs.TRACER.span("child")
            child.__enter__()
        assert obs.current_span() is None
        assert [r[0] for r in obs.TRACER.records()] == ["parent"]

    def test_buffer_cap_counts_drops(self, clean_tracer):
        obs.enable()
        old = obs.TRACER.capacity
        obs.TRACER.capacity = 2
        try:
            for _ in range(5):
                with obs.span("e"):
                    pass
            assert len(obs.TRACER) == 2
            assert obs.TRACER.dropped == 3
            assert obs.TRACER.summary()["dropped"] == 3
        finally:
            obs.TRACER.capacity = old

    def test_flow_links_cross_threads(self, clean_tracer, tmp_path):
        obs.enable()
        fid = obs.new_flow()

        def worker():
            with obs.span("consume", flow=fid):
                pass

        with obs.span("produce", flow=fid):
            pass
        t = threading.Thread(target=worker, name="worker-thread")
        t.start()
        t.join()
        path = str(tmp_path / "flow.json")
        obs.export_trace(path)
        doc = json.loads(open(path).read())
        flows = [e for e in doc["traceEvents"] if e.get("cat") == "flow"]
        assert {e["ph"] for e in flows} == {"s", "f"}
        assert len({e["tid"] for e in flows}) == 2
        assert all(e["id"] == fid for e in flows)

    def test_single_span_flow_emits_no_dangling_link(self, clean_tracer):
        obs.enable()
        with obs.span("solo", flow=obs.new_flow()):
            pass
        doc = obs.TRACER.chrome_trace()
        assert not [e for e in doc["traceEvents"]
                    if e.get("cat") == "flow"]

    def test_attrs_exported_as_args(self, clean_tracer, tmp_path):
        obs.enable()
        with obs.span("tagged", attrs={"k": "v"}):
            pass
        doc = obs.TRACER.chrome_trace()
        ev = next(e for e in doc["traceEvents"] if e.get("ph") == "X")
        assert ev["args"] == {"k": "v"}


# ---------------------------------------------------------------------------
# acceptance: one trace, train + serving, >= 3 linked threads, live MFU
# ---------------------------------------------------------------------------

class TestEndToEndTrace:
    def _train_3_steps(self, tmp_path):
        main, startup, loss = _simple_program()
        path = str(tmp_path / "part-0.txt")
        rng = np.random.RandomState(0)
        with open(path, "w") as f:
            for _ in range(12):  # batch 4 -> 3 steps
                f.write("4 " + " ".join(
                    f"{v:.6f}" for v in rng.randn(4)) + "\n")
        scope = Scope()
        with scope_guard(scope):
            exe = fluid.Executor()
            exe.run(startup)
            ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
            ds.set_batch_size(4)
            ds.set_use_var([main.global_block().var("x")])
            ds.set_filelist([path])
            ds.load_into_memory()
            exe.train_from_dataset(main, ds, fetch_list=[loss])

    def _serve_one_request(self):
        import jax.numpy as jnp

        from paddle_tpu import serving

        w = jnp.ones((4, 2), jnp.float32)
        eng = serving.Engine(lambda x: x @ w,
                             serving.EngineConfig(max_queue_delay_ms=0.0))
        try:
            out = eng.infer([np.ones((2, 4), np.float32)], timeout=60)
            np.testing.assert_allclose(out[0], np.full((2, 2), 4.0))
        finally:
            eng.shutdown(drain=True)

    def test_combined_trace_links_three_threads(self, clean_tracer,
                                                tmp_path):
        """Acceptance: ONE Chrome-trace export of a 3-step train run +
        one serving request shows flow-linked spans across >= 3 threads
        (feed producer, serving dispatch, serving completer)."""
        obs.enable(reset=True)
        self._train_3_steps(tmp_path)
        self._serve_one_request()
        obs.disable()
        path = str(tmp_path / "combined.json")
        n = obs.export_trace(path)
        assert n > 0
        s = tracetool.summarize(tracetool.load_trace(path), top=100)
        names = {r["name"] for r in s["top_spans"]}
        # the whole stack is in the one file
        assert {"feed.stage", "feed.ring_get", "executor.prepare",
                "executor.dispatch", "serving.admit", "serving.dispatch",
                "serving.complete"} <= names
        thread_names = {t["name"] for t in s["threads"]}
        assert {"feed-producer", "serving-dispatch",
                "serving-complete"} <= thread_names
        # flow links span >= 3 distinct threads overall
        doc = tracetool.load_trace(path)
        flow_tids = {}
        for e in doc["traceEvents"]:
            if e.get("cat") == "flow":
                flow_tids.setdefault(e["id"], set()).add(e["tid"])
        linked_tids = set()
        for tids in flow_tids.values():
            if len(tids) > 1:
                linked_tids |= tids
        assert len(linked_tids) >= 3, (
            f"flow-linked spans cover only threads {linked_tids}")
        assert s["cross_thread_flows"] >= 4  # 3 feed batches + request

    def test_serving_flow_survives_batcher_handoff(self, clean_tracer):
        """The request's flow id minted at submit() reappears on the
        dispatch- and completer-thread spans."""
        import jax.numpy as jnp

        from paddle_tpu import serving

        obs.enable(reset=True)
        w = jnp.ones((4, 2), jnp.float32)
        eng = serving.Engine(lambda x: x @ w,
                             serving.EngineConfig(max_queue_delay_ms=0.0))
        try:
            eng.infer([np.ones((2, 4), np.float32)], timeout=60)
        finally:
            eng.shutdown(drain=True)
        obs.disable()
        recs = obs.TRACER.records()
        by_name = {}
        for name, _tid, tname, _t0, _dur, flows, _attrs in recs:
            if flows:
                by_name.setdefault(name, set()).update(flows)
        admit = by_name.get("serving.admit", set())
        assert admit, "no flow on the admission span"
        for stage in ("serving.coalesce", "serving.dispatch",
                      "serving.complete"):
            assert admit & by_name.get(stage, set()), (
                f"flow id lost between admit and {stage}")


# ---------------------------------------------------------------------------
# cost attribution
# ---------------------------------------------------------------------------

class TestCostAttribution:
    def test_mfu_gauge_from_cached_cost_analysis(self, clean_tracer):
        """Acceptance: obs.snapshot() reports a nonzero mfu_pct derived
        from the cost_analysis cached with the CompileCache entry —
        on CPU, with tracing never enabled (gauges are always-on)."""
        from paddle_tpu.obs import cost as obs_cost

        obs_cost.reset_programs()
        main, startup, loss = _simple_program()
        with scope_guard(Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            feed = {"x": np.ones((2, 4), "float32")}
            for _ in range(3):
                exe.run(main, feed=feed, fetch_list=[loss])
            # cost is cached WITH the compile-cache entry
            entry = next(e for e in exe._cache.values()
                         if e.fetch_names == [loss.name])
            assert entry.cost is not None
            assert entry.cost.flops > 0
            assert entry.cost.dispatches == 3
        snap = obs.snapshot()
        assert snap["cost"]["device_class"] == "cpu-fallback"
        assert snap["cost"]["mfu_pct"] > 0.0
        prog = next(p for p in snap["cost"]["programs"]
                    if p["label"] == entry.cost.label)
        assert prog["mfu_pct"] > 0.0 and prog["flops"] > 0
        assert prog["step_ms"] > 0.0

    def test_cost_capture_can_be_disabled(self, clean_tracer,
                                          monkeypatch):
        monkeypatch.setenv("PADDLE_OBS_COST", "0")
        main, startup, loss = _simple_program()
        with scope_guard(Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            feed = {"x": np.ones((2, 4), "float32")}
            (out,) = exe.run(main, feed=feed, fetch_list=[loss])
            entry = next(e for e in exe._cache.values()
                         if e.fetch_names == [loss.name])
            assert entry.cost is None and entry.fn_compiled is None
            assert np.isfinite(out).all()

    def test_aot_fallback_on_signature_drift(self, clean_tracer):
        """An AOT executable that rejects its arguments (signature
        drift under the cached entry) must fall back to the jit path —
        permanently — instead of failing the run."""
        main, startup, loss = _simple_program()
        with scope_guard(Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            feed = {"x": np.ones((2, 4), "float32")}
            (want,) = exe.run(main, feed=feed, fetch_list=[loss])
            entry = next(e for e in exe._cache.values()
                         if e.fetch_names == [loss.name])
            assert entry.fn_compiled is not None

            def rejecting(*args):
                raise TypeError("Argument types differ from the types "
                                "for which this computation was compiled")

            entry.fn_compiled = rejecting
            (out,) = exe.run(main, feed=feed, fetch_list=[loss])
            np.testing.assert_allclose(out, want, rtol=1e-6)
            assert entry.fn_compiled is None  # permanent fallback
            (out2,) = exe.run(main, feed=feed, fetch_list=[loss])
            np.testing.assert_allclose(out2, want, rtol=1e-6)

    def test_collective_bytes_on_wire_counter(self, clean_tracer,
                                              fresh_programs):
        """collective_bytes_<type> records the logical payload at
        lowering time — the EQuARX assertion seam."""
        import paddle_tpu.distributed.collective as coll

        profiler.stat_reset("collective_bytes_c_allreduce_sum")
        profiler.stat_reset("collective_count_c_allreduce_sum")
        main, startup, scope = fresh_programs
        x = fluid.data("x", [8, 4], "float32")
        y = coll.all_reduce(x)
        compiled = fluid.CompiledProgram(main).with_data_parallel()
        exe = fluid.Executor()
        X = np.arange(32, dtype="float32").reshape(8, 4)
        exe.run(compiled, feed={"x": X}, fetch_list=[y])
        stats = profiler.get_int_stats()
        # per-shard payload: 8 rows over 8 shards = (1, 4) f32 = 16 B
        assert stats.get("collective_bytes_c_allreduce_sum") == 16
        assert stats.get("collective_count_c_allreduce_sum") == 1
        snap = obs.snapshot()
        assert snap["cost"]["collective_bytes"].get(
            "c_allreduce_sum") == 16
        # cache hit: no re-trace, counter stays flat
        exe.run(compiled, feed={"x": X}, fetch_list=[y])
        assert profiler.get_int_stats()[
            "collective_bytes_c_allreduce_sum"] == 16

    def test_serving_bucket_cost_registered(self, clean_tracer):
        import jax.numpy as jnp

        from paddle_tpu.obs import cost as obs_cost
        from paddle_tpu.serving.bucketing import BucketedRunner

        obs_cost.reset_programs()
        w = jnp.ones((4, 4), jnp.float32)
        runner = BucketedRunner(lambda x: x @ w, buckets=[8])
        for _ in range(2):
            runner.run([np.ones((3, 4), np.float32)])
        labels = {pc.label for pc in obs_cost.programs()}
        assert "serving.bucket8" in labels
        pc = next(p for p in obs_cost.programs()
                  if p.label == "serving.bucket8")
        assert pc.flops > 0 and pc.dispatches == 2


# ---------------------------------------------------------------------------
# disabled-mode overhead: hot-path counters unchanged
# ---------------------------------------------------------------------------

class TestDisabledOverhead:
    def test_disabled_tracing_keeps_sync_counters_flat(self,
                                                       clean_tracer):
        """Acceptance: with tracing disabled, executor_sync_count and
        the per-step dispatch timing counters behave exactly as the
        async hot path promises (zero syncs, dispatch_ms accumulating,
        no span recorded anywhere)."""
        assert not obs.enabled()
        main, startup, loss = _simple_program()
        with scope_guard(Scope()):
            exe = fluid.Executor()
            exe.run(startup)
            feed = {"x": np.ones((2, 4), "float32")}
            exe.run(main, feed=feed, fetch_list=[loss])  # compile step
            profiler.stat_reset("executor_sync_count")
            profiler.time_reset()
            handles = None
            for _ in range(5):
                handles = exe.run(main, feed=feed, fetch_list=[loss],
                                  return_numpy=False)
            # dispatch-only loop performed ZERO device->host transfers
            assert profiler.get_int_stats().get(
                "executor_sync_count", 0) == 0
            times = profiler.get_time_stats()
            assert times.get("dispatch_ms", 0) > 0
            assert times.get("compile_ms", 0.0) == 0.0  # all cache hits
            float(handles[0])  # sync-ok: outside the measured loop
            assert profiler.get_int_stats()["executor_sync_count"] == 1
        assert len(obs.TRACER) == 0  # nothing recorded while disabled


# ---------------------------------------------------------------------------
# snapshot / tracetool round trip
# ---------------------------------------------------------------------------

class TestTracetoolRoundTrip:
    def test_export_summarize_roundtrip(self, clean_tracer, tmp_path):
        obs.enable(reset=True)
        fid = obs.new_flow()
        with obs.span("a", flow=fid):
            time.sleep(0.001)
        t = threading.Thread(
            target=lambda: obs.add_span("b", time.perf_counter(), 1e-4,
                                        flow=fid),
            name="other")
        t.start()
        t.join()
        obs.disable()
        path = str(tmp_path / "rt.json")
        n = obs.export_trace(path)
        assert n == 2
        s = tracetool.summarize(tracetool.load_trace(path))
        assert s["spans"] == 2 and s["cross_thread_flows"] == 1
        assert {r["name"] for r in s["top_spans"]} == {"a", "b"}
        # the embedded snapshot made stall/MFU reporting possible
        assert "stall_attribution" in s
        assert s["device_class"] == "cpu-fallback"

    def test_tracetool_diff(self, clean_tracer, tmp_path):
        tr = Tracer()
        tr.enable()
        tr.add_span("x", 0.0, 0.010)
        a = str(tmp_path / "a.json")
        tr.export(a)
        tr.add_span("x", 1.0, 0.030)
        tr.add_span("y", 1.0, 0.005)
        b = str(tmp_path / "b.json")
        tr.export(b)
        rows = tracetool.diff_traces(tracetool.load_trace(a),
                                     tracetool.load_trace(b))
        byname = {r["name"]: r for r in rows}
        assert byname["x"]["a_count"] == 1 and byname["x"]["b_count"] == 2
        assert byname["x"]["delta_ms"] == pytest.approx(30.0, abs=0.5)
        assert byname["y"]["a_count"] == 0

    def test_tracetool_selftest_clean(self):
        assert tracetool.selftest(verbose=False) == 0

    def test_snapshot_shape(self, clean_tracer):
        snap = obs.snapshot()
        assert set(snap) == {"spans", "counters", "timers_ms", "cost",
                             "host", "op_profile", "devprof", "memory",
                             "numerics"}
        assert {"device_class", "peak_flops", "mfu_pct",
                "programs", "collective_bytes"} <= set(snap["cost"])
        assert snap["host"] == 0  # tagged with jax.process_index()
        assert "orphaned_flows" in snap["spans"]


# ---------------------------------------------------------------------------
# span-leak lint rule
# ---------------------------------------------------------------------------

class TestSpanLeakRule:
    def _lint(self):
        import tpulint

        return tpulint.load_lint()

    def test_flags_unclosed_span(self, tmp_path):
        lint = self._lint()
        bad = tmp_path / "paddle_tpu" / "obs"
        bad.mkdir(parents=True)
        (bad / "leaky.py").write_text(
            "def f(obs):\n"
            "    s = obs.span('x')\n"          # leak: assigned
            "    s.__enter__()\n"
            "    with obs.span('ok'):\n"       # closed
            "        pass\n"
            "    return obs.span('deleg')\n")  # delegation: allowed
        # the other watched paths must exist for the rule to walk
        for rel in ("paddle_tpu/profiler", "paddle_tpu/serving",
                    "paddle_tpu/transforms", "paddle_tpu/ckpt"):
            (tmp_path / rel).mkdir(parents=True, exist_ok=True)
        for rel in ("paddle_tpu/fluid/executor.py",
                    "paddle_tpu/parallel/compiler.py",
                    "paddle_tpu/dataset/feed_pipeline.py",
                    "paddle_tpu/transforms/__init__.py",
                    "paddle_tpu/analysis/verifier.py",
                    "paddle_tpu/obs/telemetry.py",
                    "paddle_tpu/obs/devprof.py",
                    "paddle_tpu/obs/memprof.py",
                    "paddle_tpu/obs/numerics.py",
                    "paddle_tpu/fluid/aot_cache.py",
                    "paddle_tpu/parallel/quant_collectives.py",
                    "paddle_tpu/ops/pallas/attention.py",
                    "bench.py"):
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text("")
        findings = lint.run_rules(root=str(tmp_path),
                                  rules=["span-leak"])
        assert len(findings) == 1
        assert findings[0].line == 2

    def test_suppression_marker(self, tmp_path):
        lint = self._lint()
        d = tmp_path / "paddle_tpu" / "obs"
        d.mkdir(parents=True)
        (d / "m.py").write_text(
            "def f(obs):\n"
            "    s = obs.span('x')  # span-ok: closed by caller\n"
            "    return [s]\n")
        for rel in ("paddle_tpu/profiler", "paddle_tpu/serving",
                    "paddle_tpu/transforms", "paddle_tpu/ckpt"):
            (tmp_path / rel).mkdir(parents=True, exist_ok=True)
        for rel in ("paddle_tpu/fluid/executor.py",
                    "paddle_tpu/parallel/compiler.py",
                    "paddle_tpu/dataset/feed_pipeline.py",
                    "paddle_tpu/transforms/__init__.py",
                    "paddle_tpu/analysis/verifier.py",
                    "paddle_tpu/obs/telemetry.py",
                    "paddle_tpu/obs/devprof.py",
                    "paddle_tpu/obs/memprof.py",
                    "paddle_tpu/obs/numerics.py",
                    "paddle_tpu/fluid/aot_cache.py",
                    "paddle_tpu/parallel/quant_collectives.py",
                    "paddle_tpu/ops/pallas/attention.py",
                    "bench.py"):
            p = tmp_path / rel
            p.parent.mkdir(parents=True, exist_ok=True)
            p.write_text("")
        assert not lint.run_rules(root=str(tmp_path),
                                  rules=["span-leak"])

    def test_shipped_tree_is_clean(self):
        lint = self._lint()
        findings = lint.run_rules(rules=["span-leak"])
        assert not findings, "\n".join(str(f) for f in findings)

    def test_obs_entries_on_hot_path_watchlist(self):
        lint = self._lint()
        watched = set(lint.hot_path_sync.WATCHLIST)
        assert ("paddle_tpu/obs/tracing.py", "Tracer.add_span") in watched
        assert ("paddle_tpu/obs/cost.py",
                "ProgramCost.observe_dispatch") in watched
