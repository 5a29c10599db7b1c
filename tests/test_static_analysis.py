"""Static-analysis subsystem tests (ISSUE 3): the Program verifier
(paddle_tpu/analysis/verifier.py) and the tpulint framework
(paddle_tpu/analysis/lint/).

Positive sweep: the verifier reports zero ERROR findings over every
fixture program (tests/fixtures/programs.py) and the book-model zoo
(tests/test_book_models.py BOOK_BUILDERS).  Negative sweep: each pass
fires on a deliberately-corrupted Program — unknown op type,
use-before-def, fetch+donate conflict, collective under a conditional —
with `program#<id> block<idx> op<id> (<type>)` provenance.  Hot-path
contract: the verifier runs ONLY on a compile-cache miss
(profiler-asserted zero verifier time on cache-hit steps).  Lint side:
the shipped tree is clean under every registered rule, each rule fires
on crafted violations, suppression markers work, and the
tools/run_lints.py aggregator gates it all (this file IS its tier-1
wiring — a rule regression fails the suite here).
"""

import os
import re
import subprocess
import sys
import warnings

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.fluid as fluid
from paddle_tpu import profiler
from paddle_tpu.analysis import (ERROR, WARNING, Finding,
                                 ProgramVerificationError,
                                 registered_passes, verify_program)
from paddle_tpu.analysis.verifier import maybe_verify_program
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.fluid.executor import Scope, scope_guard

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO_ROOT, "tools")
_TESTS = os.path.dirname(os.path.abspath(__file__))
for _p in (TOOLS, _TESTS):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from fixtures import programs as fixture_programs  # noqa: E402
import test_book_models as book  # noqa: E402

from tpulint import load_lint  # noqa: E402

lint = load_lint()


def _errors(findings):
    return [f for f in findings if f.severity == ERROR]


# ---------------------------------------------------------------------------
# Verifier: positive sweep over the fixture + book-model zoos
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(fixture_programs.FIXTURES))
def test_fixture_zoo_verifies_clean(name):
    main, startup, fetch = fixture_programs.FIXTURES[name]()
    for label, prog, fl in (("main", main, fetch),
                            ("startup", startup, None)):
        errs = _errors(verify_program(prog, fetch_list=fl))
        assert not errs, (name, label, errs)


@pytest.mark.parametrize("name", sorted(book.BOOK_BUILDERS))
def test_book_model_zoo_verifies_clean(name):
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        fetch = book.BOOK_BUILDERS[name]()
    for label, prog, fl in (("main", main, fetch),
                            ("startup", startup, None)):
        errs = _errors(verify_program(prog, fetch_list=fl))
        assert not errs, (name, label, errs)


def test_all_passes_registered():
    names = set(registered_passes())
    assert {"op-registry", "def-before-use", "block-linkage",
            "donation-safety", "collective-order",
            "shard-consistency"} <= names
    assert {"dead-op", "write-never-read"} <= set(
        registered_passes(tier=WARNING))


# ---------------------------------------------------------------------------
# Verifier: negative sweep — each pass fires on a corrupted Program
# ---------------------------------------------------------------------------

def _simple_program():
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [-1, 4], "float32")
        y = fluid.layers.fc(x, 2)
    return main, startup, x, y

_PROVENANCE_RE = re.compile(r"^program#\d+ block\d+ op\d+ \([\w.]+\)")


def test_unknown_op_type_fires():
    main, _startup, _x, y = _simple_program()
    main.global_block().append_op(
        type="totally_bogus_op", inputs={"X": [y]},
        outputs={"Out": [y]}, infer_shape=False)
    errs = _errors(verify_program(main))
    assert any(f.pass_name == "op-registry" for f in errs), errs
    f = next(f for f in errs if f.pass_name == "op-registry")
    assert f.op_type == "totally_bogus_op"
    # greppable provenance: program#<id> block<idx> op<id> (<type>)
    assert _PROVENANCE_RE.match(str(f)), str(f)


def test_use_before_def_fires():
    main, _startup, _x, _y = _simple_program()
    main.global_block().ops[0].inputs.setdefault("X", []).append(
        "phantom_never_written")
    errs = _errors(verify_program(main))
    assert any(f.pass_name == "def-before-use"
               and "phantom_never_written" in f.message for f in errs), errs


def test_read_before_write_in_block_fires():
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [-1, 4], "float32")
        y = fluid.layers.fc(x, 2)
        z = fluid.layers.relu(y)
    blk = main.global_block()
    # move the producer of z's input after its consumer
    relu_op = blk.ops[-1]
    blk.ops.remove(relu_op)
    blk.ops.insert(0, relu_op)
    errs = _errors(verify_program(main, fetch_list=[z]))
    assert any(f.pass_name == "def-before-use"
               and "read before it is written" in f.message
               for f in errs), errs


def test_fetch_donate_conflict_fires():
    main, _startup, _x, y = _simple_program()
    errs = _errors(verify_program(main, fetch_list=[y],
                                  donated=[y.name]))
    assert any(f.pass_name == "donation-safety" and f.var == y.name
               for f in errs), errs
    # without the donation the same program is clean
    assert not _errors(verify_program(main, fetch_list=[y]))


def _conditional_collective_program():
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [-1, 4], "float32")
        cond = fluid.data("cond", [1], "bool")
        sub = main._create_block()
        sub.append_op(
            "c_allreduce_sum", inputs={"X": [x.name]},
            outputs={"Out": [x.name]}, attrs={"ring_id": 0},
            infer_shape=False)
        main._rollback()
        main.current_block().append_op(
            "conditional_block",
            inputs={"Cond": [cond.name], "Input": [x.name]},
            outputs={"Out": ["@EMPTY@"], "Scope": ["@EMPTY@"]},
            attrs={"sub_block": sub.idx, "is_scalar_condition": True},
            infer_shape=False)
    return main


def test_collective_under_conditional_fires():
    main = _conditional_collective_program()
    errs = _errors(verify_program(main))
    assert any(f.pass_name == "collective-order"
               and f.op_type == "c_allreduce_sum" for f in errs), errs
    # the finding points INTO the sub-block
    f = next(f for f in errs if f.pass_name == "collective-order")
    assert f.block_idx == 1


def test_p2p_send_recv_under_conditional_is_clean():
    """send_v2/recv_v2 pairs inside a conditional sub-block are a
    supported pattern (the p2p queue pairs them at lowering,
    test_distributed.py::test_send_recv_in_conditional_block) — only
    ring collectives are order-checked."""
    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [8, 4], "float32")
        cond = fluid.data("cond", [1], "bool")
        sub = main._create_block()
        sub.append_op("send_v2", inputs={"X": [x.name]}, outputs={},
                      attrs={"ring_id": 0, "peer": 3},
                      infer_shape=False)
        sub.append_op("recv_v2", inputs={},
                      outputs={"Out": ["recv_out"]},
                      attrs={"ring_id": 0, "peer": 0,
                             "out_shape": [1, 4], "dtype": "float32"},
                      infer_shape=False)
        main._rollback()
        main.current_block().append_op(
            "conditional_block",
            inputs={"Cond": [cond.name], "Input": [x.name]},
            outputs={"Out": ["@EMPTY@"], "Scope": ["@EMPTY@"]},
            attrs={"sub_block": sub.idx, "is_scalar_condition": True},
            infer_shape=False)
    assert not [f for f in _errors(verify_program(main))
                if f.pass_name == "collective-order"]


def test_dangling_sub_block_fires():
    main, _startup, _x, y = _simple_program()
    main.global_block().append_op(
        "conditional_block", inputs={"Cond": [y.name]},
        outputs={"Out": ["@EMPTY@"]},
        attrs={"sub_block": 99}, infer_shape=False)
    errs = _errors(verify_program(main))
    assert any(f.pass_name == "block-linkage"
               and "sub_block" in f.message for f in errs), errs


def test_dead_op_warning_tier():
    main, _startup, x, y = _simple_program()
    with framework.program_guard(main):
        dead = fluid.layers.relu(y)  # never fetched, never read
    findings = verify_program(main, fetch_list=[y])
    dead_hits = [f for f in findings if f.pass_name == "dead-op"]
    assert dead_hits and all(f.severity == WARNING for f in dead_hits)
    # ERROR-tier-only invocation (what the executor runs) skips it
    assert not [f for f in verify_program(main, fetch_list=[y],
                                          tiers=(ERROR,))
                if f.pass_name == "dead-op"]


# ---------------------------------------------------------------------------
# Verifier: provenance formatting (op_callstack)
# ---------------------------------------------------------------------------

def test_op_callstack_provenance():
    paddle_tpu.set_flags({"FLAGS_op_callstack": True})
    try:
        main, startup = framework.Program(), framework.Program()
        with framework.program_guard(main, startup), \
                unique_name.guard():
            x = fluid.data("x", [-1, 4], "float32")
            y = fluid.layers.fc(x, 2)
        main.global_block().append_op(
            type="totally_bogus_op", inputs={"X": [y]},
            outputs={"Out": [y]}, infer_shape=False)  # <- reported line
    finally:
        paddle_tpu.set_flags({"FLAGS_op_callstack": False})
    errs = _errors(verify_program(main))
    f = next(f for f in errs if f.pass_name == "op-registry")
    assert f.callstack, "op_callstack not recorded on the op"
    text = str(f)
    assert "at " in text and "test_static_analysis.py" in text, text


# ---------------------------------------------------------------------------
# Executor integration: FLAGS_verify_program gate + cache-miss-only
# ---------------------------------------------------------------------------

def _run_ctx():
    main, startup = framework.Program(), framework.Program()
    scope = Scope()
    return main, startup, scope


def test_executor_raises_on_corrupt_program():
    main, startup, scope = _run_ctx()
    with framework.program_guard(main, startup), unique_name.guard(), \
            scope_guard(scope):
        x = fluid.data("x", [-1, 4], "float32")
        y = fluid.layers.fc(x, 2)
        exe = fluid.Executor()
        exe.run(startup)
        main.global_block().append_op(
            type="totally_bogus_op", inputs={"X": [y]},
            outputs={"Out": [y]}, infer_shape=False)
        with pytest.raises(ProgramVerificationError) as ei:
            exe.run(main, feed={"x": np.ones((2, 4), "float32")},
                    fetch_list=[y])
        assert "totally_bogus_op" in str(ei.value)
        assert "program#" in str(ei.value)


def test_verify_program_warn_and_off_modes():
    main, _startup, _x, y = _simple_program()
    main.global_block().append_op(
        type="totally_bogus_op", inputs={"X": [y]},
        outputs={"Out": [y]}, infer_shape=False)
    paddle_tpu.set_flags({"FLAGS_verify_program": "warn"})
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            maybe_verify_program(main)  # must NOT raise
        assert any("totally_bogus_op" in str(x.message) for x in w), \
            [str(x.message) for x in w]
        paddle_tpu.set_flags({"FLAGS_verify_program": "off"})
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            maybe_verify_program(main)
        assert not w
    finally:
        paddle_tpu.set_flags({"FLAGS_verify_program": "on"})


def test_verifier_runs_only_on_cache_miss():
    """The hot-path contract: verification happens once per compiled
    entry; cache-hit steps pay ZERO verifier time (profiler-asserted)."""
    main, startup, scope = _run_ctx()
    with framework.program_guard(main, startup), unique_name.guard(), \
            scope_guard(scope):
        x = fluid.data("x", [-1, 4], "float32")
        y = fluid.layers.fc(x, 2)
        exe = fluid.Executor()
        exe.run(startup)
        feed = {"x": np.ones((3, 4), "float32")}
        exe.run(main, feed=feed, fetch_list=[y])  # compile-cache miss

        runs0 = profiler.get_int_stats().get("verifier_runs", 0)
        ms0 = profiler.get_time_stats().get("verify_ms", 0.0)
        assert runs0 >= 1
        for _ in range(5):  # cache hits: same program/signature
            exe.run(main, feed=feed, fetch_list=[y])
        assert profiler.get_int_stats().get("verifier_runs", 0) == runs0
        assert profiler.get_time_stats().get("verify_ms", 0.0) == ms0

        # a NEW feed signature is a fresh miss -> verified again
        exe.run(main, feed={"x": np.ones((7, 4), "float32")},
                fetch_list=[y])
        assert profiler.get_int_stats().get("verifier_runs", 0) == \
            runs0 + 1


# ---------------------------------------------------------------------------
# tpulint: shipped tree is clean; every rule fires on crafted input
# ---------------------------------------------------------------------------

def test_lint_rules_registered():
    assert set(lint.registered_rules()) >= {
        "hot-path-sync", "lock-order", "untraced-side-effect"}


def test_shipped_tree_is_lint_clean():
    findings = lint.run_rules()
    assert not findings, "\n".join(str(f) for f in findings)


def test_hot_path_shim_surface():
    """tools/check_hot_path_sync.py keeps its historical CLI surface as
    a thin shim over the framework rule."""
    import check_hot_path_sync as shim

    assert shim.check_repo() == []
    assert len(shim.WATCHLIST) >= 20
    assert shim.SYNC_OK == "# sync-ok"
    # shim and framework share ONE watchlist manifest
    assert shim.WATCHLIST is lint.hot_path_sync.WATCHLIST


def test_feed_pipeline_on_hot_path_watchlist():
    """ISSUE 4: the pod-scale feed pipeline's entry points are lint-
    watched — the producer/ring feed path carries the same zero-sync
    contract as the executor dispatch loop, and
    test_shipped_tree_is_lint_clean above proves the shipped tree
    honors it."""
    watched = set(lint.hot_path_sync.WATCHLIST)
    for qual in ("FeedPipeline.__iter__", "FeedPipeline._produce",
                 "DeviceRing.put", "DeviceRing.get"):
        assert ("paddle_tpu/dataset/feed_pipeline.py", qual) in watched
    # _FeedPrefetcher (the compatibility adapter) stays watched too
    assert ("paddle_tpu/fluid/executor.py", "_FeedPrefetcher") in watched


def test_transforms_on_hot_path_watchlist():
    """ISSUE 5: the graph-transform entry points are lint-watched —
    transforms run only on the compile-cache-miss path and manipulate
    Program metadata, so they carry the zero-sync contract (no device
    array may ever flow through a pass)."""
    watched = set(lint.hot_path_sync.WATCHLIST)
    for qual in ("maybe_transform_program", "apply_transforms"):
        assert ("paddle_tpu/transforms/__init__.py", qual) in watched


def test_telemetry_on_hot_path_watchlist():
    """ISSUE 10: the live-telemetry entry points are lint-watched — the
    sampler thread, the watchdog evaluator and the HTTP handler run
    concurrently with every training/serving loop and must read
    host-side tables only; obs/telemetry.py is also in the span-leak
    watched set, and test_shipped_tree_is_lint_clean above proves the
    shipped tree honors both."""
    watched = set(lint.hot_path_sync.WATCHLIST)
    for qual in ("Collector.sample_once", "Collector._loop",
                 "Watchdog.evaluate", "Watchdog.observe",
                 "_Handler.do_GET"):
        assert ("paddle_tpu/obs/telemetry.py", qual) in watched
    assert "paddle_tpu/obs/telemetry.py" in lint.span_leak.WATCHED


def test_devprof_on_hot_path_watchlist():
    """obs/devprof.py reads a finished trace after the fact: nothing of
    it runs on the dispatch path, so no entry of it is on the
    hot-path watchlist; it stays in the span-leak watched set."""
    watched = lint.hot_path_sync.WATCHLIST
    assert not [e for e in watched if e[0] == "paddle_tpu/obs/devprof.py"]
    assert "paddle_tpu/obs/devprof.py" in lint.span_leak.WATCHED


def test_quant_collectives_on_hot_path_watchlist():
    """ISSUE 16: the int8 collective codec's entry points are lint-
    watched — pack/quantize/dequantize trace INSIDE the jitted step,
    where a host sync or numpy materialization would stall every
    quantized gradient reduction; parallel/quant_collectives.py is
    also in the span-leak watched set."""
    watched = set(lint.hot_path_sync.WATCHLIST)
    for qual in ("pack", "quantize_blockwise", "dequantize_blockwise",
                 "quant_allreduce_sum"):
        assert ("paddle_tpu/parallel/quant_collectives.py",
                qual) in watched
    assert "paddle_tpu/parallel/quant_collectives.py" \
        in lint.span_leak.WATCHED


def test_memprof_on_hot_path_watchlist():
    """ISSUE 14: the memory-ledger entry points are lint-watched —
    set/add run on the dispatch/ring/ckpt hot paths, ledger_gauges on
    the telemetry sampler thread and oom_report on the dispatch
    except-path, so all of them must stay host-registry reads;
    obs/memprof.py is also in the span-leak watched set, and
    test_shipped_tree_is_lint_clean above proves the shipped tree
    honors both."""
    watched = set(lint.hot_path_sync.WATCHLIST)
    for qual in ("set_entry", "add_entry", "ledger_gauges",
                 "oom_report"):
        assert ("paddle_tpu/obs/memprof.py", qual) in watched
    assert "paddle_tpu/obs/memprof.py" in lint.span_leak.WATCHED


def test_numerics_on_hot_path_watchlist():
    """ISSUE 15: the numeric-health entry points are lint-watched —
    note_dispatch_stats/note_loss_scale run ON the dispatch hot path
    (bounded host appends of device references), drain/health_gauges
    on the telemetry sampler thread (the sanctioned LazyFetch-style
    materialization boundary), and bisect_nonfinite is offline
    forensics; obs/numerics.py is also in the span-leak watched set,
    and test_shipped_tree_is_lint_clean above proves the shipped tree
    honors both."""
    watched = set(lint.hot_path_sync.WATCHLIST)
    for qual in ("note_dispatch_stats", "note_loss_scale", "drain",
                 "health_gauges", "bisect_nonfinite"):
        assert ("paddle_tpu/obs/numerics.py", qual) in watched
    assert "paddle_tpu/obs/numerics.py" in lint.span_leak.WATCHED


def test_fleet_and_aot_cache_on_hot_path_watchlist():
    """ISSUE 17: the multi-tenant fleet's admission/dispatch entry
    points and the persistent AOT cache's load/store are lint-watched
    — registry dispatch and quota checks run on client threads racing
    the dispatch loop, and aot_cache load/store handle DEVICE
    executables on compile-miss paths; both modules are also in the
    span-leak watched set (serving/ via the directory entry,
    fluid/aot_cache.py explicitly)."""
    watched = set(lint.hot_path_sync.WATCHLIST)
    for rel, qual in (
            ("paddle_tpu/serving/batcher.py", "DynamicBatcher.submit"),
            ("paddle_tpu/serving/batcher.py",
             "DynamicBatcher._pop_best"),
            ("paddle_tpu/serving/registry.py", "ModelRegistry.submit"),
            ("paddle_tpu/serving/registry.py", "_TenantCache.put"),
            ("paddle_tpu/serving/registry.py", "_TenantCache._evicted"),
            ("paddle_tpu/fluid/aot_cache.py", "try_load"),
            ("paddle_tpu/fluid/aot_cache.py", "try_store"),
            ("paddle_tpu/fluid/aot_cache.py",
             "compile_entry_with_cache")):
        assert (rel, qual) in watched
    assert "paddle_tpu/fluid/aot_cache.py" in lint.span_leak.WATCHED
    assert "paddle_tpu/serving" in lint.span_leak.WATCHED


def test_shard_check_on_hot_path_watchlist():
    """ISSUE 18: the static sharding analyzer's entry points are
    lint-watched — shard_consistency_pass runs on the compile-cache-
    miss path inside the verifier pipeline, and run/comm_report/
    feasibility must stay pure host-side metadata walks (the analyzer
    predicts collective traffic, it must never CAUSE any);
    test_shipped_tree_is_lint_clean above proves the shipped tree
    honors it."""
    watched = set(lint.hot_path_sync.WATCHLIST)
    for qual in ("shard_consistency_pass", "_ShardChecker.run",
                 "comm_report", "feasibility"):
        assert ("paddle_tpu/analysis/shard_check.py", qual) in watched


def test_fast_decode_on_hot_path_watchlist():
    """ISSUE 20: the fast-decode entry points are lint-watched — the
    chunk scheduler (_prefill_tick) and the lazy-growth /
    extend-backpressure path (_ensure_pages, _grow_to) run every
    engine step between decode dispatches, and the ragged
    paged-attention dispatch seam traces INSIDE the decode jit;
    ops/pallas/attention.py is also in the span-leak watched set, and
    test_shipped_tree_is_lint_clean above proves the shipped tree
    honors both."""
    watched = set(lint.hot_path_sync.WATCHLIST)
    for qual in ("AutoregressiveEngine._prefill_tick",
                 "AutoregressiveEngine._ensure_pages",
                 "AutoregressiveEngine._grow_to"):
        assert ("paddle_tpu/serving/engine.py", qual) in watched
    assert ("paddle_tpu/ops/pallas/attention.py",
            "paged_attention") in watched
    assert "paddle_tpu/ops/pallas/attention.py" \
        in lint.span_leak.WATCHED


def test_hot_path_rule_fires_on_unsanctioned_sync(tmp_path):
    bad = tmp_path / "paddle_tpu" / "fluid"
    bad.mkdir(parents=True)
    (bad / "executor.py").write_text(
        "class Executor:\n"
        "    def run(self):\n"
        "        import numpy as np\n"
        "        return np.asarray(self.x)\n"
        "    def _dispatch(self):\n"
        "        return np.asarray(self.y)  # sync-ok: test boundary\n")
    msgs = lint.hot_path_sync.check_file(
        str(bad / "executor.py"), ["Executor.run", "Executor._dispatch"],
        root=str(tmp_path))
    assert len(msgs) == 1 and "Executor.run" in msgs[0], msgs


def test_hot_path_rule_flags_renamed_function(tmp_path):
    f = tmp_path / "m.py"
    f.write_text("def other():\n    pass\n")
    msgs = lint.hot_path_sync.check_file(
        str(f), ["Executor.run"], root=str(tmp_path))
    assert len(msgs) == 1 and "not found" in msgs[0], msgs


_LOCK_CYCLE_SRC = """
import threading, jax

class A:
    def __init__(self):
        self.lock_a = threading.Lock()
        self.b = B()
    def foo(self):
        with self.lock_a:
            self.b.bar()
    def put(self, x):
        with self.lock_a:
            return jax.device_put(x)

class B:
    def __init__(self):
        self.lock_b = threading.Lock()
        self.a = A()
    def bar(self):
        with self.lock_b:
            pass
    def baz(self):
        with self.lock_b:
            self.a.foo()
"""


def test_lock_order_rule_finds_cycle_and_device_work():
    findings = lint.lock_order.check_sources({"x.py": _LOCK_CYCLE_SRC})
    msgs = [f.message for f in findings]
    assert any("lock-order cycle" in m for m in msgs), msgs
    assert any("device_put while holding" in m for m in msgs), msgs


def test_lock_order_rule_finds_self_deadlock():
    src = ("import threading\n"
           "class D:\n"
           "    def __init__(self):\n"
           "        self.mu = threading.Lock()\n"
           "    def outer(self):\n"
           "        with self.mu:\n"
           "            self.inner()\n"
           "    def inner(self):\n"
           "        with self.mu:\n"
           "            pass\n")
    findings = lint.lock_order.check_sources({"z.py": src})
    assert any("re-acquires non-reentrant lock D.mu" in f.message
               for f in findings), findings


def test_lock_order_compile_lock_exempt():
    src = ("import threading, jax\n"
           "class E:\n"
           "    def __init__(self):\n"
           "        self._compile_lock = threading.Lock()\n"
           "    def build(self, x):\n"
           "        with self._compile_lock:\n"
           "            return jax.device_put(x)\n")
    assert not lint.lock_order.check_sources({"c.py": src})


_LOCAL_RECEIVER_SRC = """
import threading

class Batcher:
    def __init__(self):
        self._lock = threading.Lock()
        self.w = Worker()
    def push(self):
        with self._lock:
            w = self.w
            w.drain()

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self.b = Batcher()
    def drain(self):
        with self._lock:
            pass
    def kick(self):
        with self._lock:
            b = self.b
            b.push()
"""


def test_lock_order_resolves_plain_local_receivers():
    """`w = self.w; w.drain()` must resolve like `self.w.drain()` — the
    call-graph edge (and the cycle) survives the local alias."""
    findings = lint.lock_order.check_sources({"a.py": _LOCAL_RECEIVER_SRC})
    assert any("lock-order cycle" in f.message
               and "Batcher._lock" in f.message
               and "Worker._lock" in f.message
               for f in findings), [f.message for f in findings]


_MODULE_SINGLETON_SRC = """
import threading

class Engine:
    def __init__(self):
        self.mu = threading.Lock()
    def run(self):
        with self.mu:
            _PUMP.go()

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
    def go(self):
        lk = self._lock
        with lk:
            _ENGINE.run()

_ENGINE = Engine()
_PUMP = Pump()
"""


def test_lock_order_resolves_module_singletons_and_lock_aliases():
    """Module-level `_ENGINE = Engine()` receivers and `lk = self._lock`
    acquisitions both resolve; the cross-singleton cycle is reported."""
    findings = lint.lock_order.check_sources(
        {"b.py": _MODULE_SINGLETON_SRC})
    assert any("lock-order cycle" in f.message
               and "Engine.mu" in f.message and "Pump._lock" in f.message
               for f in findings), [f.message for f in findings]


def test_lock_order_untyped_locals_stay_unresolved():
    """A local bound from an arbitrary call has no known type: no edge
    may be invented, even when a wrong guess would close a cycle."""
    src = ("import threading\n"
           "class G:\n"
           "    def __init__(self):\n"
           "        self.mu = threading.Lock()\n"
           "    def a(self, x):\n"
           "        with self.mu:\n"
           "            h = x.get()\n"
           "            h.b()\n"
           "class H:\n"
           "    def __init__(self):\n"
           "        self.mu = threading.Lock()\n"
           "    def b(self):\n"
           "        with self.mu:\n"
           "            pass\n"
           "    def c(self, y):\n"
           "        with self.mu:\n"
           "            g = y.get()\n"
           "            g.a(None)\n")
    assert not lint.lock_order.check_sources({"c.py": src})


_SIDE_EFFECT_SRC = """
import jax

class C:
    def step(self, x):
        self.count += 1
        return x
    def go(self, x):
        return jax.jit(self.step)(x)

@jax.jit
def f(x):
    global N
    N = 1
    return x
"""


def test_side_effect_rule_fires():
    findings = lint.side_effects.check_source("y.py", _SIDE_EFFECT_SRC)
    msgs = [f.message for f in findings]
    assert any("mutates self.count" in m for m in msgs), msgs
    assert any("assigns global 'N'" in m for m in msgs), msgs


def test_side_effect_closure_box_exempt():
    # closure-cell mutation is the sanctioned trace-time side channel
    src = ("import jax\n"
           "def make():\n"
           "    box = []\n"
           "    def step(x):\n"
           "        box[:] = [1]\n"
           "        return x\n"
           "    return jax.jit(step)\n")
    assert not lint.side_effects.check_source("ok.py", src)


def test_suppression_markers():
    assert lint.suppressed("x = 1  # tpulint: disable=lock-order",
                           "lock-order")
    assert lint.suppressed("x = 1  # tpulint: disable=all", "anything")
    assert lint.suppressed("x = 1  # sync-ok: boundary", "hot-path-sync",
                           marker="# sync-ok")
    assert not lint.suppressed("x = 1  # tpulint: disable=lock-order",
                               "hot-path-sync")
    assert not lint.suppressed("x = 1", "lock-order")


# ---------------------------------------------------------------------------
# CI aggregator: tools/run_lints.py + tools/tpulint.py CLIs
# ---------------------------------------------------------------------------

def test_run_lints_aggregator_clean():
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "run_lints.py"),
         "--skip-op-coverage"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_run_lints_aggregator_fails_on_regression(tmp_path):
    # an empty tree is missing every watched hot-path file: the
    # aggregator must fail, proving a rule regression fails tier-1
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "run_lints.py"),
         "--skip-op-coverage", "--root", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "finding" in proc.stderr


def test_shapecheck_cli_selftest():
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "shapecheck.py"),
         "--selftest"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "selftest ok" in proc.stdout


def test_shapecheck_cli_dump_roundtrip(tmp_path):
    """Executor-grade verification of a Program.to_dict() dump, then
    the same dump with a planted dtype drift (exit 1 + finding)."""
    import json

    main, startup = framework.Program(), framework.Program()
    with framework.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [8, 4], "float32")
        y = fluid.layers.fc(x, 4)
    d = main.to_dict()
    clean = tmp_path / "prog.json"
    clean.write_text(json.dumps(d))
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "shapecheck.py"),
         str(clean), "--feed", "x", "--fetch", y.name],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr

    # plant the renamed/removed-var signature (catchable without jax:
    # dataflow corruption, not numeric rule evaluation)
    op0 = d["blocks"][0]["ops"][0]
    slot = next(iter(op0["inputs"]))
    op0["inputs"][slot] = ["ghost" for _ in op0["inputs"][slot]]
    dirty = tmp_path / "dirty.json"
    dirty.write_text(json.dumps(d))
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "shapecheck.py"),
         str(dirty), "--feed", "x", "--fetch", y.name],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "renamed or removed" in proc.stderr


def test_tpulint_cli():
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "tpulint.py"), "--list"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    for rule in ("hot-path-sync", "lock-order", "untraced-side-effect"):
        assert rule in proc.stdout
    proc = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "tpulint.py"),
         "--rule", "no-such-rule"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
