"""Set-up accounts for itself (ISSUE 36): the phase log of
`profiler.stage` / `add_phase`, the phases the program records at the
sites that do the work (import, weights, state, JAX's trace / lower /
compile / cache-load events, the Pallas entry points, the Executor's
passes), and the benchmark's `setup.*` metrics that partition `setup_s`
with them — on the tiny presets of benchmark/tests, where the log and
every start-up counter must stand still across a measured window."""

import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import profiler
from paddle_tpu.fluid import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STARTUP_COUNTERS = ("setup_", "param_init_", "jax_", "backend_compiles_")


@pytest.fixture(autouse=True)
def empty_log():
    """A worker's earlier tests may have filled the log to its cap."""
    profiler.reset_phases()
    yield
    profiler.reset_phases()


def _named(name):
    return [p for p in profiler.get_phases() if p.name == name]


def _subprocess_json(code, **env):
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
             **env})
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


# -- the instrument -----------------------------------------------------------

def test_stage_keeps_a_phase_with_its_parent():
    t0 = time.perf_counter()
    with profiler.stage("setup.outer", "outer_test_ms"):
        with profiler.stage("setup.inner", attrs={"kernel": "k"}):
            time.sleep(0.01)
    t1 = time.perf_counter()
    inner, outer = profiler.get_phases()        # in the order they ended
    assert (inner.name, inner.parent) == ("setup.inner", "setup.outer")
    assert (outer.name, outer.parent) == ("setup.outer", None)
    assert inner.attrs == {"kernel": "k"} and outer.attrs is None
    assert t0 <= outer.start_s <= inner.start_s
    assert inner.start_s + inner.dur_s <= outer.start_s + outer.dur_s <= t1
    assert inner.dur_s >= 0.01
    assert profiler.get_time_stats()["outer_test_ms"] >= 10.0


def test_phase_closes_under_an_exception():
    with pytest.raises(ValueError):
        with profiler.stage("setup.outer"):
            with profiler.stage("setup.inner"):
                raise ValueError("in the body")
    assert [(p.name, p.parent) for p in profiler.get_phases()] == [
        ("setup.inner", "setup.outer"), ("setup.outer", None)]
    # nothing is left open: the next stage is at top level again
    with profiler.stage("setup.after"):
        pass
    assert profiler.get_phases()[-1].parent is None


def test_per_step_stage_leaves_the_log_alone():
    before = profiler.get_time_stats().get("host_feed_ms", 0.0)
    with profiler.stage("executor.feed", "host_feed_ms"):
        profiler.add_phase("setup.seen_inside", time.perf_counter(), 0.0)
    # no phase of its own, and it is nobody's parent
    (only,) = profiler.get_phases()
    assert (only.name, only.parent) == ("setup.seen_inside", None)
    assert profiler.get_time_stats()["host_feed_ms"] > before


def test_add_phase_takes_the_open_stage_as_parent_or_the_one_given():
    with profiler.stage("setup.outer"):
        profiler.add_phase("setup.late", 1.0, 2.0)
        profiler.add_phase("setup.told", 1.0, 2.0, parent="setup.other")
    late, told, _ = profiler.get_phases()
    assert late == ("setup.late", 1.0, 2.0, "setup.outer", None)
    assert told.parent == "setup.other"


def test_the_cap_counts_what_it_drops(monkeypatch):
    monkeypatch.setattr(profiler, "PHASE_CAPACITY", 3)
    dropped = profiler.get_int_stats().get("setup_phases_dropped_total", 0)
    for i in range(5):
        with profiler.stage("setup.many"):
            pass
    assert len(profiler.get_phases()) == 3
    assert profiler.get_int_stats()["setup_phases_dropped_total"] \
        == dropped + 2


def test_phase_totals_merge_the_intervals_of_a_name():
    profiler.add_phase("setup.a", 0.0, 10.0)
    profiler.add_phase("setup.a", 2.0, 3.0)         # inside the first
    profiler.add_phase("setup.a", 9.0, 3.0)         # runs over its end
    profiler.add_phase("setup.b", 1.0, 1.0)
    assert profiler.phase_totals() == {"setup.a": 12.0, "setup.b": 1.0}


# -- JAX's events -------------------------------------------------------------

def test_listener_is_installed_once():
    from jax._src import monitoring

    def mine():
        return [f for f in monitoring.get_event_time_span_listeners()
                if f is compile_cache._on_jax_time_span]

    assert len(mine()) == 1             # by the import of compile_cache
    compile_cache.install_phase_listener()
    assert len(mine()) == 1
    # an event it does not record changes nothing
    jax.monitoring.record_event_time_span("/not/ours", 0.0, 1.0)
    jax.monitoring.record_event_duration_secs("/not/ours", 1.0)
    assert profiler.get_phases() == []


def test_jit_inside_a_jit_is_one_trace_on_the_programs_clock():
    inner = jax.jit(lambda x: jnp.tanh(x) * 2.0)
    outer = jax.jit(lambda x: inner(x + 1.0).sum())
    x = jnp.ones((16,), jnp.float32)    # an eager op: a program of its own
    profiler.reset_phases()
    traces = profiler.get_int_stats().get("jax_traces_total", 0)
    t0 = time.perf_counter()
    outer.lower(x).compile()
    wall = time.perf_counter() - t0
    (trace,) = _named("setup.trace")    # the inner trace fired inside it
    assert profiler.get_int_stats()["jax_traces_total"] == traces + 1
    assert trace.attrs["fun_name"] == "<lambda>"
    (lower,) = _named("setup.lower")
    (compiled,) = _named("setup.backend_compile")
    # time.time() stamps converted to perf_counter: inside the wall
    # interval of the call, in order, and no longer than it
    slack = 0.005
    assert t0 - slack <= trace.start_s
    assert trace.start_s + trace.dur_s <= lower.start_s + lower.dur_s + slack
    assert lower.start_s <= compiled.start_s + slack
    assert compiled.start_s + compiled.dur_s <= t0 + wall + slack
    totals = profiler.phase_totals()
    assert totals["setup.trace"] <= wall
    assert sum(totals.values()) <= wall + 3 * slack


_CACHED_COMPILE = """
import json, jax, jax.numpy as jnp
import paddle_tpu
from paddle_tpu import profiler
from paddle_tpu.fluid.compile_cache import enable_persistent_cache
enable_persistent_cache()
x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
profiler.reset_phases()
before = dict(profiler.get_int_stats())
jax.jit(lambda x: jnp.sin(x) @ x.T).lower(x).compile()
after = profiler.get_int_stats()
print(json.dumps({
    "totals": profiler.phase_totals(),
    "compiles": after.get("backend_compiles_total", 0)
                - before.get("backend_compiles_total", 0)}))
"""


def test_a_persistent_cache_hit_is_a_cache_load_not_a_compile(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)}
    cold = _subprocess_json(_CACHED_COMPILE, **env)
    warm = _subprocess_json(_CACHED_COMPILE, **env)
    assert cold["compiles"] == 1
    assert cold["totals"]["setup.backend_compile"] > 0
    assert "setup.cache_load" not in cold["totals"]
    assert warm["compiles"] == 0
    assert "setup.backend_compile" not in warm["totals"]
    assert warm["totals"]["setup.cache_load"] > 0
    # every process traces and lowers, whatever the cache holds
    assert warm["totals"]["setup.trace"] > 0
    assert warm["totals"]["setup.lower"] > 0


# -- the sites ----------------------------------------------------------------

def test_import_leaves_one_phase_whose_children_fit_inside_it():
    got = _subprocess_json(
        "import json, time\n"
        "t0 = time.perf_counter()\n"
        "import paddle_tpu\n"
        "t1 = time.perf_counter()\n"
        "from paddle_tpu import profiler\n"
        "print(json.dumps({'t0': t0, 't1': t1,\n"
        "    'import_ms': profiler.get_time_stats()['import_ms'],\n"
        "    'phases': [list(p[:4]) for p in profiler.get_phases()\n"
        "               if p.name.startswith('setup.import')]}))\n")
    whole = [p for p in got["phases"] if p[0] == "setup.import"]
    children = [p for p in got["phases"] if p[0] != "setup.import"]
    assert len(whole) == 1
    _, start, dur, parent = whole[0]
    assert parent is None
    assert got["t0"] <= start and start + dur <= got["t1"]
    assert got["import_ms"] == pytest.approx(dur * 1e3)
    assert {"setup.import/fluid", "setup.import/nn", "setup.import/ops",
            "setup.import/incubate"} <= {p[0] for p in children}
    assert all(p[3] == "setup.import" for p in children)
    assert all(start <= p[1] and p[1] + p[2] <= start + dur + 1e-9
               for p in children)
    assert sum(p[2] for p in children) <= dur + 1e-9


@pytest.mark.parametrize("shape,dtype,nbytes", [
    ((3, 5), "float32", 60), ((7,), "float32", 28), ((4, 2), "int64", 64)])
def test_create_parameter_counts_the_leaf(shape, dtype, nbytes):
    from paddle_tpu import nn

    stats = profiler.get_int_stats()
    count0 = stats.get("param_init_total", 0)
    bytes0 = stats.get("param_init_bytes_total", 0)
    ms0 = profiler.get_time_stats().get("param_init_ms", 0.0)
    nn.Layer().create_parameter(list(shape), dtype=dtype)
    stats = profiler.get_int_stats()
    assert stats["param_init_total"] - count0 == math.prod(shape)
    assert stats["param_init_bytes_total"] - bytes0 == nbytes
    assert len(_named("setup.param_init")) == 1
    assert profiler.get_time_stats()["param_init_ms"] > ms0


def test_step_builder_records_the_state_it_builds():
    from paddle_tpu.models import bert

    model = bert.BertForPretraining(bert.BertConfig(
        vocab_size=64, hidden_size=16, num_hidden_layers=1,
        num_attention_heads=2, intermediate_size=32,
        max_position_embeddings=16))
    leaves = len(_named("setup.param_init"))
    assert leaves == len(model.state_dict())
    _, state = bert.build_pretrain_step(model, bf16=False)
    (built,) = _named("setup.state_build")
    assert built.parent is None and built.dur_s > 0
    assert set(state) == {"params", "m", "v", "t"}
    assert profiler.get_time_stats()["state_build_ms"] > 0


def test_kernel_entry_points_are_phases_only_while_a_program_is_traced():
    from paddle_tpu.ops.pallas.attention import flash_attention

    q = jnp.ones((1, 128, 2, 64), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, is_causal=True,
                               interpret=True).sum()

    flash_attention(q, q, q, interpret=True)        # concrete operands
    assert _named("setup.kernel_trace") == []
    jax.jit(jax.grad(loss)).lower(q, q, q)
    phases = _named("setup.kernel_trace")
    # the entry point with the forward rule inside it, then the
    # backward rule when the program's backward pass is traced
    assert len(phases) >= 2
    assert all(p.attrs == {"kernel": "flash_attention"} for p in phases)
    (trace,) = [p for p in _named("setup.trace")
                if p.attrs["fun_name"] == "loss"]
    assert all(trace.start_s - 0.005 <= p.start_s
               and p.start_s + p.dur_s <= trace.start_s + trace.dur_s + 0.005
               for p in phases)
    assert profiler.get_time_stats()["kernel_trace_ms"] > 0


def test_chip_smoke_prints_the_totals_on_one_line(capsys):
    sys.path.insert(0, REPO)
    import chip_smoke

    profiler.add_phase("setup.import", 0.0, 1.5)
    profiler.add_phase("setup.import/fluid", 0.0, 1.0, parent="setup.import")
    profiler.add_phase("setup.trace", 2.0, 0.25)
    chip_smoke._print_startup_phases()
    (line,) = capsys.readouterr().out.splitlines()
    assert line.endswith("{'setup.import': 1.5, 'setup.trace': 0.25}")


# -- the benchmark's metrics on the tiny presets ------------------------------

@pytest.fixture(scope="module")
def preset():
    import tempfile

    from benchmark import run as harness
    from benchmark.lib import setup_phases
    from benchmark.tests import preset_tree

    with tempfile.TemporaryDirectory(prefix="preset_setup_") as root:
        path = preset_tree.write(root)
        with open(path) as f:
            manifest = json.load(f)
        # the tree copies the real manifest's metrics; were the ten new
        # ones not in it yet, they are entries to append, not an edit
        have = {m["name"] for m in manifest["per_layer"]}
        for name in (*setup_phases.TIMES, "setup.named_share"):
            if name not in have:
                manifest["per_layer"].append({
                    "name": name, "unit": "s", "better": "lower",
                    "source": "program_span", "layer": "startup",
                    "moves": "setup_s"})
        with open(path, "w") as f:
            json.dump(manifest, f)
        yield harness, path, os.path.join(root, "out")


@pytest.mark.parametrize("cell", ["bert_tiny.pretrain", "resnet_tiny.train"])
def test_preset_cell_partitions_its_setup_and_windows_add_nothing(
        preset, cell, monkeypatch):
    from benchmark.lib import setup_phases

    harness, manifest_path, out_dir = preset
    monkeypatch.setattr(harness, "OUT_DIR", out_dir)
    loop = harness.load_module(os.path.join(harness.HERE, "lib", "loop.py"))
    run_window, windows, seen = loop.run, [], {}
    make_partition = setup_phases.partition

    def startup_state():
        return (len(profiler.get_phases()),
                {k: v for k, v in {**profiler.get_int_stats(),
                                   **profiler.get_time_stats()}.items()
                 if k.startswith(STARTUP_COUNTERS) or k in (
                     "import_ms", "state_build_ms", "kernel_trace_ms",
                     "transform_ms", "verify_ms", "aot_cache_load_ms")})

    def watched(*args, **kwargs):
        if not windows:     # as run.py reads it, one line before
            seen["setup_s"] = time.perf_counter() - harness._T0
        before = startup_state()
        window = run_window(*args, **kwargs)
        windows.append((before, startup_state()))
        return window

    def partition(phases, spans, start_s, end_s):
        seen["total"] = end_s - start_s
        seen["parts"] = make_partition(phases, spans, start_s, end_s)
        return seen["parts"]

    monkeypatch.setattr(loop, "run", watched)
    monkeypatch.setattr(setup_phases, "partition", partition)
    result = harness.run_cell(manifest_path, cell, seed=7, seconds=0.2,
                              trace=True)
    assert result["correct"], result["checks"]
    # the measured window, the traced window's lead-in and itself
    assert len(windows) == 3
    for before, after in windows:
        assert before == after
    assert windows[0][0][0] > 0 and windows[0][0][1]["jax_traces_total"] > 0

    metrics = {k: v["value"] for k, v in result["metrics"].items()
               if k.startswith("setup.")}
    assert set(metrics) == {*setup_phases.TIMES, "setup.named_share"}
    assert all(math.isfinite(v) and v >= 0 for v in metrics.values())
    assert all(metrics[m] == seen["parts"][m] for m in metrics)
    # the nine times and the unnamed rest are this run's `setup_s`
    assert seen["total"] == pytest.approx(seen["setup_s"], abs=1e-3)
    assert sum(metrics[m] for m in setup_phases.TIMES) \
        + seen["parts"]["unnamed_s"] == pytest.approx(seen["setup_s"],
                                                      abs=1e-3)
    assert seen["parts"]["unnamed_s"] >= 0
    assert metrics["setup.named_share"] == pytest.approx(
        1.0 - seen["parts"]["unnamed_s"] / seen["total"])
    assert metrics["setup.reference_s"] == pytest.approx(
        result["setup_spans_s"]["setup.reference"], abs=1e-3)
    assert metrics["setup.compile_s"] > 0       # no persistent cache here
    assert metrics["setup.trace_lower_s"] > 0
    names = {p.name for p in profiler.get_phases()}
    if cell.startswith("bert"):
        assert {"setup.param_init", "setup.state_build"} <= names
        assert metrics["setup.param_init_s"] > 0
    else:
        assert {"setup.transform", "setup.verify"} <= names
