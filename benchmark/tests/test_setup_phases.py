"""`lib/setup_phases.py` on hand-made intervals: self time by the
innermost interval, the reference check opaque, clipping to process
start and window start, and the partition adding up."""

import types

import pytest

from benchmark.lib import setup_phases
from benchmark.lib.setup_phases import partition


def ns(name, start_s, end_s):
    return (name, int(start_s * 1e9), int(end_s * 1e9))


def times(out):
    return {k: round(v, 9) for k, v in out.items()
            if k in setup_phases.TIMES and v}


def test_nested_phases_give_self_time():
    out = partition(
        [("setup.trace", 10.0, 8.0, None),              # [10, 18)
         ("setup.kernel_trace", 12.0, 3.0, None),       # inside it
         ("setup.backend_compile", 13.0, 1.0, "setup.kernel_trace"),
         ("setup.cache_load", 13.5, 0.25, "setup.kernel_trace")],
        [], 0.0, 20.0)
    assert times(out) == {
        "setup.trace_lower_s": 5.0, "setup.kernel_trace_s": 2.0,
        "setup.compile_s": 0.75, "setup.cache_load_s": 0.25}
    assert out["unnamed_s"] == pytest.approx(12.0)
    assert out["setup.named_share"] == pytest.approx(0.4)


def test_overlapping_intervals_of_one_metric_count_once():
    out = partition(
        [("setup.trace", 1.0, 4.0, None), ("setup.lower", 3.0, 4.0, None),
         ("setup.trace", 2.0, 1.0, None), ("setup.verify", 6.5, 2.0, None),
         ("setup.param_init", 20.0, 1.0, None),
         ("setup.state_build", 20.5, 1.0, None)],
        [], 0.0, 30.0)
    assert times(out) == {"setup.trace_lower_s": 7.5,
                          "setup.param_init_s": 1.5}


def test_clipped_at_process_start_and_window_start():
    out = partition(
        [("setup.import", 95.0, 10.0, None),            # began before
         ("setup.import/fluid", 95.0, 8.0, "setup.import"),
         ("setup.trace", 118.0, 5.0, None),             # ends in the window
         ("setup.lower", 130.0, 5.0, None)],            # all in the window
        [ns("setup.warm_up", 119.0, 120.0)], 100.0, 120.0)
    assert times(out) == {"setup.import_s": 5.0, "setup.trace_lower_s": 1.0,
                          "setup.warm_up_s": 1.0}
    assert out["unnamed_s"] == pytest.approx(13.0)


def test_reach_runs_to_the_first_import_and_children_are_import():
    out = partition(
        [("setup.import/fluid", 7.0, 2.0, "setup.import"),
         ("setup.import/ops", 9.0, 0.5, "setup.import"),
         ("setup.import", 7.0, 3.0, None),
         ("setup.backend_compile", 8.0, 0.5, None),     # at import time
         ("setup.import", 15.0, 1.0, None)],            # a re-import
        [], 0.0, 20.0)
    assert times(out) == {"setup.reach_s": 7.0, "setup.import_s": 3.5,
                          "setup.compile_s": 0.5}


def test_everything_inside_the_reference_check_is_the_reference_checks():
    out = partition(
        [("setup.trace", 9.0, 3.0, None),       # 1 s before, 2 s inside
         ("setup.backend_compile", 11.0, 1.0, None),
         ("setup.cache_load", 14.5, 1.0, None)],    # half inside
        [ns("setup.reference", 10.0, 15.0), ns("setup.model", 0.0, 9.0),
         ns("setup.warm_up", 16.0, 18.0)], 0.0, 18.0)
    assert times(out) == {
        "setup.trace_lower_s": 1.0, "setup.reference_s": 5.0,
        "setup.cache_load_s": 0.5, "setup.warm_up_s": 2.0}
    # the benchmark's `setup.model` span names nothing
    assert out["unnamed_s"] == pytest.approx(9.5)


def test_parts_add_up_and_empty_input_is_all_unnamed():
    out = partition([], [], 5.0, 9.0)
    assert out["unnamed_s"] == 4.0 and out["setup.named_share"] == 0.0
    assert not times(out)
    phases = [("setup.trace", 0.3 * i, 0.45, None) for i in range(40)] + [
        ("setup.cache_load", 0.3 * i + 0.1, 0.1, None) for i in range(40)]
    out = partition(phases, [ns("setup.reference", 3.0, 4.0)], 0.0, 13.0)
    assert sum(out[m] for m in setup_phases.TIMES) + out["unnamed_s"] \
        == pytest.approx(13.0, abs=1e-9)
    assert out["setup.reference_s"] == pytest.approx(1.0)


def test_a_program_without_a_phase_log_reads_nothing(monkeypatch):
    from paddle_tpu import profiler

    run = types.SimpleNamespace(
        window=types.SimpleNamespace(start_ns=int(20e9)), setup_s=20.0,
        spans=types.SimpleNamespace(spans=[ns("setup.warm_up", 18.0, 20.0)]))
    monkeypatch.setattr(profiler, "get_phases",
                        lambda: [("setup.import", 4.0, 2.0, None)])
    assert setup_phases.of_run(run, "setup.reach_s") == pytest.approx(4.0)
    assert setup_phases.of_run(run, "setup.warm_up_s") == pytest.approx(2.0)
    monkeypatch.delattr(profiler, "get_phases")
    parent = types.SimpleNamespace(**vars(run))
    del parent._setup_partition
    assert setup_phases.of_run(parent, "setup.reach_s") is None
