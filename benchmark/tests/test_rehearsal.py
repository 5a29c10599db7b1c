"""The harness end to end on the CPU, at a tiny preset.

What the chip runs, rehearsed: the real builders, loop, counters,
spans, readers and the traced branch, driven through `run.run_cell`
with the tiny configurations of benchmark/tests/preset.  On the CPU
the BERT step takes the program's XLA attention (its kernels run under
Mosaic only), no peak is known and no operation runs on a device, so
no device metric comes out — and none is printed: nothing here is a
measurement.

The preset tree (preset_tree.py) holds a dummy configuration, traffic
mix and per-layer metric that no file of the harness knows: adding one
is adding files and entries, never an edit to run.py.
"""

import math
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run as harness

from benchmark.tests import preset_tree

BENCH = preset_tree.BENCH
CELLS = [c for c in preset_tree.CELLS if c[1] != "bert_small"]


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory):
    return preset_tree.write(str(tmp_path_factory.mktemp("preset")))


@pytest.mark.parametrize("cell", [c[0] for c in CELLS])
@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end(manifest_path, cell, trace, monkeypatch,
                              tmp_path):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    result = harness.run_cell(manifest_path, cell, seed=3, seconds=0.2,
                              trace=trace)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    metrics = result["metrics"]
    assert all(math.isfinite(m["value"]) for m in metrics.values())
    if not trace:
        # no peak for a CPU: a share of peak is not invented
        assert set(metrics) == {"items_per_s_per_chip", "setup_s"}
        return
    # no operation ran on a device: no device metric, no breakdown
    assert "breakdown" not in result
    assert "busy_s" not in result["device"]
    assert not {"device.idle_share", "kernel.flash_ms_per_step",
                "kernel.flash_roofline", "spmd.collective_ms_per_step",
                "spmd.collective_exposed_share"} & set(metrics)
    assert metrics["cache.compiles_in_window"]["value"] == 0
    assert metrics["step.ms_p50"]["value"] > 0
    assert ("executor.dispatch_ms_per_step" in metrics) == \
        cell.startswith("resnet")
    assert ("dummy.steps_seen" in metrics) == (cell == "dummy.mix")


def test_data_parallel_cell_checks_its_twin(manifest_path):
    result = harness.run_cell(manifest_path, "resnet_tiny.train_dp4",
                              seed=4, seconds=0.1, trace=False)
    assert result["checks"]["first_loss_matches_one_device_twin"]
    assert result["checks"]["parameters_on_every_chip"]


def test_same_seed_same_inputs(manifest_path):
    a, b, c = (harness.run_cell(manifest_path, "bert_tiny.pretrain", seed=s,
                                seconds=0.05, trace=False)
               for s in (5, 5, 6))
    assert a["losses"]["warm_up"] == b["losses"]["warm_up"]
    assert a["losses"]["warm_up"] != c["losses"]["warm_up"]


def test_command_line_has_no_cpu_mode():
    """On a machine without a TPU the command exits non-zero and prints
    no result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "bert_base.pretrain_s512", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=harness.ROOT)
    assert proc.returncode != 0
    assert "no chip found" in proc.stderr
    assert proc.stdout.strip() == ""


def test_alone_it_exits_nonzero(tmp_path):
    """In a directory that holds only BENCHMARK.json and benchmark/."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "bert_base.pretrain_s512", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        cwd=tmp_path, env=env)
    assert proc.returncode != 0
    assert "no paddle_tpu package" in proc.stderr
    assert proc.stdout.strip() == ""
