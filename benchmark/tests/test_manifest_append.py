"""Appending to BENCHMARK.json changes nothing that is there.

A copy of the real manifest with one probe configuration, one probe
cell and one probe per-layer metric that lists the probe cell appended
at the ends of their lists passes every cell's own manifest check (the
`check_manifest` of its `test_bench_*` module, where it has one), and
each accepted cell is found and reports the same metrics as before.
So a later PR adds a configuration, its cell and its readers by
appending files and entries, and edits no file the benchmark has."""

import copy
import json
import os

import pytest

from benchmark import run as harness
from benchmark.tests import (test_bench_joyai_flash, test_bench_kimi_linear,
                             test_bench_laguna, test_bench_qwen3_next)

CHECKS = {m.REAL: m.check_manifest for m in (
    test_bench_joyai_flash, test_bench_kimi_linear, test_bench_laguna,
    test_bench_qwen3_next)}
PROBE_CELL = "probe_model.probe_mix"


def _real() -> dict:
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _appended(manifest: dict) -> dict:
    out = copy.deepcopy(manifest)
    out["configs"].append({
        "name": "probe_model", "source": "https://example.org/probe-model",
        "file": "benchmark/configs/probe_model.json", "reduced": [],
        "why": "a configuration appended by the test"})
    out["workloads"].append({
        "name": PROBE_CELL, "config": "probe_model", "traffic": "probe_mix",
        "chips": 1, "why": "a cell appended by the test"})
    out["per_layer"].append({
        "name": "probe.ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "probe",
        "moves": "items_per_s_per_chip", "workloads": [PROBE_CELL]})
    return out


def _reported(manifest: dict, cell: str) -> dict:
    """The metric names a run of `cell` reports, by kind, in order."""
    return {kind: [m["name"] for m in manifest[kind]
                   if cell in m.get("workloads", [cell])]
            for kind in ("end_to_end", "per_layer")}


def test_every_cell_with_a_test_module_has_its_check():
    cells = {w["name"] for w in _real()["workloads"]}
    assert set(CHECKS) <= cells


@pytest.mark.parametrize("cell", [w["name"] for w in _real()["workloads"]])
def test_an_appended_configuration_cell_and_metric_change_no_cell(cell):
    real = _real()
    probe = _appended(real)
    if cell in CHECKS:
        CHECKS[cell](probe)
    assert harness.find_cell(probe, cell) == harness.find_cell(real, cell)
    assert _reported(probe, cell) == _reported(real, cell)
    assert _reported(probe, PROBE_CELL)["per_layer"][-1] == "probe.ms"
