"""The SDAR-MoE additions to the benchmark: `lib/flops_sdar_moe.py`
against hand counts at a tiny size and at the cell's, and the new
builder rehearsed end to end on the CPU at the tiny preset
benchmark/tests/preset_sdar (its reference comparison and gradient
check included) — through `run.run_cell`, with files and manifest
entries only, as the real cell is added."""

import json
import math
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.lib import flops_sdar_moe as flops
from benchmark.tests import preset_tree

PRESET = os.path.join(preset_tree.BENCH, "tests", "preset_sdar")
CELL = "sdar_tiny.blockdiff"
REAL = "sdar_30b_a3b.blockdiff_s4096"

TINY = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 3,
        "router_width": 6, "num_experts": 2, "moe_intermediate_size": 5,
        "vocab_size": 11, "assumed": {"block_length": 2}}


def test_live_pairs_are_counted_from_the_definition():
    for seq, block in ((8, 2), (12, 4), (4096, 4)):
        # noisy-noisy block diagonal + noisy-clean strictly lower +
        # clean-clean block causal, in blocks
        n = seq // block
        by_blocks = block * block * (n + n * (n - 1) // 2
                                     + n * (n + 1) // 2)
        assert flops.live_pairs(seq, block) == by_blocks


def test_forward_macs_by_hand():
    # batch 2, seq 4: 16 rows; 5 held visits a layer, 3 masked
    macs = flops.fwd_macs_per_step(TINY, 2, 4, held_visits=5, masked=3)
    assert macs["projections"] == 3 * 16 * (2 * 8 * 8 + 2 * 8 * 4)
    assert macs["router"] == 3 * 16 * 8 * 6
    assert macs["attention"] == 3 * 2 * 2 * (4 * 4 + 2 * 4) * 2 * 4
    assert macs["experts"] == 3 * 5 * 3 * 8 * 5
    assert macs["head"] == 3 * 8 * 11
    assert flops.train_flops_per_token(TINY, 2, 4, 5, 3) == \
        6.0 * sum(macs.values()) / 8


def test_cell_step_is_the_issues_count():
    """49.9 TFLOP a step at the cell's shape with a fair router (32768
    held visits a layer) and half the tokens masked."""
    with open(os.path.join(preset_tree.BENCH, "configs",
                           "sdar_30b_a3b.json")) as f:
        config = json.load(f)
    per_token = flops.train_flops_per_token(config, 4, 4096, 32768, 8192)
    assert abs(per_token * 16384 / 1e12 - 49.9) < 0.1


def test_kernel_costs_by_hand():
    c = flops.block_flash_cost(TINY, 2, 4)
    matmul = 2.0 * 2 * 2 * 24 * 4
    assert c["fwd"]["flops"] == 2 * matmul
    assert c["bwd"]["flops"] == 5 * matmul
    q, kv = 16 * 2 * 4 * 2, 16 * 1 * 4 * 2
    assert c["fwd"]["bytes"] == 2 * q + 2 * kv
    assert c["bwd"]["bytes"] == 4 * q + 4 * kv
    g = flops.grouped_matmul_cost(TINY, 10)
    assert g["fwd"]["flops"] == 2.0 * 10 * 3 * 8 * 5
    assert g["bwd"]["flops"] == 2 * g["fwd"]["flops"]
    assert g["fwd"]["bytes"] == 10 * (16 + 15) * 2 + 2 * 3 * 8 * 5 * 2


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("preset_sdar"))
    path = preset_tree.write(root)
    shutil.copytree(PRESET, os.path.join(root, "bench"), dirs_exist_ok=True)
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "sdar_tiny",
                                "file": "bench/configs/sdar_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "sdar_tiny",
                                  "traffic": "tiny_blockdiff", "chips": 1})
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    for m in manifest["per_layer"]:
        if real.get(m["name"], {}).get("workloads") == [REAL]:
            m["workloads"] = [CELL]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end(manifest_path, trace, monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    result = harness.run_cell(manifest_path, CELL, seed=2 ** 31 + 5,
                              seconds=0.2, trace=trace)
    assert result["correct"], (result["checks"], result["reference"])
    assert result["failed"] == 0 and result["attempted"] > 0
    ref = result["reference"]
    assert ref["routing"]["all_near_ties"] and ref["gradients"]["ok"]
    assert ref["probed_positions"] > 0
    metrics = result["metrics"]
    assert all(math.isfinite(m["value"]) for m in metrics.values())
    if not trace:
        assert set(metrics) == {"items_per_s_per_chip", "setup_s"}
        return
    # counters read on the CPU too; no device metric comes of a CPU run
    assert metrics["moe.load_max_over_mean"]["value"] >= 1.0
    assert 0.0 < metrics["moe.held_visit_share"]["value"] < 1.0
    assert metrics["cache.compiles_in_window"]["value"] == 0
    assert not {"moe.router_ms", "moe.experts_ms", "attn.block_mask_ms",
                "moe.dispatch_combine_ms", "kernel.block_flash_roofline",
                "kernel.block_flash_ms_per_step", "device.idle_share",
                "kernel.grouped_matmul_roofline"} & set(metrics)


def test_same_seed_same_inputs(manifest_path):
    a, b, c = (harness.run_cell(manifest_path, CELL, seed=s, seconds=0.05,
                                trace=False) for s in (5, 5, 6))
    assert a["losses"]["warm_up"] == b["losses"]["warm_up"]
    assert a["losses"]["warm_up"] != c["losses"]["warm_up"]


# -- the comparison that decides `correct`, given planted faults --------------

def _tiny_reference_case():
    import numpy as np

    builder = harness.load_module(os.path.join(
        preset_tree.BENCH, "configs", "sdar_moe.py"))
    with open(os.path.join(PRESET, "configs", "sdar_tiny.json")) as f:
        config = json.load(f)
    from paddle_tpu.jit import functional_state

    params = functional_state(builder.build_model(config, 3))
    batch = builder.make_batch(config, 1, 32, np.random.default_rng(3))
    cfg = builder.reference_config(config)
    names = [n.format(last=cfg["num_hidden_layers"] - 1)
             for n in builder._GRAD_LEAVES]
    return cfg, params, batch, names


def test_a_gradient_that_is_not_finite_fails_whichever_leaf_it_is():
    import numpy as np

    from benchmark.reference import sdar_moe as reference

    want = {"a.moe.gate_weight": np.ones(4), "b.moe.w_down": np.ones(4),
            "c.q_norm.weight": np.ones(4), "d.embed_tokens.weight": np.ones(4)}
    assert reference.compare_gradients(want, want)["ok"]
    for leaf in want:       # `max()` skips a NaN that is not its first value
        bad = {**want, leaf: np.full(4, np.nan)}
        assert not reference.compare_gradients(bad, want)["ok"], leaf
    off = {**want, "b.moe.w_down": np.ones(4) * (
        1 + 2 * reference.GRAD_TOLERANCE["moe.w_down"])}
    out = reference.compare_gradients(off, want)
    assert not out["ok"] and out["limit"]["b.moe.w_down"] == \
        reference.GRAD_TOLERANCE["moe.w_down"]


def test_lower_precision_readings_go_through_the_harness_own_comparison():
    """benchmark/tests/precision_readings.py at the tiny preset: the
    reference with bfloat16 and with float8 operands, handed to
    `compare` and `compare_gradients` as a system's outputs would be.
    Every reading is finite (a saturating cast, a straight-through
    gradient), float32 against itself reads nothing, and float8 reads
    several times what bfloat16 reads on every quantity.  (The limits
    themselves are set at the cell's sizes, on the chip.)"""
    import numpy as np

    from benchmark.reference import sdar_moe as reference

    cfg, params, batch, names = _tiny_reference_case()
    ref = reference.forward(cfg, params, batch)
    routing = list(ref["experts"])
    want = reference.grads(cfg, params, batch, routing, wrt=names)
    same = reference.compare_gradients(
        reference.grads(cfg, params, batch, routing, wrt=names), want)
    assert same["ok"] and max(same["rel_l2"].values()) < 1e-6
    at = lambda r: np.asarray(r["logits"])[0][batch["masked"][0]]
    read = {}
    for dtype in ("bfloat16", "float8_e4m3fn"):
        low = {**cfg, "operand_dtype": dtype}
        got = reference.forward(low, params, batch, routing)
        read[dtype] = {
            "logits": reference.compare(
                float(got["loss"]), at(got), float(ref["loss"]),
                at(ref))["logits_rel_rms"],
            **reference.compare_gradients(reference.grads(
                low, params, batch, routing, wrt=names), want)["rel_l2"]}
    for k, fp8 in read["float8_e4m3fn"].items():
        assert math.isfinite(fp8) and fp8 > 4 * read["bfloat16"][k] > 0, \
            (k, read)
