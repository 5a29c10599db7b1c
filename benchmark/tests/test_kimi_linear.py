"""The Kimi Linear additions to the benchmark: `lib/flops_kimi_linear.py`
against hand counts at a tiny size and at the cell's, the configuration
file against the catalog's rule (every published width unchanged), and
the new builder rehearsed end to end on the CPU at the tiny preset
benchmark/tests/preset_kimi (its reference comparison and gradient
check included) — through `run.run_cell`, with files and manifest
entries only, as the real cell is added."""

import json
import math
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.lib import flops_kimi_linear as flops
from benchmark.tests import preset_tree

PRESET = os.path.join(preset_tree.BENCH, "tests", "preset_kimi")
CELL = "kimi_tiny.ar"
REAL = "kimi_linear_48b_a3b.ar_s16384"

TINY = {"hidden_size": 8, "num_attention_heads": 2, "kv_lora_rank": 4,
        "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 3,
        "num_hidden_layers": 4, "first_k_dense_replace": 1,
        "linear_attn_config": {"kda_layers": [1, 2, 4],
                               "full_attn_layers": [3], "num_heads": 2,
                               "head_dim": 5},
        "intermediate_size": 10, "moe_intermediate_size": 5,
        "router_width": 6, "num_shared_experts": 1, "vocab_size": 11}


def _real_config():
    with open(os.path.join(preset_tree.BENCH, "configs",
                           "kimi_linear_48b_a3b.json")) as f:
        return json.load(f)


def test_forward_macs_by_hand():
    # batch 2, seq 4: 8 rows; 3 KDA layers, 1 latent layer, 3 expert layers
    assert flops.layer_kinds(TINY) == ["kda", "kda", "mla", "kda"]
    macs = flops.fwd_macs_per_step(TINY, 2, 4, held_visits=5)
    kda = 4 * 8 * 10 + 2 * (8 * 5 + 5 * 10) + 8 * 2
    assert flops.kda_projection_macs_per_row(TINY) == kda
    assert macs["kda_projections"] == 3 * 8 * kda
    assert macs["kda_scan"] == 3 * 8 * 2 * 3 * 25
    mla = 8 * 2 * 6 + 8 * (4 + 2) + 4 * 2 * (4 + 3) + 2 * 3 * 8
    assert macs["latent_projections"] == 8 * mla
    assert macs["attention"] == 2 * 2 * 10 * (6 + 3)
    assert macs["dense_ffn"] == 8 * 3 * 8 * 10
    assert macs["router"] == 3 * 8 * 8 * 6
    assert macs["experts"] == 3 * 5 * 3 * 8 * 5
    assert macs["shared_expert"] == 3 * 8 * 3 * 8 * 5
    assert macs["head"] == 2 * 3 * 8 * 11
    assert flops.train_flops_per_token(TINY, 2, 4, 5) == \
        6.0 * sum(macs.values()) / 8


def test_cell_step_is_the_issues_count():
    """851.5 MFLOP a token forward, 2.555 GFLOP a token, 41.85 TFLOP a
    step at the cell's shape with a fair router (4,096 held visits a
    layer): a KDA layer's projections 79 MFLOP a token and its scan 3,
    the one latent layer's causal scores 168."""
    config = _real_config()
    per_token = flops.train_flops_per_token(config, 1, 16384, 4096)
    assert abs(per_token / 1e9 - 2.555) < 1e-3
    assert abs(per_token * 16384 / 1e12 - 41.85) < 0.01
    macs = flops.fwd_macs_per_step(config, 1, 16384, 4096)
    assert abs(2 * sum(macs.values()) / 16384 / 1e6 - 851.5) < 0.1
    assert round(2 * macs["kda_projections"] / 4 / 16384 / 1e6) == 79
    assert round(2 * macs["kda_scan"] / 4 / 16384 / 1e6) == 3
    assert round(2 * macs["attention"] / 16384 / 1e6) == 168


def test_kda_core_cost_by_hand():
    c = flops.kda_core_cost(2, 4, 3, 5, 7)
    tokens = 2 * 4 * 3
    assert c["fwd"]["flops"] == tokens * 6 * 5 * 7
    assert c["bwd"]["flops"] == tokens * 12 * 5 * 7
    # q, k (5 wide) and v, o (7 wide) in bfloat16; g (5) and beta float32
    assert c["fwd"]["bytes"] == tokens * (2 * 5 * 2 + 2 * 7 * 2 + 6 * 4)
    assert c["bwd"]["bytes"] == tokens * (4 * 5 * 2 + 3 * 7 * 2 + 12 * 4)
    # memory-bound at the cell's shape: 0.99 ms forward, 1.81 backward
    real = flops.kda_core_cost(1, 16384, 32, 128, 128)
    assert real["fwd"]["bytes"] / 819e9 > real["fwd"]["flops"] / 197e12


def test_configuration_keeps_every_published_width():
    config = _real_config()
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "kimi_linear_48b_a3b")
    assert sorted(entry["reduced"]) == ["num_experts", "num_hidden_layers",
                                        "vocab_size"]
    # the manifest's limit of form: a `why` is one line of 1 to 200 characters
    whys = [e["why"] for e in manifest["configs"] + manifest["workloads"]]
    assert all(1 <= len(w) <= 200 and w.isprintable() for w in whys)
    published = {
        "hidden_size": 2304, "q_lora_rank": None, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_attention_heads": 32, "moe_intermediate_size": 1024,
        "router_width": 256, "num_experts_per_token": 8,
        "routed_scaling_factor": 2.446, "num_shared_experts": 1,
        "intermediate_size": 9216, "rms_norm_eps": 1e-5,
        "mla_use_nope": True, "moe_router_activation_func": "sigmoid"}
    assert {k: config[k] for k in published} == published
    lin = config["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(lin["kda_layers"]) == 20
    assert flops.layer_kinds(config) == ["kda", "kda", "kda", "mla", "kda"]
    assert config["published"] == {"num_hidden_layers": 27,
                                   "num_experts": 256, "vocab_size": 163840}
    assert config["num_experts"] == config["experts_held"][1] == 8
    assert config["vocab_size"] * 8 == 163840
    assert "32 chips share each layer" in config["deployment"]
    assert all(k + "_why" in config["assumed"] for k in (
        "kda_gate_rank", "kda_decay", "kda_output_gate_bias"))


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("preset_kimi"))
    path = preset_tree.write(root)
    shutil.copytree(PRESET, os.path.join(root, "bench"), dirs_exist_ok=True)
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "kimi_tiny",
                                "file": "bench/configs/kimi_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "kimi_tiny",
                                  "traffic": "tiny_ar", "chips": 1})
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        real = {m["name"]: m for m in json.load(f)["per_layer"]}
    for m in manifest["per_layer"]:
        if real.get(m["name"], {}).get("workloads") == [REAL]:
            m["workloads"] = [CELL]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


@pytest.fixture
def tiny_tolerances(monkeypatch):
    """The limits of `correct` are set on the chip at the published
    widths (reference/kimi_linear.py).  At the preset's widths a
    bfloat16 rounding is a larger share of a 64-wide sum and a leaf has
    a handful of entries (`A_log`: 2), so the rehearsal — which proves
    the control flow, not the precision — runs with them widened."""
    from benchmark.reference import kimi_linear as reference

    monkeypatch.setattr(reference, "LOGITS_TOLERANCE", 0.05)
    monkeypatch.setattr(reference, "GRAD_TOLERANCE",
                        {k: 0.2 for k in reference.GRAD_TOLERANCE})


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end(manifest_path, trace, monkeypatch, tmp_path,
                              tiny_tolerances):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    result = harness.run_cell(manifest_path, CELL, seed=2 ** 31 + 5,
                              seconds=0.2, trace=trace)
    assert result["correct"], (result["checks"], result["reference"])
    assert result["failed"] == 0 and result["attempted"] > 0
    ref = result["reference"]
    assert ref["routing"]["all_near_ties"] and ref["gradients"]["ok"]
    assert len(ref["gradients"]["rel_l2"]) == 8
    assert ref["probed_positions"] > 0
    metrics = result["metrics"]
    assert all(math.isfinite(m["value"]) for m in metrics.values())
    if not trace:
        assert set(metrics) == {"items_per_s_per_chip", "setup_s"}
        return
    # counters read on the CPU too; no device metric comes of a CPU run
    assert metrics["cache.compiles_in_window"]["value"] == 0
    assert not {"attn.kda_ms", "attn.kda_core_ms", "attn.kda_conv_gate_ms",
                "kernel.kda_core_roofline", "attn.nope_mla_ms",
                "kernel.nope_mla_flash_roofline", "moe.kimi_layers_ms",
                "device.idle_share"} & set(metrics)


def test_same_seed_same_inputs(manifest_path, tiny_tolerances):
    a, b, c = (harness.run_cell(manifest_path, CELL, seed=s, seconds=0.05,
                                trace=False) for s in (5, 5, 6))
    assert a["losses"]["warm_up"] == b["losses"]["warm_up"]
    assert a["losses"]["warm_up"] != c["losses"]["warm_up"]
