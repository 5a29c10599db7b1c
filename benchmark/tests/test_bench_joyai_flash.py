"""The JoyAI-LLM-Flash additions to the benchmark: `lib/flops_joyai.py`
against hand counts at a tiny size and at the cell's, the configuration
file against the catalog's rule (every published width unchanged), and
the new builder rehearsed end to end on the CPU at the tiny preset
benchmark/tests/preset_joyai (its reference comparison and gradient
check included) — through `run.run_cell`, with files and manifest
entries only, as the real cell is added."""

import json
import math
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.lib import flops_joyai as flops
from benchmark.tests import preset_tree

PRESET = os.path.join(preset_tree.BENCH, "tests", "preset_joyai")
CELL = "joyai_tiny.ar_mtp"
REAL = "joyai_llm_flash.ar_mtp_s8192"

TINY = {"hidden_size": 8, "num_attention_heads": 2, "q_lora_rank": 6,
        "kv_lora_rank": 4, "qk_nope_head_dim": 4, "qk_rope_head_dim": 2,
        "v_head_dim": 3, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 1, "intermediate_size": 10,
        "moe_intermediate_size": 5, "router_width": 6,
        "n_shared_experts": 1, "vocab_size": 11}


def _real_config():
    with open(os.path.join(preset_tree.BENCH, "configs",
                           "joyai_llm_flash.json")) as f:
        return json.load(f)


def test_forward_macs_by_hand():
    # batch 2, seq 4: 8 rows; 3 + 1 attention layers, 2 + 1 expert layers
    macs = flops.fwd_macs_per_step(TINY, 2, 4, held_visits=5)
    per_row = 8 * 6 + 6 * 2 * 6 + 8 * (4 + 2) + 4 * 2 * (4 + 3) + 2 * 3 * 8
    assert flops.latent_projection_macs_per_row(TINY) == per_row
    assert macs["latent_projections"] == 4 * 8 * per_row
    assert macs["attention"] == 4 * 2 * 2 * 10 * (6 + 3)
    assert macs["dense_ffn"] == 8 * 3 * 8 * 10
    assert macs["router"] == 3 * 8 * 8 * 6
    assert macs["experts"] == 3 * 5 * 3 * 8 * 5
    assert macs["shared_expert"] == 3 * 8 * 3 * 8 * 5
    assert macs["mtp_projection"] == 8 * 16 * 8
    assert macs["heads"] == 2 * (3 + 2) * 8 * 11
    assert flops.train_flops_per_token(TINY, 2, 4, 5) == \
        6.0 * sum(macs.values()) / 8


def test_cell_step_is_the_issues_count():
    """55.7 TFLOP a step at the cell's shape with a fair router (8192
    held visits a layer): 84 MFLOP a token forward in a layer's causal
    scores against 53 in its six projections."""
    config = _real_config()
    per_token = flops.train_flops_per_token(config, 2, 8192, 8192)
    assert abs(per_token * 16384 / 1e12 - 55.7) < 0.1
    macs = flops.fwd_macs_per_step(config, 2, 8192, 8192)
    layers = flops.attention_layers(config)
    assert round(2 * macs["attention"] / layers / 16384 / 1e6) == 84
    assert round(2 * macs["latent_projections"] / layers / 16384 / 1e6) == 53


def test_kernel_costs_by_hand():
    c = flops.mla_flash_cost(TINY, 2, 4)
    pairs = 2.0 * 2 * 2 * 10
    assert c["fwd"]["flops"] == pairs * (6 + 3)
    assert c["bwd"]["flops"] == pairs * (3 * 6 + 2 * 3)
    rows = 8 * 2 * 2
    assert c["fwd"]["bytes"] == rows * (2 * 6 + 2 * 3)
    assert c["bwd"]["bytes"] == rows * (4 * 6 + 4 * 3)


def check_manifest(manifest: dict):
    """This cell's configuration entry in `manifest`, found by name."""
    [entry] = [c for c in manifest["configs"]
               if c["name"] == "joyai_llm_flash"]
    assert sorted(entry["reduced"]) == ["n_routed_experts",
                                        "num_hidden_layers", "vocab_size"]


def test_configuration_keeps_every_published_width():
    config = _real_config()
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        check_manifest(json.load(f))
    published = {
        "hidden_size": 2048, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_attention_heads": 32, "moe_intermediate_size": 768,
        "router_width": 256, "num_experts_per_tok": 8,
        "routed_scaling_factor": 2.5, "n_shared_experts": 1,
        "intermediate_size": 7168, "num_nextn_predict_layers": 1,
        "rope_theta": 32000000, "scoring_func": "sigmoid"}
    assert {k: config[k] for k in published} == published
    assert config["published"] == {"num_hidden_layers": 40,
                                   "n_routed_experts": 256,
                                   "vocab_size": 129280}
    assert config["n_routed_experts"] == config["experts_held"][1] == 16
    assert config["vocab_size"] * 8 == 129280
    assert "16 chips share each layer" in config["deployment"]


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("preset_joyai"))
    path = preset_tree.write(root)
    shutil.copytree(PRESET, os.path.join(root, "bench"), dirs_exist_ok=True)
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "joyai_tiny",
                                "file": "bench/configs/joyai_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "joyai_tiny",
                                  "traffic": "tiny_ar_mtp", "chips": 1})
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
    assert metrics["moe.global_load_max_over_mean"]["value"] >= 1.0
    assert metrics["cache.compiles_in_window"]["value"] == 0
    assert not {"attn.mla_ms", "attn.mla_latent_proj_ms", "mtp.ms",
                "kernel.mla_flash_ms_per_step", "kernel.mla_flash_roofline",
                "moe.shared_expert_ms", "moe.biased_router_ms",
                "device.idle_share"} & set(metrics)


def test_same_seed_same_inputs(manifest_path):
    a, b, c = (harness.run_cell(manifest_path, CELL, seed=s, seconds=0.05,
                                trace=False) for s in (5, 5, 6))
    assert a["losses"]["warm_up"] == b["losses"]["warm_up"]
    assert a["losses"]["warm_up"] != c["losses"]["warm_up"]
