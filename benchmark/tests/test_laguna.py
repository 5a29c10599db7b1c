"""The Laguna additions to the benchmark: `lib/flops_laguna.py` against
hand counts at a tiny size and against ISSUE 38's figures at the cell's,
the configuration file against the catalog's rule (every published
width unchanged, the three lists as published), and the new builder
rehearsed end to end on the CPU at the tiny preset
benchmark/tests/preset_laguna (its reference comparison and gradient
check included) — through `run.run_cell`, with files and manifest
entries only, as the real cell is added."""

import json
import math
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.lib import flops_laguna as flops
from benchmark.tests import preset_tree

PRESET = os.path.join(preset_tree.BENCH, "tests", "preset_laguna")
CELL = "laguna_tiny.ar"
REAL = "laguna_xs2.ar_s16384"
NEW_METRICS = {"attn.window_ms", "attn.full_gqa_ms", "attn.rope_gate_ms",
               "kernel.window_flash_roofline",
               "kernel.gqa_full_flash_roofline",
               "kernel.window_grid_live_share", "moe.laguna_layers_ms"}

TINY = {"hidden_size": 8, "head_dim": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 3, "sliding_window": 3,
        "layer_types": ["full_attention", "sliding_attention",
                        "sliding_attention", "full_attention"],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
        "num_attention_heads_per_layer": [2, 4, 4, 2],
        "intermediate_size": 10, "moe_intermediate_size": 5,
        "shared_expert_intermediate_size": 5, "router_width": 6,
        "vocab_size": 11}


def _real_config():
    with open(os.path.join(preset_tree.BENCH, "configs",
                           "laguna_xs2.json")) as f:
        return json.load(f)


def test_forward_macs_by_hand():
    # batch 2, seq 5: 10 rows; layer 0 full + dense, layers 1, 2 window
    # + experts (the lists' fourth entry is cut with the depth)
    assert flops.layer_kinds(TINY) == ["full", "window", "window"]
    assert (flops.layers_of(TINY, "window"), flops.sparse_layers(TINY)) \
        == (2, 2)
    # a window of 3 over 5 rows: 1 + 2 + 3 + 3 + 3 pairs
    assert flops.window_pairs(5, 3) == 12
    assert flops.window_pairs(2, 3) == 3 and flops.window_pairs(3, 3) == 6
    macs = flops.fwd_macs_per_step(TINY, 2, 5, held_visits=7)
    full = 2 * 8 * 2 * 4 + 2 * 8 * 2 * 4 + 8 * 2
    window = 2 * 8 * 4 * 4 + 2 * 8 * 2 * 4 + 8 * 4
    assert flops.projection_macs_per_row(TINY, 0) == full
    assert flops.projection_macs_per_row(TINY, 1) == window
    assert macs["full_projections"] == 10 * full
    assert macs["window_projections"] == 2 * 10 * window
    assert macs["full_attention"] == 2 * 2 * 15 * 2 * 4
    assert macs["window_attention"] == 2 * 2 * 4 * 12 * 2 * 4
    assert macs["dense_ffn"] == 10 * 3 * 8 * 10
    assert macs["router"] == 2 * 10 * 8 * 6
    assert macs["experts"] == 2 * 7 * 3 * 8 * 5
    assert macs["shared_expert"] == 2 * 10 * 3 * 8 * 5
    assert macs["head"] == 2 * 4 * 8 * 11
    assert flops.train_flops_per_token(TINY, 2, 5, 7) == \
        6.0 * sum(macs.values()) / 10


def test_cell_step_is_the_issues_count():
    """ISSUE 38's check of the builder's function: layer 0 360.9 MFLOP
    forward a token, a window layer 102.8 (projections 75.8, its
    8,257,792 kept pairs 16.5, expert layer 10.5), layer 4 270.7, the
    head 51.4: 991.3 MFLOP forward, 2.974 GFLOP an item, 48.73 TFLOP a
    step with a fair router (8,192 held visits a layer)."""
    config = _real_config()
    assert flops.window_pairs(16384, 512) == 8257792
    per_token = flops.train_flops_per_token(config, 1, 16384, 8192)
    assert abs(per_token / 1e9 - 2.974) < 1e-3
    assert abs(per_token * 16384 / 1e12 - 48.73) < 0.01
    macs = flops.fwd_macs_per_step(config, 1, 16384, 8192)
    mflop = lambda m: 2 * m / 16384 / 1e6
    assert abs(mflop(sum(macs.values())) - 991.3) < 0.1
    assert abs(mflop(macs["window_projections"]) / 3 - 75.8) < 0.05
    assert abs(mflop(macs["window_attention"]) / 3 - 16.5) < 0.05
    assert abs(mflop(macs["full_attention"]) / 2 - 201.3) < 0.05
    expert_layer = (macs["router"] + macs["experts"]
                    + macs["shared_expert"]) / 4
    assert abs(mflop(expert_layer) - 10.5) < 0.05
    assert abs(mflop(macs["full_projections"] / 2 + macs["full_attention"]
                     / 2 + macs["dense_ffn"]) - 360.9) < 0.1
    assert abs(mflop(macs["head"]) - 51.4) < 0.05


def test_flash_costs_by_hand():
    c = flops.window_flash_cost(TINY, 2, 5)
    matmul = 2.0 * 2 * 4 * 12 * 4           # batch, heads, pairs, width
    assert c["fwd"]["flops"] == 2 * matmul
    assert c["bwd"]["flops"] == 5 * matmul
    q_like, kv_like = 10 * 4 * 4 * 2, 10 * 2 * 4 * 2
    assert c["fwd"]["bytes"] == 2 * q_like + 2 * kv_like
    assert c["bwd"]["bytes"] == 4 * q_like + 4 * kv_like
    f = flops.full_flash_cost(TINY, 2, 5)
    assert f["fwd"]["flops"] == 2 * 2.0 * 2 * 2 * 15 * 4
    # at the cell's shape a window layer's kernels are bound by compute
    # still (the band is 512 keys deep), a full layer's by far
    config = _real_config()
    for cost in (flops.window_flash_cost, flops.full_flash_cost):
        real = cost(config, 1, 16384)["fwd"]
        assert real["flops"] / 197e12 > real["bytes"] / 819e9
    assert round(flops.window_flash_cost(config, 1, 16384)["fwd"]["flops"]
                 / 1e9) == 271
    assert flops.self_attn_pattern(config, "window") \
        == r"(^|/)layers/(1|2|3)/self_attn(/|$)"
    assert flops.self_attn_pattern(config, "full") \
        == r"(^|/)layers/(0|4)/self_attn(/|$)"


def test_configuration_keeps_every_published_width():
    config = _real_config()
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == "laguna_xs2")
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json")
    cell = next(w for w in manifest["workloads"] if w["name"] == REAL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna_xs2", "ar_s16384", 1)
    whys = [e["why"] for e in manifest["configs"] + manifest["workloads"]]
    assert all(1 <= len(w) <= 200 and w.isprintable() for w in whys)
    ours = [m for m in manifest["per_layer"]
            if m.get("workloads") == [REAL]]
    assert {m["name"] for m in ours} == NEW_METRICS
    assert manifest["per_layer"][-7:] == ours
    assert all(m["moves"] == "items_per_s_per_chip" for m in ours)
    published = {
        "hidden_size": 2048, "intermediate_size": 8192,
        "num_attention_heads": 48, "num_key_value_heads": 8,
        "head_dim": 128, "max_position_embeddings": 262144,
        "rms_norm_eps": 1e-6, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "gating": True,
        "sliding_window": 512, "partial_rotary_factor": 0.5,
        "moe_routed_scaling_factor": 2.5, "router_width": 256,
        "attention_bias": False, "tie_word_embeddings": False,
        "moe_apply_router_weight_on_input": False, "model_type": "laguna"}
    assert {k: config[k] for k in published} == published
    assert config["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    # the three lists as published, all 40 entries; layers 0-4 run
    period = ["full_attention"] + ["sliding_attention"] * 3
    assert config["layer_types"] == period * 10
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64] * 10
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 39
    assert flops.layer_kinds(config) == ["full", "window", "window",
                                         "window", "full"]
    assert config["published"] == {"num_hidden_layers": 40,
                                   "num_experts": 256,
                                   "vocab_size": 100352}
    assert config["num_hidden_layers"] == 5
    assert config["num_experts"] == config["experts_held"][1] == 16
    assert config["vocab_size"] * 8 == 100352
    assert "16 chips share each layer" in config["deployment"]
    assert all(k + "_why" in config["assumed"] for k in (
        "router_scoring", "attention_gate", "shared_expert_gate",
        "hidden_act", "qk_norm", "initializer_range",
        "auxiliary_balance_loss", "norm_topk_prob"))
    seeded = config["assumed"]["seeded_weights"]
    assert abs(seeded["embedding_multiplier"] - math.sqrt(2048)) < 1e-5
    assert abs(seeded["residual_projection_divisor"]
               - math.sqrt(2 * 40)) < 1e-5


def test_state_is_the_issues_490_million_parameters():
    """490.3 M parameters, 8.22 GiB at 18 bytes a parameter."""
    c = _real_config()
    e, d, kv, f = (c["hidden_size"], c["head_dim"],
                   c["num_key_value_heads"], c["moe_intermediate_size"])
    total = 2 * c["vocab_size"] * e + e
    for i in range(c["num_hidden_layers"]):
        h = c["num_attention_heads_per_layer"][i]
        total += 2 * e * h * d + 2 * e * kv * d + e * h + 2 * e
        if c["mlp_layer_types"][i] == "sparse":
            total += e * c["router_width"] + 3 * e * f * (
                c["num_experts"] + 1)
        else:
            total += 3 * e * c["intermediate_size"]
    assert round(total / 1e6, 1) == 490.3
    assert round(total * 18 / 2 ** 30, 2) == 8.22


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("preset_laguna"))
    path = preset_tree.write(root)
    shutil.copytree(PRESET, os.path.join(root, "bench"), dirs_exist_ok=True)
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "laguna_tiny",
                                "file": "bench/configs/laguna_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "laguna_tiny",
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
    widths (reference/laguna.py).  At the preset's widths a bfloat16
    rounding is a larger share of a 64-wide sum, so the rehearsal —
    which proves the control flow, not the precision — runs with them
    widened."""
    from benchmark.reference import laguna as reference

    monkeypatch.setattr(reference, "LOGITS_TOLERANCE", 0.05)
    monkeypatch.setattr(reference, "NEAR_TIE", 0.1)
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
    assert len(ref["gradients"]["rel_l2"]) == 9
    assert ref["probed_positions"] > 0
    metrics = result["metrics"]
    assert all(math.isfinite(m["value"]) for m in metrics.values())
    if not trace:
        assert set(metrics) == {"items_per_s_per_chip", "setup_s"}
        return
    # counters read on the CPU too (`kernel.window_grid_live_share` is
    # one: it reads the process's own count, nothing here where no
    # kernel was traced); no device metric comes of a CPU run
    assert metrics["cache.compiles_in_window"]["value"] == 0
    assert not (NEW_METRICS - {"kernel.window_grid_live_share"}
                | {"device.idle_share"}) & set(metrics)


def test_same_seed_same_inputs(manifest_path, tiny_tolerances):
    a, b, c = (harness.run_cell(manifest_path, CELL, seed=s, seconds=0.05,
                                trace=False) for s in (5, 5, 6))
    assert a["losses"]["warm_up"] == b["losses"]["warm_up"]
    assert a["losses"]["warm_up"] != c["losses"]["warm_up"]


def test_new_readers_return_nothing_on_another_configuration():
    """A per-layer reader of this configuration asked about a run of
    another (or of a program without the counters) returns None and
    does not raise: the line leaves the metric out."""

    class Run:
        config = {"hidden_size": 8}
        trace = None
        peaks = None
        system = object()

        def setup_delta(self, name):
            return 0

    base = os.path.join(preset_tree.BENCH, "layers")
    for name in sorted(NEW_METRICS):
        module = harness.load_module(os.path.join(base, name + ".py"))
        assert module.read(Run()) is None, name
