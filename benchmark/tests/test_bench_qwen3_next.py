"""The Qwen3-Next additions to the benchmark: `lib/flops_qwen3_next.py`
against hand counts at a tiny size and against the figures at the
cell's, the configuration file against the catalog's rule (every
published width unchanged), the cell's manifest entries and metrics
pinned BY NAME, and the new builder rehearsed end to end on the CPU at
the tiny preset benchmark/tests/preset_qwen3_next (its reference
comparison and gradient check included, and the cell's readers over the
compiled step's own scopes) — through `run.run_cell`, with files and
manifest entries only, as the real cell is added."""

import collections
import json
import math
import os
import shutil

import pytest

from benchmark import run as harness
from benchmark.lib import flops_qwen3_next as flops
from benchmark.lib import peaks, scopes
from benchmark.tests import preset_tree

PRESET = os.path.join(preset_tree.BENCH, "tests", "preset_qwen3_next")
CELL = "qwen3_next_tiny.ar"
REAL = "qwen3_next_80b_a3b.ar_s16384"
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/"
          "config.json")
# the cell's own per-layer metrics (PR 46), each with its unit
NEW_METRICS = {"attn.gdn_ms": "ms", "attn.gdn_pre_ms": "ms",
               "attn.gdn_core_ms": "ms", "kernel.gdn_core_roofline": "%",
               "attn.gated_full_ms": "ms"}
# the accepted metrics that list no cells: every cell reports them
UNLISTED = {
    "input.wait_ms_per_step", "cache.compiles_in_window",
    "cache.persistent_hits", "step.ms_p50", "step.ms_max",
    "device.idle_share", "device.peak_hbm_gib", "setup.reach_s",
    "setup.import_s", "setup.param_init_s", "setup.trace_lower_s",
    "setup.kernel_trace_s", "setup.compile_s", "setup.cache_load_s",
    "setup.reference_s", "setup.warm_up_s", "setup.named_share"}

TINY = {"hidden_size": 8, "head_dim": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_hidden_layers": 4,
        "full_attention_interval": 4, "linear_num_key_heads": 1,
        "linear_num_value_heads": 2, "linear_key_head_dim": 3,
        "linear_value_head_dim": 5, "decoder_sparse_step": 1,
        "mlp_only_layers": [1], "intermediate_size": 10,
        "moe_intermediate_size": 5, "shared_expert_intermediate_size": 5,
        "router_width": 6, "vocab_size": 11}


def _real_config():
    with open(os.path.join(preset_tree.BENCH, "configs",
                           "qwen3_next_80b_a3b.json")) as f:
        return json.load(f)


def _manifest():
    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_forward_macs_by_hand():
    # batch 2, seq 5: 10 rows; layers 0-2 Gated DeltaNet, 3 full; layer
    # 1 dense (mlp_only_layers), the others expert layers
    assert flops.layer_kinds(TINY) == ["gdn", "gdn", "gdn", "full"]
    assert flops.sparse_layers(TINY) == 3
    macs = flops.fwd_macs_per_step(TINY, 2, 5, held_visits=7)
    gdn = 8 * (2 * 3 + 2 * 10) + 8 * 4 + 10 * 8
    full = 8 * 2 * 2 * 4 + 2 * 8 * 1 * 4 + 2 * 4 * 8
    assert flops.gdn_projection_macs_per_row(TINY) == gdn
    assert flops.full_projection_macs_per_row(TINY) == full
    assert macs["gdn_projections"] == 3 * 10 * gdn
    assert macs["gdn_scan"] == 3 * 10 * 2 * 3 * 3 * 5
    assert macs["full_projections"] == 10 * full
    assert macs["full_attention"] == 2 * 2 * 15 * 2 * 4
    assert macs["dense_ffn"] == 10 * 3 * 8 * 10
    assert macs["router"] == 3 * 10 * 8 * 6
    assert macs["experts"] == 3 * 7 * 3 * 8 * 5
    assert macs["shared_expert"] == 3 * 10 * 8 * (3 * 5 + 1)
    assert macs["head"] == 2 * 4 * 8 * 11
    assert flops.train_flops_per_token(TINY, 2, 5, 7) == \
        6.0 * sum(macs.values()) / 10


def test_cell_step_counts():
    """MACs a token forward: a GDN layer's projections 33.7 M, its scan
    32 x 3 x 128^2 = 1.57 M; the full layer's projections 27.3 M, its
    causal pairs 16 heads x 8,192.5 x 512 = 67.1 M; an expert layer the
    router 1.05 M, 10 visits x 32/512 held x 3.15 M and the gated shared
    expert 3.15 M; the head 38.9 M: 263.7 M, 1.582 GFLOP a token
    trained, 25.92 TFLOP a step with a fair router."""
    config = _real_config()
    assert flops.layer_kinds(config) == ["gdn", "gdn", "gdn", "full"]
    assert flops.gdn_projection_macs_per_row(config) == 33_685_504
    assert flops.full_projection_macs_per_row(config) == 27_262_976
    held = 16384 * 10 * 32 / 512
    macs = flops.fwd_macs_per_step(config, 1, 16384, held)
    per_token = lambda m: m / 16384 / 1e6
    assert abs(per_token(macs["gdn_scan"]) / 3 - 1.5729) < 1e-3
    assert abs(per_token(macs["full_attention"]) - 67.11) < 0.01
    assert abs(per_token(macs["experts"]) / 4 - 1.966) < 1e-3
    assert abs(per_token(macs["head"]) - 38.89) < 0.01
    assert abs(per_token(sum(macs.values())) - 263.69) < 0.01
    flop = flops.train_flops_per_token(config, 1, 16384, held)
    assert abs(flop / 1e9 - 1.5822) < 1e-4
    assert abs(flop * 16384 / 1e12 - 25.92) < 0.01


def test_scan_cost_counts_keys_once_a_key_head():
    c = flops.gdn_core_cost(2, 5, 1, 2, 3, 4)
    tokens = 10
    assert c["fwd"]["flops"] == 6 * tokens * 2 * 3 * 4
    assert c["bwd"]["flops"] == 2 * c["fwd"]["flops"]
    qk, v, gates = tokens * 1 * 3 * 2, tokens * 2 * 4 * 2, tokens * 2 * 2 * 4
    assert c["fwd"]["bytes"] == 2 * qk + 2 * v + gates
    assert c["bwd"]["bytes"] == 4 * qk + 3 * v + 2 * gates
    # at the cell's shape the scan is bound by memory, as Kimi's is
    real = flops.gdn_core_cost(1, 16384, 16, 32, 128, 128)["fwd"]
    assert real["flops"] / 197e12 < real["bytes"] / 819e9


def test_reader_patterns_take_the_layers_from_the_configuration():
    config = _real_config()
    assert flops.attn_pattern(config, "gdn") \
        == r"(^|/)layers/(0|1|2)/linear_attn(/|$)"
    assert flops.attn_pattern(config, "gdn", "gdn_core") \
        == r"(^|/)layers/(0|1|2)/linear_attn/gdn_core(/|$)"
    assert flops.attn_pattern(config, "full") \
        == r"(^|/)layers/(3)/self_attn(/|$)"
    eight = dict(config, num_hidden_layers=8)
    assert flops.attn_pattern(eight, "full") \
        == r"(^|/)layers/(3|7)/self_attn(/|$)"


def _check_entries(manifest: dict):
    """The configuration and the cell, once each, found by name."""
    [entry] = [c for c in manifest["configs"]
               if c["name"] == "qwen3_next_80b_a3b"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size"]
    assert entry["source"] == SOURCE
    [cell] = [w for w in manifest["workloads"] if w["name"] == REAL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3_next_80b_a3b", "ar_s16384", 1)
    whys = [e["why"] for e in manifest["configs"] + manifest["workloads"]]
    assert all(1 <= len(w) <= 200 and w.isprintable() for w in whys)


def _check_metrics(manifest: dict):
    """The metrics that list the cell are its five, each once and
    listing it alone; the metrics that list no cells are the accepted
    ones — all by name, wherever they stand."""
    per_layer = manifest["per_layer"]
    ours = [m for m in per_layer if REAL in m.get("workloads", [])]
    assert sorted(m["name"] for m in ours) == sorted(NEW_METRICS)
    assert all(m["workloads"] == [REAL]
               and m["unit"] == NEW_METRICS[m["name"]]
               and m["source"] == "device_trace"
               and m["moves"] == "items_per_s_per_chip" for m in ours)
    assert {m["name"] for m in per_layer if "workloads" not in m} \
        == UNLISTED


def check_manifest(manifest: dict):
    """This cell's entries in `manifest`, pinned by name, so that entries
    appended after them change nothing."""
    _check_entries(manifest)
    _check_metrics(manifest)


def test_configuration_keeps_every_published_width():
    config = _real_config()
    _check_entries(_manifest())
    assert config["source"] == SOURCE
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "router_width": 512}
    assert {k: config[k] for k in published} == published
    assert config["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 512,
                                   "vocab_size": 151936}
    assert config["num_hidden_layers"] == 4
    assert config["num_experts"] == config["experts_held"][1] == 32
    assert config["vocab_size"] * 8 == 151936
    assert "16 chips share each layer" in config["deployment"]
    # no key that the Laguna and Kimi readers take for their own
    assert "sliding_window" not in config
    assert "linear_attn_config" not in config
    assert all(k + "_why" in config["assumed"] for k in (
        "norm", "A_log", "dt_bias", "conv_taps", "projection_layout",
        "attention_gate", "shared_expert_gate", "router_scoring",
        "auxiliary_balance_loss", "initializer_range"))
    seeded = config["assumed"]["seeded_weights"]
    assert abs(seeded["embedding_multiplier"] - math.sqrt(2048)) < 1e-5
    assert abs(seeded["residual_projection_divisor"]
               - math.sqrt(2 * 48)) < 1e-5


def test_cell_metrics_by_name():
    """The cell reports its five metrics and the accepted metrics that
    list no cells — pinned by name, not by where they stand."""
    _check_metrics(_manifest())


def test_state_is_625_million_parameters():
    """3 x 33.72 M (GDN) + 27.26 M (attention) + 4 x (1.05 router +
    3.15 shared) M + 4 x 32 x 3.146 M + 2 x 18,992 x 2048 = 625.7 M;
    10.49 GiB at 18 bytes a parameter."""
    c = _real_config()
    e, f = c["hidden_size"], c["moe_intermediate_size"]
    hk, hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    key, value = hk * c["linear_key_head_dim"], hv * c["linear_value_head_dim"]
    gdn = (e * (2 * key + 2 * value) + e * 2 * hv + 4 * (2 * key + value)
           + 2 * hv + c["linear_value_head_dim"] + value * e)
    d, h, kv = c["head_dim"], c["num_attention_heads"], c["num_key_value_heads"]
    full = e * 2 * h * d + 2 * e * kv * d + h * d * e + 2 * d
    expert_layer = (e * c["router_width"] + 3 * e * f * (c["num_experts"] + 1)
                    + e)
    total = (3 * gdn + full + 4 * (expert_layer + 2 * e)
             + 2 * c["vocab_size"] * e + e)
    assert abs(gdn / 1e6 - 33.72) < 0.01 and abs(full / 1e6 - 27.26) < 0.01
    assert round(total / 1e6, 1) == 625.7
    assert round(total * 18 / 2 ** 30, 2) == 10.49


@pytest.fixture(scope="module")
def manifest_path(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("preset_qwen3_next"))
    path = preset_tree.write(root)
    shutil.copytree(PRESET, os.path.join(root, "bench"), dirs_exist_ok=True)
    with open(path) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "qwen3_next_tiny",
                                "file": "bench/configs/qwen3_next_tiny.json"})
    manifest["workloads"].append({"name": CELL, "config": "qwen3_next_tiny",
                                  "traffic": "tiny_ar", "chips": 1})
    real = {m["name"]: m for m in _manifest()["per_layer"]}
    for m in manifest["per_layer"]:
        if real.get(m["name"], {}).get("workloads") == [REAL]:
            m["workloads"] = [CELL]
    with open(path, "w") as f:
        json.dump(manifest, f)
    return path


@pytest.fixture
def tiny_tolerances(monkeypatch):
    """The limits of `correct` are set on the chip at the published
    widths (reference/qwen3_next.py).  At the preset's widths a bfloat16
    rounding is a larger share of a 64-wide sum, so the rehearsal —
    which proves the control flow, not the precision — runs with them
    widened.  A_log's most: with 2 value heads its gradient is a sum
    that nearly cancels (sum_t dg_t g_t), and bfloat16 activations flip
    its sign on a slowly decaying head (5.6e-6 in float32, -1.3e-5 in
    bfloat16, seed 2^31 + 7), where the float32 step agrees with the
    reference to 1e-5 (tests/test_qwen3_next.py)."""
    from benchmark.reference import qwen3_next as reference

    monkeypatch.setattr(reference, "LOGITS_TOLERANCE", 0.05)
    monkeypatch.setattr(reference, "NEAR_TIE", 0.1)
    monkeypatch.setattr(reference, "GRAD_TOLERANCE", {
        k: 5.0 if k.endswith("A_log") else 0.2
        for k in reference.GRAD_TOLERANCE})


def _scopes_of_the_compiled_step(monkeypatch):
    """Stands in for the device table a CPU run has not: every
    instruction of the cell's compiled step one microsecond, under the
    `(phase, path)` that the program's own name map gives it
    (`obs.opprof`, which `devprof.device_time` joins the chip's trace
    to).  Its numbers are no measurement; what it checks is that the
    readers' patterns find the scopes the program emits, and how those
    nest."""
    from paddle_tpu.obs import opprof

    def table(run):
        names = opprof.profile_hlo_text(run.system._compiled.as_text(),
                                        label="step")["instr_name"]
        count = collections.Counter(
            v for v in names.values() if isinstance(v, tuple))
        return {"steps": 1, "by_name": {k: n * 1e-6
                                        for k, n in count.items()}}

    monkeypatch.setattr(scopes, "table", table)


@pytest.mark.parametrize("trace", [False, True])
def test_cell_runs_end_to_end(manifest_path, trace, monkeypatch, tmp_path,
                              tiny_tolerances):
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path))
    if trace:
        _scopes_of_the_compiled_step(monkeypatch)
    result = harness.run_cell(
        manifest_path, CELL, seed=2 ** 31 + 7, seconds=0.2, trace=trace,
        peaks=peaks.peaks_for("TPU v5 lite") if trace else None)
    assert result["correct"], (result["checks"], result["reference"])
    assert result["failed"] == 0 and result["attempted"] > 0
    ref = result["reference"]
    assert ref["routing"]["all_near_ties"] and ref["gradients"]["ok"]
    assert len(ref["gradients"]["rel_l2"]) == 8
    assert ref["probed_positions"] > 0
    checks = result["checks"]
    assert checks["kda_group_repeat_total_is_0"]
    assert checks["kda_fallback_total_is_0"]
    metrics = result["metrics"]
    assert all(math.isfinite(m["value"]) for m in metrics.values())
    if not trace:
        assert set(metrics) == {"items_per_s_per_chip", "setup_s"}
        return
    # no device metric comes of a CPU run; the cell's five read the
    # stand-in table over the compiled step's own scopes
    assert metrics["cache.compiles_in_window"]["value"] == 0
    assert "device.idle_share" not in metrics
    ms = {name: metrics[name]["value"] for name in NEW_METRICS}
    assert ms["attn.gdn_ms"] > 0 and ms["attn.gated_full_ms"] > 0
    assert ms["attn.gdn_pre_ms"] > 0 and ms["attn.gdn_core_ms"] > 0
    assert ms["attn.gdn_pre_ms"] + ms["attn.gdn_core_ms"] \
        <= ms["attn.gdn_ms"]
    assert 0 < ms["kernel.gdn_core_roofline"] <= 100


def test_same_seed_same_inputs(manifest_path, tiny_tolerances):
    a, b, c = (harness.run_cell(manifest_path, CELL, seed=s, seconds=0.05,
                                trace=False) for s in (5, 5, 6))
    assert a["losses"]["warm_up"] == b["losses"]["warm_up"]
    assert a["losses"]["warm_up"] != c["losses"]["warm_up"]


def test_new_readers_return_nothing_on_another_configuration():
    """A reader of this cell asked about a run of another configuration
    (no `linear_num_value_heads`), or of a run without a device table,
    returns None and does not raise: the line leaves the metric out."""

    class Run:
        config = {"hidden_size": 8}
        trace = None
        peaks = None
        system = object()

    base = os.path.join(preset_tree.BENCH, "layers")
    for name in sorted(NEW_METRICS):
        module = harness.load_module(os.path.join(base, name + ".py"))
        assert module.read(Run()) is None, name
        run = Run()
        run.config = dict(_real_config())
        assert module.read(run) is None, name
