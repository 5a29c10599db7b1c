"""benchmark/lib/flops.py against the figures on record and against
counts made by hand."""

import json
import os

import pytest

from benchmark.lib import flops, peaks

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix, tflop", [
    # BENCH_r05 (2026-07-31): 9.63 TFLOP analytic, 9.62 by XLA's count
    ("pretrain_s512", 9.63),
    # ISSUE 22's prediction for the phase-1 shape
    ("pretrain_s128", 8.95),
])
def test_bert_step_flops(mix, tflop):
    t = traffic(mix)
    per_token = flops.bert_train_flops_per_token(
        config("bert_base"), t["batch"], t["seq"], t["masked"])
    assert per_token * t["batch"] * t["seq"] / 1e12 == pytest.approx(
        tflop, rel=2e-3)


def test_bert_by_hand_at_a_size_one_can_check():
    # 1 layer, hidden 2, FFN 4, vocab 5; 1 sequence of 3 positions, 1 masked
    cfg = dict(hidden_size=2, intermediate_size=4, num_hidden_layers=1,
               vocab_size=5)
    per_position = 4 * 2 * 2 + 2 * 2 * 4 + 2 * 3 * 2     # qkvo, ffn, scores+pv
    macs = 3 * per_position + 1 * (2 * 2 + 2 * 5) + (2 * 2 + 2 * 2)
    assert flops.bert_fwd_flops(cfg, 1, 3, 1) == 2 * macs


def test_resnet50_macs():
    cfg = config("resnet50")
    # the variant the repo builds (stride on the 3x3): ~4.1 GMAC, +-3%
    assert flops.resnet_fwd_macs_per_image(cfg) / 1e9 == pytest.approx(
        4.1, rel=0.03)
    # 53 convolutions and the classifier
    layers = flops.resnet_layers(cfg)
    assert len(layers) == 54 and layers[-1] == ("fc", 2048, 1000, 1, 1)
    assert layers[0] == ("conv", 3, 64, 7, 112)
    # He et al. 2015, Table 1: "3.8 x 10^9 FLOPs" (multiply-adds) for the
    # 50-layer model, which strides on the first 1x1
    paper = dict(cfg, stride_on="1x1")
    assert flops.resnet_fwd_macs_per_image(paper) / 1e9 == pytest.approx(
        3.8, rel=0.03)
    assert flops.resnet_train_flops_per_image(cfg) == \
        6 * flops.resnet_fwd_macs_per_image(cfg)


def test_flash_cost_and_its_bound():
    v5e = peaks.peaks_for("TPU v5 lite")
    cost = flops.flash_attention_cost(batch=32, heads=12, seq=512,
                                      head_dim=64)
    matmul = 2 * 32 * 12 * 512 * 512 * 64
    operand = 32 * 12 * 512 * 64 * 2
    assert cost["fwd"] == {"flops": 2 * matmul, "bytes": 4 * operand}
    assert cost["bwd"] == {"flops": 5 * matmul, "bytes": 8 * operand}
    # seq 512: 256 FLOPs a byte, above the v5e's 240 -> compute-bound;
    # seq 128: 64 FLOPs a byte -> memory-bound
    assert flops.roofline_seconds(**_fb(cost["fwd"]), peaks=v5e)[1] \
        == "compute"
    short = flops.flash_attention_cost(batch=128, heads=12, seq=128,
                                       head_dim=64)
    seconds, bound = flops.roofline_seconds(**_fb(short["fwd"]), peaks=v5e)
    assert bound == "memory"
    assert seconds == pytest.approx(short["fwd"]["bytes"] / 819e9)


def _fb(cost):
    return {"flops": cost["flops"], "nbytes": cost["bytes"]}


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no peak numbers"):
        peaks.peaks_for("TPU v9000")
