"""Operations and bytes Qwen3-Next autoregressive training NEEDS, from its
shapes — by `lib/flops.py`'s rules: matrix work the mathematics
requires, forward x 3 for a training step; no recomputation, no padding,
no dead tile and no masked part of a live one; embedding lookups, the
short convolution, norms, the gates' elementwise work, softmax, the sort
and gather of rows and the optimizer are not matrix work.

An item is one input token (batch x seq a step).  A Gated DeltaNet layer
counts its three projections (W_qkvz, W_ba, W_o) and the scan as THE
RECURRENCE'S OWN three products a token and VALUE head — the decayed
state read by k (dk dv), the rank-1 update k u^T (dk dv), the state read
by q (dk dv): 6 dk dv FLOPs forward — whatever chunk size implements it.
A full layer counts its four projections (W_q at twice the query width:
the gate) and the causal pairs, S (S + 1) / 2 a sequence and head, at
the head width for the scores and for the values.  The expert layer
counts the router, the visits that land on held experts (a measured
mean, a step), the shared expert and its gate on every row; the head the
positions that have a target (S - 1 a sequence).
"""

from __future__ import annotations

from benchmark.lib.flops_joyai import causal_pairs
from benchmark.lib.flops_laguna import _flash_cost


def layer_kinds(config: dict) -> list:
    """"gdn" | "full" for each layer the configuration runs."""
    n = config["full_attention_interval"]
    return ["full" if (i + 1) % n == 0 else "gdn"
            for i in range(config["num_hidden_layers"])]


def attn_pattern(config: dict, kind: str, scope: str = "") -> str:
    """A pattern for the scope paths under the attention sublayer of the
    layers of `kind` — `linear_attn` of "gdn", `self_attn` of "full" —
    or under its child `scope`, for the per-layer readers."""
    at = [str(i) for i, k in enumerate(layer_kinds(config)) if k == kind]
    sub = "linear_attn" if kind == "gdn" else "self_attn"
    return (r"(^|/)layers/(" + "|".join(at) + r")/" + sub
            + ("/" + scope if scope else "") + r"(/|$)")


def layers_of(config: dict, kind: str) -> int:
    return layer_kinds(config).count(kind)


def sparse_layers(config: dict) -> int:
    n = config["num_hidden_layers"]
    return sum(1 for i in range(n) if i not in config["mlp_only_layers"]
               and (i + 1) % config["decoder_sparse_step"] == 0)


def gdn_projection_macs_per_row(config: dict) -> int:
    """[q | k | v | z], [b | a] and the output projection."""
    e = config["hidden_size"]
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    key = hk * config["linear_key_head_dim"]
    value = hv * config["linear_value_head_dim"]
    return e * (2 * key + 2 * value) + e * 2 * hv + value * e


def full_projection_macs_per_row(config: dict) -> int:
    """q and its gate (2 x the query width), k, v, o."""
    e, d = config["hidden_size"], config["head_dim"]
    h, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return e * 2 * h * d + 2 * e * kv * d + h * d * e


def fwd_macs_per_step(config: dict, batch: int, seq: int,
                      held_visits: float) -> dict:
    """Forward multiply-accumulates of one step by part.  `held_visits`
    a step and expert layer."""
    e, f = config["hidden_size"], config["moe_intermediate_size"]
    rows = batch * seq
    gdn, full, sparse = (layers_of(config, "gdn"), layers_of(config, "full"),
                         sparse_layers(config))
    dense = config["num_hidden_layers"] - sparse
    return {
        "gdn_projections": gdn * rows * gdn_projection_macs_per_row(config),
        # 6 dk dv FLOPs = 3 dk dv multiply-accumulates a token and head
        "gdn_scan": gdn * rows * config["linear_num_value_heads"] * 3
        * config["linear_key_head_dim"] * config["linear_value_head_dim"],
        "full_projections": full * rows
        * full_projection_macs_per_row(config),
        "full_attention": full * batch * config["num_attention_heads"]
        * causal_pairs(seq) * 2 * config["head_dim"],
        "dense_ffn": dense * rows * 3 * e * config["intermediate_size"],
        "router": sparse * rows * e * config["router_width"],
        "experts": sparse * held_visits * 3 * e * f,
        "shared_expert": sparse * rows * e * (
            3 * config["shared_expert_intermediate_size"] + 1),
        "head": batch * (seq - 1) * e * config["vocab_size"],
    }


def train_flops_per_token(config: dict, batch: int, seq: int,
                          held_visits: float) -> float:
    macs = sum(fwd_macs_per_step(config, batch, seq, held_visits).values())
    return 3.0 * 2.0 * macs / (batch * seq)


def gdn_core_cost(batch: int, seq: int, key_heads: int, value_heads: int,
                  dk: int, dv: int, itemsize: int = 2,
                  gate_itemsize: int = 4) -> dict:
    """FLOPs and HBM bytes the scan of ONE Gated DeltaNet layer needs,
    forward and backward, whatever implements it: the recurrence's three
    products forward (6 dk dv FLOPs a token and value head), twice that
    backward; q and k at `itemsize` bytes once a KEY head, v and o once
    a value head, the decay g and beta one scalar a value head at
    `gate_itemsize` — the dtypes at `kda_attention`'s edge —, each read
    or written once forward (q, k, v, g, beta in, o out), and backward q,
    k, v, g, beta and do in, the five gradients out."""
    tokens = float(batch * seq)
    qk = tokens * key_heads * dk * itemsize
    v = tokens * value_heads * dv * itemsize
    gates = tokens * value_heads * 2 * gate_itemsize
    work = tokens * value_heads * dk * dv
    return {"fwd": {"flops": 6 * work, "bytes": 2 * qk + 2 * v + gates},
            "bwd": {"flops": 12 * work,
                    "bytes": 4 * qk + 3 * v + 2 * gates}}


def full_flash_cost(config: dict, batch: int, seq: int,
                    itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes the causal attention of ONE full layer needs,
    forward and backward, over the causal pairs; each key/value head
    read once."""
    return _flash_cost(config, batch, seq, config["num_attention_heads"],
                       causal_pairs(seq), itemsize)
