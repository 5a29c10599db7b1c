"""Operations and bytes JoyAI-LLM-Flash autoregressive training with
its multi-token-prediction module NEEDS, from its shapes — by
`lib/flops.py`'s rules: matrix work the mathematics requires, forward
x 3 for a training step; no recomputation, no padding, no dead tile and
no masked half of a live one; embedding lookups, norms, rotations,
softmax, sigmoid, the sort and gather of rows, the selection bias's
update and the optimizer are not matrix work.

An item is one input token (batch x seq a step).  It runs through every
layer and through the MTP module's block once.  Attention counts the
causal pairs, S (S + 1) / 2 a sequence and head, at
`qk_nope_head_dim + qk_rope_head_dim` for the scores and `v_head_dim`
for the values; the expert layer counts the visits that land on held
experts (a measured mean, a step — the routing decides) and the shared
expert on every row; the heads count the positions that have a target
(S - 1 and S - 2 a sequence).
"""

from __future__ import annotations


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def sparse_layers(config: dict) -> int:
    """Expert layers a step runs: the main model's and the MTP block."""
    return (config["num_hidden_layers"] - config["first_k_dense_replace"]
            + config["num_nextn_predict_layers"])


def attention_layers(config: dict) -> int:
    return config["num_hidden_layers"] + config["num_nextn_predict_layers"]


def latent_projection_macs_per_row(config: dict) -> int:
    """The six projections of one latent-attention layer: q_a, q_b,
    kv_a (latent ‖ shared rotated key), kv_b, o."""
    h, heads = config["hidden_size"], config["num_attention_heads"]
    rq, rkv = config["q_lora_rank"], config["kv_lora_rank"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    return (h * rq + rq * heads * (nope + rope) + h * (rkv + rope)
            + rkv * heads * (nope + vd) + heads * vd * h)


def fwd_macs_per_step(config: dict, batch: int, seq: int,
                      held_visits: float) -> dict:
    """Forward multiply-accumulates of one step by part.  `held_visits`
    a step and expert layer."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    heads = config["num_attention_heads"]
    width = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
             + config["v_head_dim"])
    rows = batch * seq
    attn, sparse = attention_layers(config), sparse_layers(config)
    return {
        "latent_projections": attn * rows
        * latent_projection_macs_per_row(config),
        "attention": attn * batch * heads * causal_pairs(seq) * width,
        "dense_ffn": config["first_k_dense_replace"] * rows * 3 * h
        * config["intermediate_size"],
        "router": sparse * rows * h * config["router_width"],
        "experts": sparse * held_visits * 3 * h * f,
        "shared_expert": sparse * rows * 3 * h * f
        * config["n_shared_experts"],
        "mtp_projection": config["num_nextn_predict_layers"] * rows
        * 2 * h * h,
        "heads": batch * ((seq - 1) + (seq - 2)) * h * config["vocab_size"],
    }


def train_flops_per_token(config: dict, batch: int, seq: int,
                          held_visits: float) -> float:
    macs = sum(fwd_macs_per_step(config, batch, seq, held_visits).values())
    return 3.0 * 2.0 * macs / (batch * seq)


def mla_flash_cost(config: dict, batch: int, seq: int,
                   itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes the causal latent attention of ONE layer
    needs, forward and backward, over the causal pairs only: forward
    Q K^T at the q/k width and P V at the v width; backward dV and dP
    at the v width, dQ, dK and one recomputation of Q K^T at the q/k
    width (as `flops.flash_attention_cost`: 2 and 5 matmuls).  q, k and
    their gradients at the q/k width, v, o, do and dV at the v width,
    each read or written once."""
    heads = config["num_attention_heads"]
    dqk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    dv = config["v_head_dim"]
    pairs = 2.0 * batch * heads * causal_pairs(seq)
    rows = batch * seq * heads * itemsize
    return {"fwd": {"flops": pairs * (dqk + dv),
                    "bytes": float(rows * (2 * dqk + 2 * dv))},
            "bwd": {"flops": pairs * (3 * dqk + 2 * dv),
                    "bytes": float(rows * (4 * dqk + 4 * dv))}}
