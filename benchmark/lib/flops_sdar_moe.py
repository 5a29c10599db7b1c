"""Operations and bytes SDAR-MoE block-diffusion training NEEDS, from
its shapes — by `lib/flops.py`'s rules: matrix work the mathematics
requires, forward x 3 for a training step; no recomputation, no
padding, no dead tile; embedding lookups, norms, rotations, softmax,
the sort and gather of rows and the optimizer are not matrix work.

A token is one position of x_0 (batch x seq a step).  It runs through
every layer twice (the noisy row and the clean row), so projections
and router count 2 rows a token; attention counts the LIVE pairs of
the block-diffusion mask, S^2 + B S of (2 S)^2 a sequence; the expert
layer counts the visits that land on held experts (a measured mean, a
step — the routing decides); the head counts the masked positions.
"""

from __future__ import annotations


def live_pairs(seq: int, block: int) -> int:
    """Live (row, column) pairs of the block-diffusion mask over the
    2 * seq rows of one sequence: noisy-noisy block diagonal B S,
    noisy-clean strictly lower (S^2 - B S) / 2, clean-clean block
    causal (S^2 + B S) / 2."""
    return seq * seq + block * seq


def fwd_macs_per_step(config: dict, batch: int, seq: int,
                      held_visits: float, masked: float) -> dict:
    """Forward multiply-accumulates of one step by part.  `held_visits`
    a step and expert layer, `masked` positions a step."""
    h, d = config["hidden_size"], config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    layers = config["num_hidden_layers"]
    rows = 2 * batch * seq
    block = config["assumed"]["block_length"]
    return {
        "projections": layers * rows * (2 * h * hq * d + 2 * h * hkv * d),
        "router": layers * rows * h * config["router_width"],
        "attention": layers * batch * hq * live_pairs(seq, block) * 2 * d,
        "experts": layers * held_visits * 3 * h
        * config["moe_intermediate_size"],
        "head": masked * h * config["vocab_size"],
    }


def train_flops_per_token(config: dict, batch: int, seq: int,
                          held_visits: float, masked: float) -> float:
    macs = sum(fwd_macs_per_step(config, batch, seq, held_visits,
                                 masked).values())
    return 3.0 * 2.0 * macs / (batch * seq)


def block_flash_cost(config: dict, batch: int, seq: int,
                     itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes the masked grouped-query attention of ONE
    layer needs, forward and backward: 2 and 5 matmuls (as
    `flops.flash_attention_cost`: Q K^T, P V; dV, dP, dQ, dK and one
    recomputation of Q K^T) over the live pairs only; q, o and their
    gradients at the query heads' width, k, v and theirs at the
    key/value heads' — each key/value head is read once, not once a
    query head."""
    d = config["head_dim"]
    hq, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    matmul = 2.0 * batch * hq * live_pairs(
        seq, config["assumed"]["block_length"]) * d
    rows = 2 * batch * seq
    q_like = float(rows * hq * d * itemsize)
    kv_like = float(rows * hkv * d * itemsize)
    return {"fwd": {"flops": 2 * matmul,
                    "bytes": 2 * q_like + 2 * kv_like},
            "bwd": {"flops": 5 * matmul,
                    "bytes": 4 * q_like + 4 * kv_like}}


def grouped_matmul_cost(config: dict, held_visits: float,
                        itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes the expert matmuls of ONE layer need for
    `held_visits` rows, forward (gate, up, down) and backward (the
    input and the weight gradient of each): rows in and out of every
    product once, the held experts' weights read once forward and once
    backward and their gradients written once."""
    h, f = config["hidden_size"], config["moe_intermediate_size"]
    count = config["num_experts"]
    weights = float(count * 3 * h * f * itemsize)
    per_row = (2 * h + 3 * f) * itemsize
    return {"fwd": {"flops": 2.0 * held_visits * 3 * h * f,
                    "bytes": held_visits * per_row + weights},
            "bwd": {"flops": 4.0 * held_visits * 3 * h * f,
                    "bytes": 2 * held_visits * per_row + 2 * weights}}
