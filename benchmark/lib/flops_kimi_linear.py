"""Operations and bytes Kimi Linear autoregressive training NEEDS, from
its shapes — by `lib/flops.py`'s rules: matrix work the mathematics
requires, forward x 3 for a training step; no recomputation, no padding,
no dead tile; embedding lookups, the short convolutions, norms, the
gates' elementwise work, sigmoid, the sort and gather of rows, the
selection bias's update and the optimizer are not matrix work.

An item is one input token (batch x seq a step).  A KDA layer counts its
eleven projections and the scan as THE RECURRENCE'S OWN three products
a token and head — the decayed state read by k (dk dv), the rank-1
update k u^T (dk dv), the state read by q (dk dv): 6 dk dv FLOPs forward
— whatever chunk size implements it: a chunked form does more
arithmetic (the chunk-local scores, the triangular solve) to do it on
matrix units, and that is its cost, not the algorithm's.  The latent
layer counts its four projections and the causal pairs, S (S + 1) / 2 a
sequence and head, at `qk_nope_head_dim + qk_rope_head_dim` for the
scores and `v_head_dim` for the values; the expert layer the visits
that land on held experts (a measured mean, a step) and the shared
expert on every row; the head the positions that have a target (S - 1 a
sequence).
"""

from __future__ import annotations

from benchmark.lib.flops_joyai import causal_pairs


def layer_kinds(config: dict) -> list:
    """"kda" | "mla" for each layer the configuration runs (the
    published lists number from 1)."""
    lists = config["linear_attn_config"]
    return ["kda" if i + 1 in lists["kda_layers"] else "mla"
            for i in range(config["num_hidden_layers"])]


# the scope all of a KDA layer's scan runs under, XLA part and kernels
KDA_CORE = r"(^|/)kda_core(/|$)"


def self_attn_pattern(config: dict, kind: str) -> str:
    """A pattern for the scope paths under the `self_attn` of the
    layers of `kind` ("kda" | "mla"), for the per-layer readers."""
    at = [str(i) for i, k in enumerate(layer_kinds(config)) if k == kind]
    return r"(^|/)layers/(" + "|".join(at) + r")/self_attn(/|$)"


def kda_layers(config: dict) -> int:
    return layer_kinds(config).count("kda")


def latent_layers(config: dict) -> int:
    return layer_kinds(config).count("mla")


def sparse_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def kda_projection_macs_per_row(config: dict) -> int:
    """q, k, v, o; the two low-rank gate paths; beta."""
    e = config["hidden_size"]
    lin = config["linear_attn_config"]
    d, width = lin["head_dim"], lin["num_heads"] * lin["head_dim"]
    return 4 * e * width + 2 * (e * d + d * width) + e * lin["num_heads"]


def latent_projection_macs_per_row(config: dict) -> int:
    """q (no latent), kv_a (latent ‖ shared key part), kv_b, o."""
    e, heads = config["hidden_size"], config["num_attention_heads"]
    rkv = config["kv_lora_rank"]
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    return (e * heads * (nope + rope) + e * (rkv + rope)
            + rkv * heads * (nope + vd) + heads * vd * e)


def fwd_macs_per_step(config: dict, batch: int, seq: int,
                      held_visits: float) -> dict:
    """Forward multiply-accumulates of one step by part.  `held_visits`
    a step and expert layer."""
    e, f = config["hidden_size"], config["moe_intermediate_size"]
    lin = config["linear_attn_config"]
    rows = batch * seq
    kda, mla, sparse = (kda_layers(config), latent_layers(config),
                        sparse_layers(config))
    return {
        "kda_projections": kda * rows * kda_projection_macs_per_row(config),
        # 6 dk dv FLOPs = 3 dk dv multiply-accumulates a token and head
        "kda_scan": kda * rows * lin["num_heads"] * 3 * lin["head_dim"] ** 2,
        "latent_projections": mla * rows
        * latent_projection_macs_per_row(config),
        "attention": mla * batch * config["num_attention_heads"]
        * causal_pairs(seq) * (config["qk_nope_head_dim"]
                               + config["qk_rope_head_dim"]
                               + config["v_head_dim"]),
        "dense_ffn": config["first_k_dense_replace"] * rows * 3 * e
        * config["intermediate_size"],
        "router": sparse * rows * e * config["router_width"],
        "experts": sparse * held_visits * 3 * e * f,
        "shared_expert": sparse * rows * 3 * e * f
        * config["num_shared_experts"],
        "head": batch * (seq - 1) * e * config["vocab_size"],
    }


def train_flops_per_token(config: dict, batch: int, seq: int,
                          held_visits: float) -> float:
    macs = sum(fwd_macs_per_step(config, batch, seq, held_visits).values())
    return 3.0 * 2.0 * macs / (batch * seq)


def kda_core_cost(batch: int, seq: int, heads: int, dk: int, dv: int,
                  itemsize: int = 2, gate_itemsize: int = 4) -> dict:
    """FLOPs and HBM bytes the scan of ONE KDA layer needs, forward and
    backward, whatever implements it: the recurrence's three products
    forward (6 dk dv FLOPs a token and head), twice that backward; q,
    k, v and o at `itemsize` bytes, the decay g (dk wide) and beta at
    `gate_itemsize` — the dtypes at `kda_attention`'s edge — each read
    or written once forward (q, k, v, g, beta in, o out), and backward
    q, k, v, g, beta and do in, the five gradients out."""
    tokens = float(batch * seq * heads)
    qk, v = tokens * dk * itemsize, tokens * dv * itemsize
    gates = tokens * (dk + 1) * gate_itemsize
    return {"fwd": {"flops": tokens * 6 * dk * dv,
                    "bytes": 2 * qk + 2 * v + gates},
            "bwd": {"flops": tokens * 12 * dk * dv,
                    "bytes": 4 * qk + 3 * v + 2 * gates}}
