"""Operations and bytes Laguna autoregressive training NEEDS, from its
shapes — by `lib/flops.py`'s rules: matrix work the mathematics
requires, forward x 3 for a training step; no recomputation, no padding,
no dead tile and no masked part of a live one; embedding lookups, norms,
the rotations, the gate's multiply, softmax, the sort and gather of rows
and the optimizer are not matrix work.

An item is one input token (batch x seq a step).  An attention layer
counts its five projections (q, k, v, the per-head gate's, o) at the
layer's own head count and the pairs its mask keeps — a full layer S (S
+ 1) / 2 a sequence and head, a window layer the band's, sum_i min(i +
1, window) — at the head width for the scores and for the values; the
dense layer its gated FFN; an expert layer the router, the visits that
land on held experts (a measured mean, a step) and the shared expert on
every row; the head the positions that have a target (S - 1 a
sequence).
"""

from __future__ import annotations

from benchmark.lib.flops_joyai import causal_pairs


def layer_kinds(config: dict) -> list:
    """"full" | "window" for each layer the configuration runs."""
    return ["window" if t == "sliding_attention" else "full"
            for t in config["layer_types"][:config["num_hidden_layers"]]]


def self_attn_pattern(config: dict, kind: str) -> str:
    """A pattern for the scope paths under the `self_attn` of the layers
    of `kind` ("full" | "window"), for the per-layer readers."""
    at = [str(i) for i, k in enumerate(layer_kinds(config)) if k == kind]
    return r"(^|/)layers/(" + "|".join(at) + r")/self_attn(/|$)"


def layers_of(config: dict, kind: str) -> int:
    return layer_kinds(config).count(kind)


def sparse_layers(config: dict) -> int:
    return config["mlp_layer_types"][:config["num_hidden_layers"]].count(
        "sparse")


def window_pairs(seq: int, window: int) -> int:
    """Pairs (i, j) with i - window < j <= i over one sequence."""
    w = min(window, seq)
    return w * (w + 1) // 2 + (seq - w) * w


def kept_pairs(config: dict, i: int, seq: int) -> int:
    return window_pairs(seq, config["sliding_window"]) \
        if layer_kinds(config)[i] == "window" else causal_pairs(seq)


def projection_macs_per_row(config: dict, i: int) -> int:
    """q, o at the layer's head count, k, v at the key/value heads', and
    the gate's hidden -> heads."""
    e, d = config["hidden_size"], config["head_dim"]
    h = config["num_attention_heads_per_layer"][i]
    return 2 * e * h * d + 2 * e * config["num_key_value_heads"] * d + e * h


def fwd_macs_per_step(config: dict, batch: int, seq: int,
                      held_visits: float) -> dict:
    """Forward multiply-accumulates of one step by part.  `held_visits`
    a step and expert layer."""
    e, f, d = (config["hidden_size"], config["moe_intermediate_size"],
               config["head_dim"])
    rows = batch * seq
    layers = range(config["num_hidden_layers"])
    kinds = layer_kinds(config)
    heads = config["num_attention_heads_per_layer"]
    sparse = sparse_layers(config)
    out = {}
    for kind in ("full", "window"):
        at = [i for i in layers if kinds[i] == kind]
        out[kind + "_projections"] = rows * sum(
            projection_macs_per_row(config, i) for i in at)
        out[kind + "_attention"] = batch * 2 * d * sum(
            heads[i] * kept_pairs(config, i, seq) for i in at)
    out.update({
        "dense_ffn": (len(layers) - sparse) * rows * 3 * e
        * config["intermediate_size"],
        "router": sparse * rows * e * config["router_width"],
        "experts": sparse * held_visits * 3 * e * f,
        "shared_expert": sparse * rows * 3 * e
        * config["shared_expert_intermediate_size"],
        "head": batch * (seq - 1) * e * config["vocab_size"],
    })
    return out


def train_flops_per_token(config: dict, batch: int, seq: int,
                          held_visits: float) -> float:
    macs = sum(fwd_macs_per_step(config, batch, seq, held_visits).values())
    return 3.0 * 2.0 * macs / (batch * seq)


def _flash_cost(config: dict, batch: int, seq: int, heads: int,
                pairs: int, itemsize: int) -> dict:
    """2 and 5 matmuls over `pairs` a head (as
    `flops.flash_attention_cost`: Q K^T, P V; dV, dP, dQ, dK and one
    recomputation of Q K^T); q, o and their gradients at the query
    heads' width, k, v and theirs at the key/value heads' — each
    key/value head read once, not once a query head."""
    d = config["head_dim"]
    matmul = 2.0 * batch * heads * pairs * d
    rows = batch * seq
    q_like = float(rows * heads * d * itemsize)
    kv_like = float(rows * config["num_key_value_heads"] * d * itemsize)
    return {"fwd": {"flops": 2 * matmul, "bytes": 2 * q_like + 2 * kv_like},
            "bwd": {"flops": 5 * matmul, "bytes": 4 * q_like + 4 * kv_like}}


def _heads_of(config: dict, kind: str) -> int:
    return config["num_attention_heads_per_layer"][
        layer_kinds(config).index(kind)]


def window_flash_cost(config: dict, batch: int, seq: int,
                      itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes the sliding-window attention of ONE layer
    needs, forward and backward, over the band's pairs only."""
    return _flash_cost(config, batch, seq, _heads_of(config, "window"),
                       window_pairs(seq, config["sliding_window"]), itemsize)


def full_flash_cost(config: dict, batch: int, seq: int,
                    itemsize: int = 2) -> dict:
    """The same of ONE full-attention layer, over the causal pairs."""
    return _flash_cost(config, batch, seq, _heads_of(config, "full"),
                       causal_pairs(seq), itemsize)


def kernel_roofline(run, kind: str):
    """The share of their roofline, in %, of the kernels whose kind
    (benchmark/configs/laguna.py: _kernel_calls) and whose entry in the
    system's `kernels` start with `kind`: the least time the chip could
    take for their work over their time in the device trace.  None
    where there is no trace, no such kernel or no such cost."""
    from benchmark.lib import flops

    t = run.trace
    if not t or run.peaks is None:
        return None
    seconds = sum(s for k, s in t["kernel_s"].items() if k.startswith(kind))
    costs = [c for k, c in getattr(run.system, "kernels", {}).items()
             if k.startswith(kind)]
    if not seconds or not costs:
        return None
    least = sum(flops.roofline_seconds(c["flops"], c["bytes"], run.peaks)[0]
                for c in costs)
    return 100.0 * least * t["steps"] / seconds
