"""Operations and bytes the models and kernels NEED, from their shapes.

These are the numerators of `mfu` and of a kernel's roofline share.
They count what the mathematics requires, not what a compiled program
happens to execute: recomputation, padding to a tile and the extra
matmuls a kernel repeats do not count, and XLA's `cost_analysis()` is
not consulted (it counts a Mosaic call as nothing).

A training step is forward x 3 (backward = 2 x forward for every
matmul and convolution).  Embedding lookups, norms, activations,
softmax and the optimizer are not counted: they are not matrix work,
and a chip's peak FLOP/s is a statement about its matrix unit.
"""

from __future__ import annotations


# ---------------------------------------------------------------------------
# BERT pretraining (MLM + NSP heads)
# ---------------------------------------------------------------------------

def bert_fwd_flops(config: dict, batch: int, seq: int, n_masked: int) -> float:
    """Forward FLOPs of one BERT pretraining batch.

    Per position and layer: the q, k, v, out projections (4 h^2 MACs),
    the two FFN matmuls (2 h i MACs), and the attention scores and
    weighted values (2 s h MACs: every position against all `seq` keys,
    padding included, as the published model computes them).  The MLM
    transform and the vocabulary projection are needed at the masked
    positions only; the pooler and NSP classifier once a sequence."""
    h = config["hidden_size"]
    inter = config["intermediate_size"]
    layers = config["num_hidden_layers"]
    vocab = config["vocab_size"]
    per_position = layers * (4 * h * h + 2 * h * inter + 2 * seq * h)
    macs = batch * seq * per_position
    macs += batch * n_masked * (h * h + h * vocab)
    macs += batch * (h * h + 2 * h)
    return 2.0 * macs


def bert_train_flops_per_token(config: dict, batch: int, seq: int,
                               n_masked: int) -> float:
    """Model FLOPs per token of one train step; a token is a sequence
    position, padding included (batch x seq a step)."""
    return 3.0 * bert_fwd_flops(config, batch, seq, n_masked) / (batch * seq)


# ---------------------------------------------------------------------------
# ResNet (He et al. 2015), from its convolution shapes
# ---------------------------------------------------------------------------

def resnet_layers(config: dict) -> list:
    """Every convolution and the classifier of a ResNet as
    `(kind, c_in, c_out, kernel, out_hw)`, walking the architecture the
    way the paper's Table 1 lays it out.  `config["stride_on"]` says
    which convolution of a bottleneck carries a stage's stride: "1x1"
    is the paper's ResNet (v1), "3x3" the widely used v1.5."""
    if config["block"] != "bottleneck":
        raise ValueError("only bottleneck ResNets are counted here")
    hw = config["image_size"]
    width = config["width"]
    out = []

    def conv(c_in, c_out, k, stride, hw_in):
        hw_out = -(-hw_in // stride)
        out.append(("conv", c_in, c_out, k, hw_out))
        return hw_out

    hw = conv(3, width, 7, 2, hw)
    hw = -(-hw // 2)                               # 3x3 max pool, stride 2
    c_in = width
    for stage, repeats in enumerate(config["stage_blocks"]):
        mid = width * 2 ** stage
        for i in range(repeats):
            stride = 2 if i == 0 and stage > 0 else 1
            on_1x1 = config["stride_on"] == "1x1"
            hw_a = conv(c_in, mid, 1, stride if on_1x1 else 1, hw)
            hw_b = conv(mid, mid, 3, 1 if on_1x1 else stride, hw_a)
            conv(mid, 4 * mid, 1, 1, hw_b)
            if c_in != 4 * mid or stride != 1:
                conv(c_in, 4 * mid, 1, stride, hw)  # projection shortcut
            c_in, hw = 4 * mid, hw_b
    out.append(("fc", c_in, config["num_classes"], 1, 1))
    return out


def resnet_fwd_macs_per_image(config: dict) -> float:
    return float(sum(c_in * c_out * k * k * hw * hw
                     for _, c_in, c_out, k, hw in resnet_layers(config)))


def resnet_train_flops_per_image(config: dict) -> float:
    return 3.0 * 2.0 * resnet_fwd_macs_per_image(config)


# ---------------------------------------------------------------------------
# the flash-attention kernels (ops/pallas/attention.py)
# ---------------------------------------------------------------------------

def flash_attention_cost(batch: int, heads: int, seq: int, head_dim: int,
                         itemsize: int = 2) -> dict:
    """FLOPs and HBM bytes self-attention needs, forward and backward,
    for `(batch, seq, heads, head_dim)` operands of `itemsize` bytes.

    Forward: Q K^T and P V, 2 matmuls of b h s^2 d MACs; reads q, k, v,
    writes o (the f32 log-sum-exp row is 1/d of that and is left out).
    Backward: dV, dP, dQ, dK plus one recomputation of Q K^T that flash
    attention trades for not storing P — 5 matmuls; reads q, k, v, o,
    do, writes dq, dk, dv.  The kernels here recompute Q K^T and dP in
    both backward calls (7 matmuls executed); the two extra are the
    kernels' cost, not the algorithm's, and are not counted."""
    matmul = 2.0 * batch * heads * seq * seq * head_dim
    operand = float(batch * heads * seq * head_dim * itemsize)
    return {"fwd": {"flops": 2 * matmul, "bytes": 4 * operand},
            "bwd": {"flops": 5 * matmul, "bytes": 8 * operand}}


def roofline_seconds(flops: float, nbytes: float, peaks: dict):
    """`(least seconds the chip could take, which bound)`: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    t_compute = flops / peaks["flops"]
    t_memory = nbytes / peaks["hbm_bps"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
