"""Plain reference for Laguna autoregressive training: forward pass, the
next-token loss and `jax.grad` of it.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`: attention as a masked
softmax whose mask — causal, or the band of a sliding window — is built
from row and key indices, a head at a time and a block of query rows at
a time so that 16,384 rows fit; its own YaRN frequencies and its own
rotation; its own softmax router; a loop over the experts held.  No
kernel, no visit plan, no recomputation but where `remat` asks (for
`grads` at the timed sizes).  It imports nothing of `paddle_tpu/`; it
is fed the system's own seeded weights under the system's parameter
names; a Linear weight there is (in, out).

`x` (B, S, 2048).  Layer i, pre-norm, RMSNorm eps 1e-6 with a learned
scale, no biases:

    a = RMSNorm(x; g1)
    H = num_attention_heads_per_layer[i], 8 key/value heads of 128
    q = a Wq (-> H x 128), k = a Wk, v = a Wv (-> 8 x 128)
    g = sigmoid(a Wg)                                   (-> H), float32
    query head j reads key/value head j // (H / 8)
    o_j = softmax(R(q_j) R(k)^T / sqrt(128) + mask) v
    x = x + concat_j(g_j o_j) Wo
  layer_types[i] == "full_attention":  mask j <= i; R rotates lanes
    0..63 (rotate-half within those 64) and passes lanes 64..127; YaRN
    (arXiv:2309.00071) as `transformers` computes `rope_type: yarn`:
    dim 64, base 5e5, f_p = base^(-2p / dim), c(r) = dim ln(4096 /
    (2 pi r)) / (2 ln base), low = floor(c(64)) = 5, high = ceil(c(1))
    = 16, ramp_p = clip((p - low) / (high - low), 0, 1), inv_p = f_p
    ((1 - ramp_p) + ramp_p / 64); cos and sin times 1.4158883083359672
  layer_types[i] == "sliding_attention":  mask i - 512 < j <= i; R the
    plain rotation of all 128 lanes, theta 1e4
    b = RMSNorm(x; g2)
  mlp_layer_types[i] == "dense":   x = x + (SiLU(b Wg) * (b Wu)) Wd
  "sparse":  s = softmax(b W_r) over 256, float32;  I = top-8(s)
             w_i = 2.5 s_i / sum_{j in I} s_j
             x = x + sum_{i in I, i held} w_i FFN_i(b) + FFN_shared(b)
    h = RMSNorm(x_L; gf);   L = CE(t_{i+1} | h_i W_head), i < S - 1

Departures from the published description, each also the system's:

* a chip's share (`experts_held`, a vocabulary slice) and GIVEN
  routing, as benchmark/reference/joyai_flash.py sets out;
* not in `config.json`, assumed (the configuration file lists them
  with reasons): the router's softmax and its renormalisation, the
  gate's form (per head, after the attention, sigmoid, from the layer's
  normed input), SiLU, no QK norm, no auxiliary balance loss.

Control readings (benchmark/tests/precision_readings_laguna.py; each
has to come out as not correct): `operand_dtype` (every matmul operand
rounded), `rope_angle_dtype` (the angles position x inv_freq rounded
before cos and sin), `gate_dtype` (the gate's logits and its sigmoid
rounded), `router_dtype` (the router's scores rounded),
`window_lower_bound: False` (a window layer that sees every key j <=
i).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.joyai_flash import (  # noqa: F401
    _f32, _gated_ffn, _r, _rms_norm, rel_rms, routing_agreement)
from benchmark.reference.kimi_linear import _ce_in_row_chunks

_ROW_BLOCK = 1024

# Tolerances of the comparison that decides `correct`: the system (bf16
# activations over float32 master weights; the flash kernels, a window
# as a band their grids walk; rotation angles, the gate's sigmoid and
# the router's softmax in float32; grouped matmuls) against this file on
# the chip, at the timed sizes.  Each limit lies between two readings
# (my chip runs, PR 38; PERF.md §6): the largest the system gave over
# its seeds, and what this file gives against itself with every matmul
# operand rounded to float8_e4m3fn (`operand_dtype`), put through the
# same `compare` / `compare_gradients` by
# benchmark/tests/precision_readings_laguna.py; the other controls'
# readings are beside them.  fp8 fails by every limit but the loss's; a
# rotation whose angles are bfloat16, and a window without its lower
# bound, by the logits and by the gradients of the attention leaves
# (0.35–0.89 where the limits are 0.024–0.042).  A bfloat16 gate reads
# 0.00005 on the logits and at most 0.0017 on a gradient, a bfloat16
# router 0.0073 on the near-tie distance: both UNDER what the system's
# own bfloat16 activations read, so no limit here can tell them apart
# and the builder asks the executable instead (`gates_in_float32`,
# `routers_choose_in_float32`).
#
# LOGITS: relative RMS difference of the logits at the probed positions.
# System 0.00298 to 0.00299; fp8 operands 0.054; bfloat16 angles 0.0073;
# no lower bound 0.0109; this file with bfloat16 operands 0.0024.
# LOGITS_FLOOR as in benchmark/reference/joyai_flash.py: under it the
# system did not compute in bfloat16 as the configuration says.
LOGITS_TOLERANCE = 0.006
LOGITS_FLOOR = 1e-4
# LOSS: relative difference of the cross-entropy (system 8e-7, fp8
# 6e-5); a weak witness of precision and a strong one of the objective
# (the shift, the position left out, the divisor), held to the accepted
# cells' 2e-3.
LOSS_TOLERANCE = 2e-3
# GRADIENTS: relative L2 difference of each named leaf's gradient — the
# timed step's own, read from Adam's first moment — a limit a leaf (the
# key ends the leaf's name, with the layer's kind where both kinds have
# the leaf: `compare_gradients` is given the kinds), each near the
# geometric mean of the readings it lies between.  System, largest of
# its seeds | fp8 operands | bfloat16 angles | no lower bound | (this
# file with bfloat16 operands):
GRAD_TOLERANCE = {
    "window.self_attn.g_proj.weight": 0.042,    # 0.0084 | 0.21 | 0.53 | 0.86 (0.0055)
    "full.self_attn.g_proj.weight": 0.03,       # 0.0090 | 0.40 | 0.35 | 0.035 (0.0063)
    "window.self_attn.q_proj.weight": 0.026,    # 0.0093 | 0.073 | 0.83 | 0.89 (0.0060)
    "full.self_attn.q_proj.weight": 0.028,      # 0.0098 | 0.083 | 0.50 | 0.017 (0.0068)
    "full.self_attn.k_proj.weight": 0.024,      # 0.0101 | 0.057 | 0.50 | 0.017 (0.0069)
    "moe.gate_weight": 0.05,                    # 0.0096 | 0.27 | 0.015 | 0.018 (0.0052)
    "moe.w_down": 0.027,                        # 0.0086 | 0.086 | 0.012 | 0.018 (0.0046)
    "shared_experts.down_proj.weight": 0.024,   # 0.0073 | 0.077 | 0.011 | 0.016 (0.0042)
    "embed_tokens.weight": 0.018,               # 0.0062 | 0.056 | 0.016 | 0.017 (0.0018)
}
# A (row, slot) choice that differs from this file's own top-k must be a
# near-tie: this file's softmax score of the system's pick within this
# relative distance of its own k-th largest.  Over the 5.2e5 choices of
# a comparison the system's largest read 0.020 to 0.026 (0.6 to 0.7% of
# the picks differ: a softmax score moves by its logit's absolute
# error, and the rows reach the router through bfloat16 layers); fp8
# operands 0.19; bfloat16 angles 0.035, no lower bound 0.047; this
# file's own router with bfloat16 operands 0.0094, with bfloat16 scores
# 0.0073.  The limit is of a maximum, which a fresh seed can read
# higher: near the geometric mean of 0.026 and 0.19.
NEAR_TIE = 0.07


def yarn_inverse_frequencies(rope: dict, dim: int) -> np.ndarray:
    """(dim / 2,) float32: `rope_type: yarn` as the `transformers`
    library computes it (`_compute_yarn_parameters`, truncated
    correction range)."""
    base, factor = float(rope["rope_theta"]), float(rope["factor"])
    orig = rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    pair = np.arange(dim // 2, dtype=np.float64)
    extrapolated = base ** (-2.0 * pair / dim)
    ramp = np.clip((pair - low) / (high - low), 0.0, 1.0)
    return (extrapolated * (1.0 - ramp)
            + extrapolated / factor * ramp).astype(np.float32)


def _rotation(cfg, rope: dict, head_dim: int, seq: int):
    """(cos, sin (S, dim / 2), dim) of one layer kind: the rotated
    width, the angles' cos and sin times the amplitude."""
    dim = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    if rope["rope_type"] == "yarn":
        inv = yarn_inverse_frequencies(rope, dim)
        amplitude = rope.get("attention_factor") or (
            0.1 * math.log(rope["factor"]) + 1.0)
    else:
        inv = (float(rope["rope_theta"]) ** (
            -2.0 * np.arange(dim // 2, dtype=np.float64) / dim)).astype(
            np.float32)
        amplitude = 1.0
    angles = jnp.arange(seq, dtype=jnp.float32)[:, None] * jnp.asarray(inv)
    if cfg.get("rope_angle_dtype"):
        info = jnp.finfo(cfg["rope_angle_dtype"])
        angles = jax.lax.reduce_precision(angles, info.nexp, info.nmant)
    return jnp.cos(angles) * amplitude, jnp.sin(angles) * amplitude, dim


def _rotate(x, cos, sin, dim):
    """x (B, S, H, D): lanes [0, dim) rotated, rotate-half within them,
    the others as they are."""
    half = dim // 2
    x1, x2, rest = x[..., :half], x[..., half:dim], x[..., dim:]
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attention(cfg, p, pre, x, i, remat=False):
    b, s, _ = x.shape
    heads = cfg["num_attention_heads_per_layer"][i]
    kv_heads, d = cfg["num_key_value_heads"], cfg["head_dim"]
    kind = cfg["layer_types"][i]
    window = cfg["sliding_window"] if kind == "sliding_attention" \
        and cfg.get("window_lower_bound", True) else None
    x = _r(cfg, x)
    proj = lambda name, n: (x @ _r(cfg, p[pre + name + ".weight"])).reshape(
        b, s, n, -1)
    q, k, v = proj("q_proj", heads), proj("k_proj", kv_heads), proj(
        "v_proj", kv_heads)
    gate = x @ _r(cfg, p[pre + "g_proj.weight"])            # (B, S, H)
    if cfg.get("gate_dtype"):
        info = jnp.finfo(cfg["gate_dtype"])
        low = lambda a: a + jax.lax.stop_gradient(jax.lax.reduce_precision(
            a, info.nexp, info.nmant) - a)
        gate = low(jax.nn.sigmoid(low(gate)))
    else:
        gate = jax.nn.sigmoid(gate)
    cos, sin, dim = _rotation(cfg, cfg["rope_parameters"][kind], d, s)
    q, k = _r(cfg, _rotate(q, cos, sin, dim)), _r(cfg, _rotate(k, cos, sin,
                                                               dim))
    v = _r(cfg, v)
    rows = min(_ROW_BLOCK, s)
    pad = -s % rows
    # a window layer's row block reads the keys it can see and no
    # others: `span` keys from `first - back`
    back = 0 if window is None else window - 1
    span = s + pad if window is None else rows + back

    def head(j):
        q_j = q[:, :, j]
        k_j, v_j = (jnp.pad(a[:, :, j // (heads // kv_heads)],
                            ((0, 0), (back, pad), (0, 0))) for a in (k, v))
        q_b = jnp.moveaxis(jnp.pad(q_j, ((0, 0), (0, pad), (0, 0))).reshape(
            b, -1, rows, d), 1, 0)

        def block(a):           # a block of query rows against its keys
            q_rows, first = a
            start = first if window is not None else 0
            keys = jax.lax.dynamic_slice_in_dim(k_j, start, span, axis=1)
            vals = jax.lax.dynamic_slice_in_dim(v_j, start, span, axis=1)
            at_row = (first + jnp.arange(rows))[:, None]
            at_key = (start - back + jnp.arange(span))[None, :]
            seen = (at_key <= at_row) & (at_key >= 0)
            if window is not None:
                seen &= at_key > at_row - window
            scores = jnp.einsum("bqd,bkd->bqk", q_rows, keys) / np.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", _r(cfg, probs), vals)

        out = jax.lax.map(jax.checkpoint(block) if remat else block,
                          (q_b, jnp.arange(q_b.shape[0]) * rows))
        return jnp.moveaxis(out, 0, 1).reshape(b, -1, d)[:, :s]

    out = jax.lax.map(jax.checkpoint(head) if remat else head,
                      jnp.arange(heads))                    # (H, B, S, D)
    out = out.transpose(1, 2, 0, 3) * gate[..., None]
    return _r(cfg, out.reshape(b, s, heads * d)) @ _r(
        cfg, p[pre + "o_proj.weight"])


def route(cfg, wr, x, given=None):
    """x (T, H) -> (experts (T, k), weights (T, k), scores (T,
    num_experts)): softmax over all experts in float32, the top-k (or
    the `given` indices), their scores divided by their sum, times the
    scaling factor."""
    scores = jax.nn.softmax(_r(cfg, x) @ _r(cfg, wr), axis=-1)
    if cfg.get("router_dtype"):         # a control reading
        info = jnp.finfo(cfg["router_dtype"])
        scores = jax.lax.reduce_precision(scores, info.nexp, info.nmant)
    experts = given if given is not None else jax.lax.top_k(
        scores, cfg["num_experts_per_tok"])[1]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    weights = weights / weights.sum(-1, keepdims=True)
    return experts, weights * cfg["moe_routed_scaling_factor"], scores


def moe_layer(cfg, p, pre, x, held, given=None, remat=False):
    """The expert layer's output for rows x (T, H): the part the routed
    experts `held = (first, count)` give, plus the shared expert every
    row passes.  -> (out, experts, scores)."""
    experts, weights, scores = route(cfg, p[pre + "gate_weight"], x, given)
    first, count = held
    x = _r(cfg, x)

    def expert(args):           # one held expert, on the rows that chose it
        e, wg, wu, wd = args
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        return w_e[:, None] * _gated_ffn(cfg, x, wg, wu, wd)

    out = jnp.sum(jax.lax.map(
        jax.checkpoint(expert) if remat else expert,
        (jnp.arange(count), p[pre + "w_gate"], p[pre + "w_up"],
         p[pre + "w_down"])), axis=0)
    shared = pre + "shared_experts."
    return out + _gated_ffn(cfg, x, p[shared + "gate_proj.weight"],
                            p[shared + "up_proj.weight"],
                            p[shared + "down_proj.weight"]), experts, scores


def _layer(cfg, p, i, x, held, given, remat=False):
    pre, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    x = x + _attention(
        cfg, p, pre + "self_attn.",
        _rms_norm(x, p[pre + "input_layernorm.weight"], eps), i, remat)
    h = _rms_norm(x, p[pre + "post_attention_layernorm.weight"], eps)
    if cfg["mlp_layer_types"][i] == "dense":
        return x + _gated_ffn(
            cfg, _r(cfg, h), p[pre + "mlp.gate_proj.weight"],
            p[pre + "mlp.up_proj.weight"],
            p[pre + "mlp.down_proj.weight"]), None, None
    b, s, hid = h.shape
    out, experts, scores = moe_layer(
        cfg, p, pre + "moe.", h.reshape(-1, hid), held, given, remat)
    return x + out.reshape(b, s, hid), experts, scores


_KEYS = ("num_hidden_layers", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "num_experts_per_tok", "moe_routed_scaling_factor",
         "sliding_window", "rope_parameters", "layer_types",
         "mlp_layer_types", "num_attention_heads_per_layer",
         "experts_held", "router_width", "operand_dtype", "rope_angle_dtype",
         "gate_dtype", "router_dtype", "window_lower_bound")


def _key(cfg) -> str:
    """The configuration as a hashable static argument."""
    return json.dumps({k: cfg[k] for k in _KEYS if k in cfg}, sort_keys=True)


def _held(cfg):
    held = cfg.get("experts_held")
    return tuple(held) if held else (0, cfg["router_width"])


def _run(cfg, p, batch, routing, remat, probe):
    """-> (loss, (logits at `probe` (B, len(probe), V), experts [(T, k)]
    and scores [(T, n)] of every expert layer))."""
    with jax.default_matmul_precision("highest"):
        ids = batch["input_ids"]
        seq = ids.shape[1]
        held = _held(cfg)
        given = iter(routing) if routing is not None else None
        experts, scores = [], []
        x = p["model.embed_tokens.weight"][ids]
        for i in range(cfg["num_hidden_layers"]):
            sparse = cfg["mlp_layer_types"][i] == "sparse"
            g = next(given) if (given is not None and sparse) else None
            f = lambda p, x, g, i=i: _layer(cfg, p, i, x, held, g, remat)
            x, e, c = (jax.checkpoint(f) if remat else f)(p, x, g)
            if e is not None:
                experts.append(e)
                scores.append(c)
        h = _rms_norm(x, p["model.norm.weight"], cfg["rms_norm_eps"])
        head = p["lm_head.weight"]
        loss = _ce_in_row_chunks(
            cfg, h, head, jnp.roll(ids, -1, axis=1),
            jnp.broadcast_to(jnp.arange(seq)[None, :] < seq - 1, ids.shape),
            remat)
        logits = _r(cfg, h[:, np.asarray(probe)]) @ _r(cfg, head)
        return loss, (logits, experts, scores)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _forward(key, params, batch, routing, remat, probe):
    return _run(json.loads(key), params, batch, routing, remat, probe)


def forward(config: dict, params: dict, batch: dict, routing=None,
            probe=None):
    """`batch`: input_ids (B, S) int32.  `routing`: per expert layer (T,
    k) expert indices to use, T = B * S.  `probe`: the positions whose
    logits to return (default: all).  -> {"loss", "logits" (B, probe,
    V), "experts", "choose_by"} in float32."""
    seq = batch["input_ids"].shape[1]
    probe = tuple(range(seq)) if probe is None else tuple(
        int(i) for i in probe)
    loss, (logits, experts, scores) = _forward(
        _key(config), _f32(params), batch, routing, False, probe)
    return {"loss": loss, "ce": loss, "logits": logits, "experts": experts,
            "choose_by": scores}


@functools.partial(jax.jit, static_argnums=(0, 5))
def _grads(key, leaves, rest, batch, routing, remat):
    return jax.grad(lambda l: _run(json.loads(key), {**rest, **l}, batch,
                                   routing, remat, (0,))[0])(leaves)


def grads(config: dict, params: dict, batch: dict, routing=None,
          wrt=None, remat=False):
    """`jax.grad` of the loss with respect to the leaves named in `wrt`
    (default: all), as a dict."""
    params = _f32(params)
    names = list(params) if wrt is None else list(wrt)
    return _grads(_key(config), {k: params[k] for k in names},
                  {k: v for k, v in params.items() if k not in names},
                  batch, routing, remat)


def _limit_key(config: dict, name: str) -> str:
    """The leaf's name with its layer's kind in place of the layer's
    path: "window.self_attn.q_proj.weight"."""
    parts = name.split(".")
    if parts[:2] != ["model", "layers"]:
        return name
    kind = "window" if config["layer_types"][int(parts[2])] \
        == "sliding_attention" else "full"
    return kind + "." + ".".join(parts[3:])


def compare_gradients(config: dict, got: dict, want: dict) -> dict:
    """Gradients `got` against the reference's `want`, leaf by leaf:
    relative L2 difference, each under the limit of GRAD_TOLERANCE
    whose key ends the leaf's name (`_limit_key`).  A reading that is
    not finite fails."""
    rel, limit = {}, {}
    for name, b in want.items():
        a, b = np.asarray(got[name], np.float32), np.asarray(b, np.float32)
        rel[name] = float(np.linalg.norm(a - b)
                          / max(float(np.linalg.norm(b)), 1e-30))
        limit[name] = next(v for k, v in GRAD_TOLERANCE.items()
                           if _limit_key(config, name).endswith(k))
    return {"ok": all(math.isfinite(rel[k]) and rel[k] < limit[k]
                      for k in rel),
            "rel_l2": rel, "limit": limit}


def compare(got: dict, want: dict) -> dict:
    """System against reference: `got` and `want` hold "ce" (a float)
    and "logits" (arrays of the same shape, at the probed positions)."""
    diff = rel_rms(got["logits"], want["logits"])
    ce = abs(got["ce"] - want["ce"]) / abs(want["ce"])
    return {"ok": bool(LOGITS_FLOOR < diff < LOGITS_TOLERANCE
                       and ce < LOSS_TOLERANCE),
            "logits_rel_rms": diff, "ce_rel": ce, "ce": got["ce"],
            "reference_ce": want["ce"]}
