"""Plain reference for Qwen3-Next autoregressive training: forward pass,
the next-token loss and `jax.grad` of it.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`: the Gated DeltaNet
recurrence ITSELF, a token at a time (`lax.scan` over t; no chunk, no
cumulated gate, no triangular solve, no kernel), the short convolution
as an explicit sum over its taps, attention as a causal softmax a head
and a block of query rows at a time, its own rotation, its own softmax
router, a loop over the experts held.  It imports nothing of
`paddle_tpu/`; it is fed the system's own seeded weights under the
system's parameter names; a Linear weight there is (in, out).

    N(x; w) = x rsqrt(mean(x^2) + 1e-6) (1 + w)            zero-centred
    a = N(x; g1);  x = x + Mixer_i(a);  b = N(x; g2);  x = x + MoE(b)
  Gated DeltaNet (layers 0, 1, 2 of each 4), 16 query/key heads of 128,
  32 value heads of 128, value head h reading key head h // 2:
    [q~ | k~ | v~ | z] = a W_qkvz;  [b | a'] = a W_ba
    q', k', v = SiLU(Conv([q~ | k~ | v~]))
                Conv(y)_t = sum_{i<4} taps[i] y_{t-3+i}, zeros before 0
    q, k  = q' rsqrt(|q'|^2 + 1e-6), k' rsqrt(|k'|^2 + 1e-6)
    beta  = sigmoid(b)[h];  g = -exp(A_log[h]) softplus(a'[h] + dt_bias[h])
    S_t   = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t   = 128^-1/2 S_t^T q_t
    out   = [RMSNorm_head(o_t) w_o * SiLU(z_t)] W_o            (w_o from 1)
  gated attention (layer 3 of each 4), 16 query heads over 2 of 256:
    [q_j | gate_j] = (a W_q)_j (512 a head);  k, v = a W_k, a W_v
    q_j, k = N(q_j; w_q), N(k; w_k) over 256 lanes
    R: rotate-half on lanes 0..63 (pairs (i, i + 32)), theta 1e7,
       lanes 64..255 untouched
    o_j = softmax(tril(R(q_j) R(k_{j // 8})^T / 16)) v_{j // 8}
    out = concat_j(o_j * sigmoid(gate_j)) W_o
  experts, every layer:
    s = softmax(b W_r) over 512, float32;  I = top-10(s)
    w_i = s_i / sum_{j in I} s_j
    MoE(b) = sum_{i in I, i held} w_i FFN_i(b) + sigmoid(b w_sg) FFN_sh(b)
    h = N(x_L; gf);   L = CE(t_{i+1} | h_i W_head), i < S - 1

Departures from the published description, each also the system's:

* a chip's share (`experts_held`, a vocabulary slice) and GIVEN
  routing, as benchmark/reference/joyai_flash.py sets out;
* no auxiliary balance loss and no multi-token-prediction module;
* the projections' columns in blocks [q | k | v | z] and [b | a] where
  the released code interleaves them by key head (a permutation of
  seeded weights: the same model);
* the configuration file's `assumed`: the norms' (1 + w) form, A_log's
  and dt_bias's initialisation, the taps', the gate's split of the
  query projection.

For sizes that do not fit at once: the scan is an outer scan over
blocks of `SCAN_BLOCK` tokens around an inner one, the inner one under
`jax.checkpoint` where `remat` (the arithmetic is the same); attention
walks the heads one at a time and a head's query rows in blocks; the
loss is taken in row chunks and logits exist at the probed positions
alone.

Control readings (benchmark/tests/precision_readings_qwen3_next.py;
each has to come out as not correct): `operand_dtype` float8_e4m3fn
(every matmul operand rounded), `gate_cumsum_dtype` bfloat16 (the decay
of token t taken from the gate cumulated over chunks of 64 and rounded:
what a chunked scan that keeps G in bfloat16 computes).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.joyai_flash import (  # noqa: F401
    _f32, _gated_ffn, _r, rel_rms, routing_agreement)
from benchmark.reference.kimi_linear import (_ce_in_row_chunks,
                                             _round_cumulated_gate,
                                             _short_conv_silu)

SCAN_BLOCK = 128
_ROW_BLOCK = 1024

# Tolerances of the comparison that decides `correct`: the system (bf16
# activations over float32 master weights; the chunked scan in float32
# with bfloat16 q, k, v, o at its edge and the decay a head; the causal
# flash kernels at head width 256; grouped matmuls) against this file on
# the chip, at the timed sizes.  Each limit lies between two readings
# (TPU v5e; PERF.md §6): the largest the system gave over its
# six seeds, and what this file gives against itself with every matmul
# operand rounded to float8_e4m3fn (`operand_dtype`), put through the
# same `compare` / `compare_gradients` by
# benchmark/tests/precision_readings_qwen3_next.py.  fp8 fails by every
# limit but the loss's; the cumulated decay kept in bfloat16
# (`gate_cumsum_dtype`) fails by the decay's own leaves (A_log 1.20,
# in_proj_ba 0.38) and by the convolution's and W_qkvz's (0.053).
#
# LOGITS: relative RMS difference of the logits at the probed positions.
# System 0.00299 to 0.00301; fp8 operands 0.050; a bfloat16 decay 0.0038;
# this file with bfloat16 operands 0.0024.  LOGITS_FLOOR as in
# benchmark/reference/joyai_flash.py: under it the system did not
# compute in bfloat16 as the configuration says.
LOGITS_TOLERANCE = 0.012
LOGITS_FLOOR = 1e-4
# LOSS: relative difference of the cross-entropy (system at most 3.1e-6,
# fp8 3.2e-5); a weak witness of precision and a strong one of the
# objective (the shift, the position left out, the divisor), held to the
# accepted cells' 2e-3.
LOSS_TOLERANCE = 2e-3
# GRADIENTS: relative L2 difference of each named leaf's gradient — the
# timed step's own, read from Adam's first moment — a limit a leaf (the
# key ends the leaf's name), each near the geometric mean of the
# readings it lies between.  System, largest of its seeds | fp8
# operands | a bfloat16 decay | (this file with bfloat16 operands).
# A_log's reading wanders most over seeds (0.0032 to 0.018): its
# gradient is a sum over tokens, sum_t dg_t g_t, that nearly cancels.
GRAD_TOLERANCE = {
    "linear_attn.A_log": 0.06,              # 0.018 | 0.230 | 1.20 | (0.0036)
    "linear_attn.in_proj_ba.weight": 0.06,  # 0.0129 | 0.296 | 0.380 | (0.0068)
    "linear_attn.conv1d.weight": 0.05,      # 0.0124 | 0.298 | 0.053 | (0.0067)
    "linear_attn.in_proj_qkvz.weight": 0.05,  # 0.0117 | 0.293 | 0.053 | (0.0062)
    "self_attn.q_proj.weight": 0.04,        # 0.0090 | 0.205 | 0.0070 | (0.0058)
    "moe.shared_expert_gate.weight": 0.045,  # 0.0080 | 0.290 | 0.0070 | (0.0049)
    "moe.w_down": 0.025,                    # 0.0084 | 0.081 | 0.0066 | (0.0046)
    "moe.gate_weight": 0.05,                # 0.0091 | 0.288 | 0.0102 | (0.0052)
}
# A (row, slot) choice that differs from this file's own top-k must be a
# near-tie: this file's softmax score of the system's pick within this
# relative distance of its own k-th largest.  Over the 6.6e5 choices of a
# comparison the system's largest read 0.020 to 0.026 (0.57 to 0.59% of
# the picks differ); fp8 operands 0.176; a bfloat16 decay 0.060; this
# file with bfloat16 operands 0.0099.  The limit is of a maximum, which a
# fresh seed can read higher: near the geometric mean of 0.026 and 0.176.
NEAR_TIE = 0.07


def _norm0(x, weight, eps):
    """The zero-centred RMSNorm: x rsqrt(mean(x^2) + eps) (1 + w)."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + weight)


def gated_delta_rule(q, k, v, g, beta, scale, remat=False):
    """The recurrence, a token at a time, a decay a head.  q, k (B, S,
    Hk, dk), v (B, S, Hv, dv), g, beta (B, S, Hv) -> o (B, S, Hv, dv);
    value head h reads key head h // (Hv / Hk)."""
    b, s, h, dk = v.shape[:3] + q.shape[-1:]
    group = h // q.shape[2]
    pad = -s % SCAN_BLOCK
    blocks = lambda a: jnp.moveaxis(jnp.pad(
        a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)), 1, 0).reshape(
        (-1, SCAN_BLOCK, b) + a.shape[2:])

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        q_t, k_t = (jnp.repeat(a, group, axis=1) for a in (q_t, k_t))
        state = state * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state))
        state = state + k_t[..., None] * u[..., None, :]
        return state, scale * jnp.einsum("bhk,bhkv->bhv", q_t, state)

    def block(state, xs):
        return jax.lax.scan(step, state, xs)

    _, o = jax.lax.scan(
        jax.checkpoint(block) if remat else block,
        jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(blocks(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((-1, b, h, v.shape[-1])), 0, 1)[:, :s]


def gdn_operands(cfg, p, pre, x):
    """The scan's operands from the layer's normed input x (B, S, E): q,
    k (B, S, Hk, 128), v (B, S, Hv, 128), g, beta (B, S, Hv); and z (B,
    S, Hv * 128)."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    b, s, _ = x.shape
    x = _r(cfg, x)
    qkvz = x @ _r(cfg, p[pre + "in_proj_qkvz.weight"])
    ba = x @ _r(cfg, p[pre + "in_proj_ba.weight"])
    y = _short_conv_silu(qkvz[..., :2 * hk * dk + hv * dv],
                         p[pre + "conv1d.weight"])
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
    q = unit(y[..., :hk * dk].reshape(b, s, hk, dk))
    k = unit(y[..., hk * dk:2 * hk * dk].reshape(b, s, hk, dk))
    v = y[..., 2 * hk * dk:].reshape(b, s, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p[pre + "A_log"]) * jax.nn.softplus(
        ba[..., hv:] + p[pre + "dt_bias"])
    if cfg.get("gate_cumsum_dtype"):
        g = _round_cumulated_gate(g[..., None], cfg["gate_cumsum_dtype"])[
            ..., 0]
    return q, k, v, g, beta, qkvz[..., 2 * hk * dk + hv * dv:]


def _gdn_output(cfg, p, pre, o, z):
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * p[pre + "norm.weight"]
    out = o.reshape(z.shape) * jax.nn.silu(z)
    return _r(cfg, out) @ _r(cfg, p[pre + "out_proj.weight"])


def _gdn(cfg, p, pre, x, remat=False):
    """Under `remat`, the operands' work and the output's are recomputed
    pieces of their own: a layer's backward then holds one piece's
    float32 intermediates at a time."""
    cut = jax.checkpoint if remat else (lambda f: f)
    q, k, v, g, beta, z = cut(
        lambda p, x: gdn_operands(cfg, p, pre, x))(p, x)
    o = gated_delta_rule(q, k, v, g, beta,
                         cfg["linear_key_head_dim"] ** -0.5, remat)
    return cut(lambda p, o, z: _gdn_output(cfg, p, pre, o, z))(p, o, z)


def _rotate(x, theta, dim):
    """x (B, S, H, D): lanes [0, dim) rotate-half at theta, the others
    as they are."""
    half = dim // 2
    inv = (float(theta) ** (-2.0 * np.arange(half, dtype=np.float64)
                            / dim)).astype(np.float32)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _attention(cfg, p, pre, x, remat=False):
    b, s, _ = x.shape
    heads, kv_heads, d = (cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    x = _r(cfg, x)
    qg = (x @ _r(cfg, p[pre + "q_proj.weight"])).reshape(b, s, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ _r(cfg, p[pre + "k_proj.weight"])).reshape(b, s, kv_heads, d)
    v = (x @ _r(cfg, p[pre + "v_proj.weight"])).reshape(b, s, kv_heads, d)
    q = _norm0(q, p[pre + "q_norm.weight"], eps)
    k = _norm0(k, p[pre + "k_norm.weight"], eps)
    dim = int(d * cfg["partial_rotary_factor"])
    q = _r(cfg, _rotate(q, cfg["rope_theta"], dim))
    k = _r(cfg, _rotate(k, cfg["rope_theta"], dim))
    v = _r(cfg, v)
    rows = min(_ROW_BLOCK, s)
    pad = -s % rows
    at = jnp.arange(s)

    def head(j):
        q_j = q[:, :, j]
        k_j, v_j = (a[:, :, j // (heads // kv_heads)] for a in (k, v))
        q_b = jnp.moveaxis(jnp.pad(q_j, ((0, 0), (0, pad), (0, 0))).reshape(
            b, -1, rows, d), 1, 0)

        def block(a):           # a block of query rows against all keys
            q_rows, first = a
            scores = jnp.einsum("bqd,bkd->bqk", q_rows, k_j) / np.sqrt(d)
            seen = (first + jnp.arange(rows))[:, None] >= at[None, :]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", _r(cfg, probs), v_j)

        out = jax.lax.map(jax.checkpoint(block) if remat else block,
                          (q_b, jnp.arange(q_b.shape[0]) * rows))
        return jnp.moveaxis(out, 0, 1).reshape(b, -1, d)[:, :s]

    out = jax.lax.map(jax.checkpoint(head) if remat else head,
                      jnp.arange(heads))                    # (H, B, S, D)
    out = out.transpose(1, 2, 0, 3) * jax.nn.sigmoid(gate)
    return _r(cfg, out.reshape(b, s, heads * d)) @ _r(
        cfg, p[pre + "o_proj.weight"])


def route(cfg, wr, x, given=None):
    """x (T, H) -> (experts (T, k), weights (T, k), scores (T,
    num_experts)): softmax over all experts in float32, the top-k (or
    the `given` indices), their scores divided by their sum."""
    scores = jax.nn.softmax(_r(cfg, x) @ _r(cfg, wr), axis=-1)
    experts = given if given is not None else jax.lax.top_k(
        scores, cfg["num_experts_per_tok"])[1]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    return experts, weights, scores


def moe_layer(cfg, p, pre, x, held, given=None, remat=False):
    """The expert layer's output for rows x (T, H): the part the routed
    experts `held = (first, count)` give, plus the gated shared expert
    every row passes.  -> (out, experts, scores)."""
    experts, weights, scores = route(cfg, p[pre + "gate_weight"], x, given)
    first, count = held
    xr = _r(cfg, x)

    def expert(args):           # one held expert, on the rows that chose it
        e, wg, wu, wd = args
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        return w_e[:, None] * _gated_ffn(cfg, xr, wg, wu, wd)

    # the held experts' parts summed as they come: one (T, H) sum, not
    # one a held expert
    each = jax.checkpoint(expert) if remat else expert
    out, _ = jax.lax.scan(
        lambda total, args: (total + each(args), None),
        jnp.zeros(x.shape, jnp.float32),
        (jnp.arange(count), p[pre + "w_gate"], p[pre + "w_up"],
         p[pre + "w_down"]))
    shared = pre + "shared_experts."
    gate = jax.nn.sigmoid(xr @ _r(cfg, p[pre + "shared_expert_gate.weight"]))
    return out + gate * _gated_ffn(
        cfg, xr, p[shared + "gate_proj.weight"], p[shared + "up_proj.weight"],
        p[shared + "down_proj.weight"]), experts, scores


def layer_kind(cfg, i: int) -> str:
    return ("full_attention" if (i + 1) % cfg["full_attention_interval"] == 0
            else "linear_attention")


def _layer(cfg, p, i, x, held, given, remat=False):
    pre, eps = f"model.layers.{i}.", cfg["rms_norm_eps"]
    a = _norm0(x, p[pre + "input_layernorm.weight"], eps)
    if layer_kind(cfg, i) == "linear_attention":
        x = x + _gdn(cfg, p, pre + "linear_attn.", a, remat)
    else:
        x = x + _attention(cfg, p, pre + "self_attn.", a, remat)
    h = _norm0(x, p[pre + "post_attention_layernorm.weight"], eps)
    b, s, hid = h.shape
    moe = lambda p, h, given: moe_layer(cfg, p, pre + "moe.", h, held,
                                        given, remat)
    out, experts, scores = (jax.checkpoint(moe) if remat else moe)(
        p, h.reshape(-1, hid), given)
    return x + out.reshape(b, s, hid), experts, scores


_KEYS = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
         "head_dim", "rms_norm_eps", "rope_theta", "partial_rotary_factor",
         "full_attention_interval", "linear_key_head_dim",
         "linear_value_head_dim", "linear_num_key_heads",
         "linear_num_value_heads", "num_experts_per_tok", "norm_topk_prob",
         "experts_held", "router_width", "operand_dtype",
         "gate_cumsum_dtype")


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
            g = next(given) if given is not None else None
            f = lambda p, x, g, i=i: _layer(cfg, p, i, x, held, g, remat)
            x, e, c = (jax.checkpoint(f) if remat else f)(p, x, g)
            experts.append(e)
            scores.append(c)
        h = _norm0(x, p["model.norm.weight"], cfg["rms_norm_eps"])
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


def compare_gradients(got: dict, want: dict) -> dict:
    """Gradients `got` against the reference's `want`, leaf by leaf:
    relative L2 difference, each under the limit of GRAD_TOLERANCE
    whose key ends the leaf's name.  A reading that is not finite
    fails."""
    rel, limit = {}, {}
    for name, b in want.items():
        a, b = np.asarray(got[name], np.float32), np.asarray(b, np.float32)
        rel[name] = float(np.linalg.norm(a - b)
                          / max(float(np.linalg.norm(b)), 1e-30))
        limit[name] = next(v for k, v in GRAD_TOLERANCE.items()
                           if name.endswith(k))
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
