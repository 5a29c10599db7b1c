"""Plain reference for Kimi Linear autoregressive training: forward pass,
the next-token loss and `jax.grad` of it.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`: the Kimi Delta Attention
recurrence ITSELF, a token at a time (`lax.scan` over t; no chunk, no
cumulated gate, no triangular solve, no kernel), the short convolution
as an explicit sum over its taps, position-free latent attention as a
plain masked softmax, a loop over the experts held.  It follows "Kimi
Linear: An Expressive, Efficient Attention Architecture"
(arXiv:2510.26692) and the released modeling code's parameter names;
the expert layer and its router are DeepSeek-V3's
(benchmark/reference/joyai_flash.py: `moe_layer`, `route`, imported).
It is fed the system's own seeded weights under the system's parameter
names; a Linear weight there is (in, out).

    a = RMSNorm(x; g1)                                    eps 1e-5
  KDA layer (H heads of dk = dv = 128), token t, head h:
    q', k', v = SiLU(Conv(a Wq)), SiLU(Conv(a Wk)), SiLU(Conv(a Wv))
                Conv(y)_t = sum_{i<4} taps[i] y_{t-3+i}, zeros before 0
    q, k  = q' rsqrt(|q'|^2 + 1e-6), k' rsqrt(|k'|^2 + 1e-6)
    g_t   = -exp(A_log[h]) softplus((a Wfa) Wfb + dt_bias)      in R^dk
    b_t   = sigmoid(a w_b[h])
    S_t   = (I - b_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + b_t k_t v_t^T
    o_t   = dk^-1/2 S_t^T q_t
    x = x + [RMSNorm_head(o_t; g_o) * sigmoid((a Wga) Wgb)] Wo
  latent layer (no query latent, NO rotation on either part):
    q = a Wq -> H x (nope ‖ rope);  [c_kv ‖ k_r] = a Wkva
    c_kv = RMSNorm(c_kv; g_kv);  [k_nope ‖ v] = c_kv Wkvb
    k_j = [k_nope_j ‖ k_r];  o_j = softmax(tril(q_j k_j^T / sqrt(192))) v_j
    x = x + concat_j(o_j) Wo
    b = RMSNorm(x; g2)
    dense layer:   x = x + (SiLU(b Wg) * (b Wu)) Wd
    sparse layer:  s = sigmoid(b W_r);  I = top-k(s + bias)
                   w_i = 2.446 * s_i / sum_{j in I} s_j
                   x = x + sum_{i in I, i held} w_i FFN_i(b) + FFN_shared(b)
    h = RMSNorm(x_L; gf);   L = CE(t_{i+1} | h_i W_head), i < S - 1

Departures from the published description, each also the system's:

* a chip's share (`experts_held`, a vocabulary slice) and GIVEN
  routing, as benchmark/reference/joyai_flash.py sets out;
* no auxiliary balance loss; the selection bias's update is the train
  step's;
* not in `config.json`, assumed (the configuration file lists them):
  the gates' rank 128, the decay's form and its initialisation, no bias
  on Wgb, the taps' initialisation.

For sizes that do not fit at once: the scan is an outer scan over
blocks of `SCAN_BLOCK` tokens around an inner one, the inner one under
`jax.checkpoint` where `remat`, so that `jax.grad` keeps S /
`SCAN_BLOCK` states a layer and not S (the one departure from "a token
at a time" autodiff needs; the arithmetic is the same); latent
attention walks the heads one at a time and a head's query rows in
blocks; the loss is taken in row chunks and logits exist at the probed
positions alone.

`gate_cumsum_dtype` (a control reading, benchmark/tests/
precision_readings_kimi.py): the decay of token t is taken from the
gate cumulated over chunks of 64 and rounded to that dtype, exp(G_t -
G_{t-1}) of the rounded sums — what a chunked scan that keeps G in
bfloat16 computes.  The comparison has to call it not correct.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.joyai_flash import (  # noqa: F401
    NEAR_TIE, _f32, _gated_ffn, _held, _r, _rms_norm, moe_layer, rel_rms,
    routing_agreement)

SCAN_BLOCK = 128
_ROW_BLOCK = 2048

# Tolerances of the comparison that decides `correct`: the system (bf16
# activations over float32 master weights; the chunked scan in float32
# with bfloat16 q, k, v, o at its edge; the causal flash kernels; grouped
# matmuls) against this file on the chip, at the timed sizes.  Each limit
# lies between two readings (my chip runs, PR 34; PERF.md §6): the
# largest the system gave over its 12 runs, and what this file gives against
# itself with every matmul operand rounded to float8_e4m3fn
# (`operand_dtype`) or with the cumulated gate kept in bfloat16
# (`gate_cumsum_dtype`), put through the same `compare` /
# `compare_gradients` by benchmark/tests/precision_readings_kimi.py: both
# have to come out as not correct — fp8 by every limit but the loss's,
# the bfloat16 gate by ONE, `f_b_proj`'s (the decay's up-projection: the
# leaf whose gradient runs through the cumulated gate alone; on the
# other leaves a bfloat16 gate reads under the system's own bfloat16
# activations).
#
# LOGITS: relative RMS difference of the logits at the probed positions.
# System 0.0033; fp8 operands 0.066; this file with bfloat16 operands
# 0.0026; a bfloat16 gate 0.0008.  LOGITS_FLOOR as in
# benchmark/reference/joyai_flash.py: under it the system did not
# compute in bfloat16 as the configuration says.
LOGITS_TOLERANCE = 0.015
LOGITS_FLOOR = 1e-4
# LOSS: relative difference of the cross-entropy (system 6e-6, fp8 6e-5);
# a weak witness of precision and a strong one of the objective (the
# shift, the position left out, the divisor), held to the accepted
# cells' 2e-3.
LOSS_TOLERANCE = 2e-3
# GRADIENTS: relative L2 difference of each named leaf's gradient — the
# timed step's own, read from Adam's first moment — a limit a leaf (the
# key ends the leaf's name), each near the geometric mean of the
# readings it lies between.  System, largest of its runs | fp8 operands
# | G in bfloat16 | (this file with bfloat16 operands):
GRAD_TOLERANCE = {
    "self_attn.k_proj.weight": 0.048,       # 0.0099 | 0.24 | 0.0070 (0.0054)
    "self_attn.f_b_proj.weight": 0.016,     # 0.0103 | 0.25 | 0.0260 (0.0062)
    "self_attn.A_log": 0.042,               # 0.0109 | 0.20 | 0.0086 (0.0047)
    "self_attn.k_conv1d.weight": 0.049,     # 0.0101 | 0.24 | 0.0070 (0.0055)
    "self_attn.b_proj.weight": 0.049,       # 0.0099 | 0.24 | 0.0065 (0.0056)
    "self_attn.kv_b_proj.weight": 0.07,     # 0.0065 | 0.82 | 0.0009 (0.0039)
    "moe.w_down": 0.027,                    # 0.0077 | 0.096 | 0.0012 (0.0044)
    "moe.gate_weight": 0.047,               # 0.0106 | 0.23 | 0.0016 (0.0055)
}
# NEAR_TIE (imported: 0.015): a router's pick that differs from this
# file's own top-k must be a near-tie of score + bias.  System 0.0066
# (0.5% of the picks differ); fp8 operands 0.060; bfloat16 operands 0.0020.


def _short_conv_silu(x, taps):
    """x (B, S, D), taps (width, D): the causal depthwise convolution as
    an explicit sum over the taps, then SiLU."""
    width, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, i:i + s] * taps[i]
                           for i in range(width)))


def _round_cumulated_gate(g, dtype, chunk=64):
    """g (B, S, H, D) -> the per-token gate a scan that keeps the gate
    cumulated over chunks of `chunk` tokens in `dtype` effectively
    applies: differences of the rounded sums."""
    b, s = g.shape[:2]
    pad = -s % chunk
    gp = jnp.pad(g, ((0, 0), (0, pad), (0, 0), (0, 0)))
    c = jnp.cumsum(gp.reshape((b, -1, chunk) + g.shape[2:]), axis=2)
    info = jnp.finfo(dtype)
    c = jax.lax.reduce_precision(c, info.nexp, info.nmant)
    low = jnp.diff(c, axis=2, prepend=jnp.zeros_like(c[:, :, :1]))
    low = low.reshape((b, s + pad) + g.shape[2:])[:, :s]
    return g + jax.lax.stop_gradient(low - g)


def delta_rule(q, k, v, g, beta, scale, remat=False):
    """The recurrence, a token at a time.  q, k, g (B, S, H, dk), v (B,
    S, H, dv), beta (B, S, H) -> o (B, S, H, dv)."""
    b, s, h, dk = q.shape
    pad = -s % SCAN_BLOCK
    blocks = lambda a: jnp.moveaxis(jnp.pad(
        a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)), 1, 0).reshape(
        (-1, SCAN_BLOCK, b) + a.shape[2:])

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
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


def kda_operands(cfg, p, pre, x):
    """The scan's operands from the layer's normed input x (B, S, E):
    q, k, v (B, S, H, 128), g (B, S, H, 128) <= 0, beta (B, S, H)."""
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    x = _r(cfg, x)
    proj = lambda name, y=x: y @ _r(cfg, p[pre + name + ".weight"])
    heads = lambda a: a.reshape(a.shape[:2] + (h, d))
    unit = lambda a: a * jax.lax.rsqrt(
        jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)
    q, k, v = (heads(_short_conv_silu(proj(n + "_proj"),
                                      p[pre + n + "_conv1d.weight"]))
               for n in "qkv")
    f = proj("f_b_proj", _r(cfg, proj("f_a_proj")))
    g = -jnp.exp(p[pre + "A_log"])[:, None] * jax.nn.softplus(
        heads(f + p[pre + "dt_bias"]))
    if cfg.get("gate_cumsum_dtype"):
        g = _round_cumulated_gate(g, cfg["gate_cumsum_dtype"])
    return unit(q), unit(k), v, g, jax.nn.sigmoid(proj("b_proj"))


def _kda(cfg, p, pre, x, remat=False):
    lin = cfg["linear_attn_config"]
    q, k, v, g, beta = kda_operands(cfg, p, pre, x)
    o = delta_rule(q, k, v, g, beta, lin["head_dim"] ** -0.5, remat)
    o = _rms_norm(o, p[pre + "o_norm.weight"], cfg["rms_norm_eps"])
    xr = _r(cfg, x)
    gate = _r(cfg, xr @ _r(cfg, p[pre + "g_a_proj.weight"])) @ _r(
        cfg, p[pre + "g_b_proj.weight"])
    out = o.reshape(gate.shape) * jax.nn.sigmoid(gate)
    return _r(cfg, out) @ _r(cfg, p[pre + "o_proj.weight"])


def _nope_attention(cfg, p, pre, x, remat=False):
    b, s, _ = x.shape
    heads = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    x = _r(cfg, x)
    q = (x @ _r(cfg, p[pre + "q_proj.weight"])).reshape(
        b, s, heads, nope + rope)
    kv_a = x @ _r(cfg, p[pre + "kv_a_proj_with_mqa.weight"])
    c_kv = _rms_norm(kv_a[..., :rank], p[pre + "kv_a_layernorm.weight"],
                     eps)
    kv = (_r(cfg, c_kv) @ _r(cfg, p[pre + "kv_b_proj.weight"])).reshape(
        b, s, heads, nope + vd)
    k_r = jnp.broadcast_to(kv_a[:, :, None, rank:], (b, s, heads, rope))
    q = _r(cfg, q)
    k = _r(cfg, jnp.concatenate([kv[..., :nope], k_r], axis=-1))
    v = _r(cfg, kv[..., nope:])
    rows = min(_ROW_BLOCK, s)
    pad = -s % rows
    at = jnp.arange(s)

    def head(args):
        q_j, k_j, v_j = args                                # (B, S, D)
        q_b = jnp.moveaxis(jnp.pad(q_j, ((0, 0), (0, pad), (0, 0))).reshape(
            b, -1, rows, nope + rope), 1, 0)

        def block(a):           # a block of query rows against all keys
            q_rows, first = a
            scores = jnp.einsum("bqd,bkd->bqk", q_rows, k_j) / np.sqrt(
                nope + rope)
            seen = (first + jnp.arange(rows))[:, None] >= at[None, :]
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("bqk,bkd->bqd", _r(cfg, probs), v_j)

        out = jax.lax.map(jax.checkpoint(block) if remat else block,
                          (q_b, jnp.arange(q_b.shape[0]) * rows))
        return jnp.moveaxis(out, 0, 1).reshape(b, -1, vd)[:, :s]

    out = jax.lax.map(jax.checkpoint(head) if remat else head,
                      tuple(a.transpose(2, 0, 1, 3) for a in (q, k, v)))
    return _r(cfg, out.transpose(1, 2, 0, 3).reshape(b, s, heads * vd)) \
        @ _r(cfg, p[pre + "o_proj.weight"])


def _layer(cfg, p, pre, x, held, given, remat=False):
    eps = cfg["rms_norm_eps"]
    a = _rms_norm(x, p[pre + "input_layernorm.weight"], eps)
    attend = _kda if pre + "self_attn.A_log" in p else _nope_attention
    x = x + attend(cfg, p, pre + "self_attn.", a, remat)
    h = _rms_norm(x, p[pre + "post_attention_layernorm.weight"], eps)
    if pre + "moe.gate_weight" not in p:
        return x + _gated_ffn(
            cfg, _r(cfg, h), p[pre + "mlp.gate_proj.weight"],
            p[pre + "mlp.up_proj.weight"],
            p[pre + "mlp.down_proj.weight"]), None, None
    b, s, hid = h.shape
    out, experts, choose_by = moe_layer(
        cfg, p, pre + "moe.", h.reshape(-1, hid), held, given, remat)
    return x + out.reshape(b, s, hid), experts, choose_by


def _key(cfg):
    keep = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps",
            "n_routed_experts", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "n_group", "topk_group",
            "num_hidden_layers", "operand_dtype", "router_dtype",
            "gate_cumsum_dtype")
    lin = cfg["linear_attn_config"]
    return tuple((k, cfg[k]) for k in keep if k in cfg) + (
        ("experts_held", _held(cfg)),
        ("linear_attn_config", tuple(
            (k, lin[k]) for k in ("num_heads", "head_dim"))))


def _ce_in_row_chunks(cfg, h, head, labels, valid, remat):
    """Mean cross-entropy of `labels` (B, S) under h (B, S, H) @ head
    over the positions where `valid`; the (rows, V) logits a chunk of
    rows at a time."""
    rows = h.shape[0] * h.shape[1]
    chunk = min(_ROW_BLOCK, rows)
    pad = -rows % chunk
    flat = lambda a: jnp.pad(a.reshape((rows,) + a.shape[2:]),
                             ((0, pad),) + ((0, 0),) * (a.ndim - 2))
    w = flat(valid).astype(jnp.float32)

    def part(a):
        x, y, wt = a
        logp = jax.nn.log_softmax(_r(cfg, x) @ _r(cfg, head), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, y[:, None], -1)[:, 0] * wt)

    total = jax.lax.map(
        jax.checkpoint(part) if remat else part,
        (flat(h).reshape(-1, chunk, h.shape[-1]),
         flat(labels).reshape(-1, chunk), w.reshape(-1, chunk)))
    return jnp.sum(total) / jnp.maximum(jnp.sum(w), 1.0)


def _run(cfg, p, batch, routing, remat, probe):
    """-> (loss, (logits at `probe` (B, len(probe), V), experts [(T, k)]
    and s + bias [(T, n)] of every expert layer))."""
    with jax.default_matmul_precision("highest"):
        ids = batch["input_ids"]
        seq = ids.shape[1]
        eps, held = cfg["rms_norm_eps"], _held(cfg)
        given = iter(routing) if routing is not None else None
        experts, choose = [], []
        x = p["model.embed_tokens.weight"][ids]
        for i in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            sparse = pre + "moe.gate_weight" in p
            g = next(given) if (given is not None and sparse) else None
            f = lambda p, x, g, pre=pre: _layer(cfg, p, pre, x, held, g,
                                                remat)
            x, e, c = (jax.checkpoint(f) if remat else f)(p, x, g)
            if e is not None:
                experts.append(e)
                choose.append(c)
        h = _rms_norm(x, p["model.norm.weight"], eps)
        head = p["lm_head.weight"]
        loss = _ce_in_row_chunks(
            cfg, h, head, jnp.roll(ids, -1, axis=1),
            jnp.broadcast_to(jnp.arange(seq)[None, :] < seq - 1, ids.shape),
            remat)
        logits = _r(cfg, h[:, np.asarray(probe)]) @ _r(cfg, head)
        return loss, (logits, experts, choose)


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _forward(key, params, batch, routing, remat, probe):
    return _run(_cfg(key), params, batch, routing, remat, probe)


def _cfg(key):
    cfg = dict(key)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"])
    return cfg


def forward(config: dict, params: dict, batch: dict, routing=None,
            probe=None):
    """`batch`: input_ids (B, S) int32.  `routing`: per expert layer (T,
    k) expert indices to use, T = B * S.  `probe`: the positions whose
    logits to return (default: all).  -> {"loss", "logits" (B, probe,
    V), "experts", "choose_by"} in float32."""
    seq = batch["input_ids"].shape[1]
    probe = tuple(range(seq)) if probe is None else tuple(
        int(i) for i in probe)
    loss, (logits, experts, choose) = _forward(
        _key(config), _f32(params), batch, routing, False, probe)
    return {"loss": loss, "ce": loss, "logits": logits, "experts": experts,
            "choose_by": choose}


@functools.partial(jax.jit, static_argnums=(0, 5))
def _grads(key, leaves, rest, batch, routing, remat):
    return jax.grad(lambda l: _run(_cfg(key), {**rest, **l}, batch,
                                   routing, remat, (0,))[0])(leaves)


def grads(config: dict, params: dict, batch: dict, routing=None,
          wrt=None, remat=False):
    """`jax.grad` of the loss with respect to the leaves named in `wrt`
    (default: all but the selection biases), as a dict."""
    params = _f32(params)
    names = [k for k in params if not k.endswith("e_score_correction_bias")
             ] if wrt is None else list(wrt)
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
