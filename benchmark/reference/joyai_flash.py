"""Plain reference for JoyAI-LLM-Flash autoregressive training with its
multi-token-prediction module: forward pass, both losses and `jax.grad`
of their sum.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`: dense S x S scores under a
`tril`, a loop over the experts held (every one on all rows, weighted
by what chose it), no kernel, no sort of rows, no cast, no
recomputation unless asked.  It follows DeepSeek-V3 (arXiv:2412.19437
§2.1.1 attention, §2.1.2 router and balancing, §2.2 multi-token
prediction; the attention's equations: DeepSeek-V2, arXiv:2405.04434
§2.1), whose keys JoyAI-LLM-Flash's `config.json` uses; it is fed the
system's own seeded weights under the system's parameter names; a
Linear weight there is (in, out).

    a = RMSNorm(x; g1)
    c_q = RMSNorm(a W_qa; g_q)          q = c_q W_qb -> H x (nope ‖ rope)
    [c_kv ‖ k_r] = a W_kva              c_kv = RMSNorm(c_kv; g_kv)
    [k_nope ‖ v] = c_kv W_kvb           -> H x (nope ‖ v_dim)
    q_j = [q_nope_j ‖ RoPE(q_r_j)]      k_j = [k_nope_j ‖ RoPE(k_r)]
    o_j = softmax(tril(q_j k_j^T / sqrt(nope + rope))) v_j
    x = x + concat_j(o_j) W_o
    b = RMSNorm(x; g2)
    dense layer:   x = x + (SiLU(b Wg) * (b Wu)) Wd
    sparse layer:  s = sigmoid(b W_r);  I = top-k(s + bias)
                   w_i = 2.5 * s_i / sum_{j in I} s_j
                   x = x + sum_{i in I, i held} w_i FFN_i(b) + FFN_shared(b)
    h = RMSNorm(x_L; gf);   logits = h W_head          (predicts t_{i+1})
    h' = [RMSNorm(Emb(t_{i+1}); g_e) ‖ RMSNorm(h; g_h)] W_eh
    h'' = SparseLayer_mtp(h');  logits' = RMSNorm(h''; g_m) W_head
                                                       (predicts t_{i+2})
    L = CE(t_{i+1} | logits_i)  +  lambda * CE(t_{i+2} | logits'_i)

RoPE rotates the pair (2 m, 2 m + 1) of the rope part by position x
theta^(-2 m / rope) (`rope_interleave`); the system sorts the lanes
into halves first, a fixed permutation of q and k alike that no score
sees.

Departures from the published description, each also the system's:

* a chip's share: only the routed experts `experts_held = (first,
  count)` add to a layer's output (the weights w_i are still normalised
  over all k chosen; the shared expert is whole), and the vocabulary
  may be a slice — the deployment the configuration file states;
* `routing`: the top-k indices may be GIVEN (per expert layer), the
  weights then come from this file's own s at those indices.  With
  random weights the k-th and (k+1)-th scores of a row are often within
  a bfloat16 rounding of each other; a comparison of logits needs both
  sides on the same experts, and `routing_agreement` says how many
  choices differed and that each was such a near-tie of s + bias;
* inside `W_eh`'s input the embedding comes FIRST, as in the released
  modeling code (DeepSeek-V3's and its serving ports' `eh_proj(cat(
  enorm(embeds), hnorm(hidden)))`); the paper's equation 21 writes the
  hidden state first;
* h_i is the main model's state AFTER its final norm, as the released
  serving code hands it to the module; the paper leaves that open;
* every position runs through the MTP module, the last with a
  placeholder token (t_0: the roll), and each loss leaves out the
  positions without a target (the last one, the last two); causal
  attention keeps a position from every earlier one.  The MTP block's
  router sees the placeholder row too;
* no auxiliary balance loss: `config.json` sets none (`topk_method`
  noaux_tc); the selection bias's update is the train step's, not the
  loss's, and not part of this file;
* not in `config.json`, assumed (the configuration file lists them):
  lambda.

For sizes that do not fit at once, `forward` takes one sequence at a
time (the caller loops) and walks the heads one at a time;
`remat=True` recomputes a layer (and a head) in the backward pass, for
`grads` at the timed sizes beside the system's resident state.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Tolerances of the comparison that decides `correct`: the system (bf16
# activations over float32 master weights, the flash kernels with
# 192-wide q/k heads over 128-wide v heads, grouped matmuls) against
# this file on the chip, at the timed sizes.  Each limit lies between
# two readings (my chip runs, PR 32; PERF.md §6): the largest the
# system gave over its runs, and what this file gives against itself
# with every matmul operand rounded to the precision below the stated
# one (`operand_dtype="float8_e4m3fn"`), put through the same `compare`
# / `compare_gradients` by benchmark/tests/precision_readings_joyai.py:
# a system that computes in fp8 has to come out as not correct.  The
# seeded weights keep attention near uniform (no sharpened q / k
# scales, as the SDAR cell's have), so the system reads a tenth of
# what that cell's does, and the limits sit accordingly.
#
# LOGITS: relative RMS difference of each head's logits at the probed
# positions.  System 0.0055 to 0.0056 (both heads); fp8 operands 0.053 /
# 0.064; this file with bfloat16 operands 0.0024 / 0.0034.  LOGITS_FLOOR
# is the other side: logits that leave a bf16 matmul carry at least the
# rounding of the output (1e-3 of their RMS); under the floor the
# system did NOT compute in bfloat16 as the configuration says.
LOGITS_TOLERANCE = 0.02
LOGITS_FLOOR = 1e-4
# LOSS: relative difference of each cross-entropy.  An untrained
# model's log-softmax hardly moves with its logits (fp8 operands move
# it by 3e-5, the system by at most 8e-6): a weak witness of precision
# and a strong one of the objective (the shift, the positions left out,
# the divisor, lambda), held to the accepted cells' 2e-3.  fp8 fails by
# the other limits, not by this one.
LOSS_TOLERANCE = 2e-3
# GRADIENTS: relative L2 difference of each named leaf's gradient over
# the batch — the timed step's own, read from Adam's first moment — a
# limit a leaf (`compare_gradients`; the key is the end of the leaf's
# name), each near the geometric mean of its two readings.  Leaving out
# the routed scaling factor (x 1 for x 2.5) reads 0.60 on the router's
# and the routed experts' leaves and under 0.014 on the others: it
# fails by those two.  System, largest of its runs | fp8 operands |
# (this file with bfloat16 operands):
GRAD_TOLERANCE = {
    "self_attn.kv_b_proj.weight": 0.08,         # 0.0127 | 0.51  (0.0048)
    "self_attn.q_a_proj.weight": 0.025,         # 0.0091 | 0.075 (0.0056)
    "moe.w_down": 0.025,                        # 0.0090 | 0.078 (0.0042)
    "shared_experts.down_proj.weight": 0.025,   # 0.0079 | 0.077 (0.0042)
    "mtp.eh_proj.weight": 0.017,                # 0.0059 | 0.049 (0.0024)
    "moe.gate_weight": 0.05,                    # 0.0091 | 0.26  (0.0049)
}
# A (row, slot) choice that differs from this file's own top-k must be
# a near-tie: this file's score + bias of the system's pick within this
# relative distance of its own k-th largest.  Over the 7.9e5 choices of
# a comparison the system's largest read 0.0042 to 0.0047 (0.5% of the
# picks differ); this file's own router with bfloat16 operands 0.0021,
# with fp8 operands 0.043.  A router whose SCORES are rounded to
# bfloat16 reads what the system reads (PERF.md §6): no limit here
# tells the two apart, and the builder asks the executable instead
# (`routers_choose_in_float32`).
NEAR_TIE = 0.015


def _r(cfg, x):
    """A matmul operand as the reference reads it: untouched, or —
    `operand_dtype`, for the readings PERF.md sets the tolerances from —
    rounded to a lower precision first, saturating at the type's
    largest value as fp8 casts do (float32 accumulation stays).  The
    gradient passes straight through the rounding."""
    dtype = cfg.get("operand_dtype")
    if dtype is None:
        return x
    info = jnp.finfo(dtype)
    if info.nexp == 8:      # bfloat16: XLA drops a convert pair on a TPU
        low = jax.lax.reduce_precision(x, info.nexp, info.nmant)
    else:
        top = float(info.max)
        low = jnp.clip(x, -top, top).astype(dtype).astype(jnp.float32)
    return x + jax.lax.stop_gradient(low - x)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * weight


def _rope_interleaved(x, positions, theta):
    """x (B, S, heads, D): the pair (2 m, 2 m + 1) rotated by position
    x theta^(-2 m / D), in place."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv      # (S, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(cfg, p, pre, x, positions, remat=False):
    b, s, _ = x.shape
    heads = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    x = _r(cfg, x)
    c_q = _rms_norm(x @ _r(cfg, p[pre + "q_a_proj.weight"]),
                    p[pre + "q_a_layernorm.weight"], eps)
    q = (_r(cfg, c_q) @ _r(cfg, p[pre + "q_b_proj.weight"])).reshape(
        b, s, heads, nope + rope)
    kv_a = x @ _r(cfg, p[pre + "kv_a_proj_with_mqa.weight"])
    c_kv = _rms_norm(kv_a[..., :rank], p[pre + "kv_a_layernorm.weight"],
                     eps)
    kv = (_r(cfg, c_kv) @ _r(cfg, p[pre + "kv_b_proj.weight"])).reshape(
        b, s, heads, nope + vd)
    k_r = _rope_interleaved(kv_a[:, :, None, rank:], positions,
                            cfg["rope_theta"])
    q_r = _rope_interleaved(q[..., nope:], positions, cfg["rope_theta"])
    q = _r(cfg, jnp.concatenate([q[..., :nope], q_r], axis=-1))
    k = _r(cfg, jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, heads, rope))],
        axis=-1))
    v = _r(cfg, kv[..., nope:])
    causal = jnp.tril(jnp.ones((s, s), jnp.bool_))

    def head(args):
        q_j, k_j, v_j = args                                # (B, S, D)
        scores = jnp.einsum("bqd,bkd->bqk", q_j, k_j) / np.sqrt(nope + rope)
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", _r(cfg, probs), v_j)

    # a head at a time, so that one S^2 score matrix exists at once
    out = jax.lax.map(jax.checkpoint(head) if remat else head,
                      tuple(a.transpose(2, 0, 1, 3) for a in (q, k, v)))
    return _r(cfg, out.transpose(1, 2, 0, 3).reshape(b, s, heads * vd)) \
        @ _r(cfg, p[pre + "o_proj.weight"])


def _gated_ffn(cfg, x, wg, wu, wd):
    act = jax.nn.silu(x @ _r(cfg, wg)) * (x @ _r(cfg, wu))
    return _r(cfg, act) @ _r(cfg, wd)


def route(cfg, wr, bias, x, given=None):
    """x (T, H) -> (experts (T, k), weights (T, k), s + bias (T,
    n_routed)): sigmoid scores over all experts, the top-k of score +
    bias (or the `given` indices), the weights the SCORES there,
    renormalised where `norm_topk_prob`, times the scaling factor."""
    if cfg.get("n_group", 1) != 1 or cfg.get("topk_group", 1) != 1:
        raise NotImplementedError("group-limited routing")
    scores = jax.nn.sigmoid(_r(cfg, x) @ _r(cfg, wr))
    if cfg.get("router_dtype"):         # a control reading: PERF.md §6
        info = jnp.finfo(cfg["router_dtype"])   # (a convert pair is
        scores = jax.lax.reduce_precision(      # dropped on a TPU)
            scores, info.nexp, info.nmant)
    choose_by = scores + bias
    experts = given if given is not None else jax.lax.top_k(
        choose_by, cfg["num_experts_per_tok"])[1]
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / weights.sum(-1, keepdims=True)
    return experts, weights * cfg["routed_scaling_factor"], choose_by


def moe_layer(cfg, p, pre, x, held, given=None, remat=False):
    """The expert layer's output for rows x (T, H): the part the routed
    experts `held = (first, count)` give, plus the shared expert.  ->
    (out, experts, s + bias)."""
    experts, weights, choose_by = route(
        cfg, p[pre + "gate_weight"], p[pre + "e_score_correction_bias"], x,
        given)
    first, count = held
    x = _r(cfg, x)

    def expert(args):           # one held expert, on the rows that chose it
        e, wg, wu, wd = args
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        return w_e[:, None] * _gated_ffn(cfg, x, wg, wu, wd)

    # a loop over the held experts (one traced body: a Python loop
    # compiles count copies of it, minutes at 16 experts x 6 layers)
    out = jnp.sum(jax.lax.map(
        jax.checkpoint(expert) if remat else expert,
        (jnp.arange(count), p[pre + "w_gate"], p[pre + "w_up"],
         p[pre + "w_down"])), axis=0)
    shared = pre + "shared_experts."
    out = out + _gated_ffn(cfg, x, p[shared + "gate_proj.weight"],
                           p[shared + "up_proj.weight"],
                           p[shared + "down_proj.weight"])
    return out, experts, choose_by


def _layer(cfg, p, pre, x, positions, held, given, remat=False):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, p, pre + "self_attn.",
                       _rms_norm(x, p[pre + "input_layernorm.weight"], eps),
                       positions, remat)
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


def _held(cfg):
    held = cfg.get("experts_held")
    return tuple(held) if held else (0, cfg["n_routed_experts"])


def _key(cfg):
    keep = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
            "n_routed_experts", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "n_group", "topk_group",
            "num_hidden_layers", "mtp_loss_weight", "operand_dtype",
            "router_dtype")
    return tuple((k, cfg[k]) for k in keep if k in cfg) \
        + (("experts_held", _held(cfg)),)


def _ce(logits, labels, valid):
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    w = valid.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def _run(cfg, p, batch, routing, remat):
    """-> (loss, (ce, mtp_ce, logits (B, S, V), mtp logits (B, S, V),
    experts [(T, k)] and s + bias [(T, n)] of every expert layer, the
    MTP block's last))."""
    with jax.default_matmul_precision("highest"):
        ids = batch["input_ids"]
        seq = ids.shape[1]
        positions = jnp.arange(seq)
        embed, head = p["model.embed_tokens.weight"], p["lm_head.weight"]
        eps, held = cfg["rms_norm_eps"], _held(cfg)
        given = iter(routing) if routing is not None else None
        experts, choose = [], []

        def layer(pre, x):
            sparse = pre + "moe.gate_weight" in p
            g = next(given) if (given is not None and sparse) else None
            f = lambda p, x, g: _layer(cfg, p, pre, x, positions, held, g,
                                       remat)
            x, e, c = (jax.checkpoint(f) if remat else f)(p, x, g)
            if e is not None:
                experts.append(e)
                choose.append(c)
            return x

        x = embed[ids]
        for i in range(cfg["num_hidden_layers"]):
            x = layer(f"model.layers.{i}.", x)
        h = _rms_norm(x, p["model.norm.weight"], eps)
        logits = _r(cfg, h) @ _r(cfg, head)
        # the MTP module: the embedding first (the released code's order)
        nxt = jnp.roll(ids, -1, axis=1)
        both = jnp.concatenate(
            [_rms_norm(embed[nxt], p["mtp.enorm.weight"], eps),
             _rms_norm(h, p["mtp.hnorm.weight"], eps)], axis=-1)
        x = layer("mtp.block.", _r(cfg, both) @ _r(
            cfg, p["mtp.eh_proj.weight"]))
        mtp_logits = _r(cfg, _rms_norm(x, p["mtp.norm.weight"], eps)) \
            @ _r(cfg, head)
        at = jnp.arange(seq)[None, :]
        ce = _ce(logits, nxt, jnp.broadcast_to(at < seq - 1, ids.shape))
        mtp_ce = _ce(mtp_logits, jnp.roll(ids, -2, axis=1),
                     jnp.broadcast_to(at < seq - 2, ids.shape))
        loss = ce + cfg["mtp_loss_weight"] * mtp_ce
        return loss, (ce, mtp_ce, logits, mtp_logits, experts, choose)


def _f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}


@functools.partial(jax.jit, static_argnums=(0, 4))
def _forward(key, params, batch, routing, remat):
    return _run(dict(key), params, batch, routing, remat)


def forward(config: dict, params: dict, batch: dict, routing=None):
    """`batch`: input_ids (B, S) int32.  `routing`: per expert layer
    (the MTP block's last) (T, k) expert indices to use, T = B * S.  ->
    {"loss", "ce", "mtp_ce", "logits", "mtp_logits" (B, S, V),
    "experts", "choose_by"} in float32."""
    loss, (ce, mtp_ce, logits, mtp_logits, experts, choose) = _forward(
        _key(config), _f32(params), batch, routing, False)
    return {"loss": loss, "ce": ce, "mtp_ce": mtp_ce, "logits": logits,
            "mtp_logits": mtp_logits, "experts": experts,
            "choose_by": choose}


@functools.partial(jax.jit, static_argnums=(0, 5))
def _grads(key, leaves, rest, batch, routing, remat):
    return jax.grad(lambda l: _run(dict(key), {**rest, **l}, batch,
                                   routing, remat)[0])(leaves)


def grads(config: dict, params: dict, batch: dict, routing=None,
          wrt=None, remat=False):
    """`jax.grad` of the loss with respect to the leaves named in `wrt`
    (default: all but the selection biases, which the loss does not
    move), as a dict."""
    params = _f32(params)
    names = [k for k in params if not k.endswith("e_score_correction_bias")
             ] if wrt is None else list(wrt)
    return _grads(_key(config), {k: params[k] for k in names},
                  {k: v for k, v in params.items() if k not in names},
                  batch, routing, remat)


def routing_agreement(experts, ref_experts, ref_choose_by,
                      near_tie=NEAR_TIE):
    """How the system's choices `experts` (T, k) sit against this
    file's own: the share of (row, slot) picks that are not in the
    reference's top-k, and whether each of those is a near-tie — its
    reference score + bias within `near_tie` (relative) of the
    reference's k-th largest."""
    experts, ref_experts, ref_choose_by = (np.asarray(a) for a in (
        experts, ref_experts, ref_choose_by))
    differs = ~(experts[:, :, None] == ref_experts[:, None, :]).any(-1)
    kth = np.take_along_axis(ref_choose_by, ref_experts, axis=1).min(
        axis=1, keepdims=True)
    picked = np.take_along_axis(ref_choose_by, experts, axis=1)
    gap = np.where(differs, np.abs(picked - kth) / np.abs(kth), 0.0)
    return {"differ_share": float(differs.mean()),
            "max_gap": float(gap.max()),
            "all_near_ties": bool(gap.max() <= near_tie)}


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


def rel_rms(a, b) -> float:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.sqrt(np.mean(np.square(a - b)))
                 / max(np.sqrt(np.mean(np.square(b))), 1e-30))


def compare(got: dict, want: dict) -> dict:
    """System against reference: `got` and `want` hold "ce", "mtp_ce"
    (floats) and "logits", "mtp_logits" (arrays of the same shape, at
    the probed positions)."""
    out = {"ok": True}
    for name in ("logits", "mtp_logits"):
        diff = rel_rms(got[name], want[name])
        out[name + "_rel_rms"] = diff
        out["ok"] &= bool(LOGITS_FLOOR < diff < LOGITS_TOLERANCE)
    for name in ("ce", "mtp_ce"):
        diff = abs(got[name] - want[name]) / abs(want[name])
        out[name + "_rel"] = diff
        out[name], out["reference_" + name] = got[name], want[name]
        out["ok"] &= bool(diff < LOSS_TOLERANCE)
    return out
