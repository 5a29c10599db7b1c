"""Plain reference for SDAR-MoE block-diffusion training: forward pass,
loss and `jax.grad` of it.

Straightforward `jax.numpy` in float32 with
`jax.default_matmul_precision("highest")`: a dense (2 S)^2 mask, a
loop over the experts held (every one on all rows, weighted by what
chose it), no kernel, no sort of rows, no cast.  It follows `modeling_sdar_moe.py` of JetLM/SDAR-30B-A3B-Chat
(the Qwen3-MoE block) and the BD3-LM objective (Arriola et al. 2025)
that SDAR trains with; it is fed the system's own seeded weights under
the system's parameter names; a Linear weight there is (in, out).

    a = RMSNorm(x; g1)
    q = a Wq -> (rows, Hq, D)   k = a Wk -> (rows, Hkv, D)   v = a Wv
    q = RMSNorm_D(q; gq)   k = RMSNorm_D(k; gk)        per head
    q, k = RoPE(q, k; position, theta)                 rotate-half
    o_j = softmax(q_j k_{j // G}^T / sqrt(D) + M) v_{j // G}
    x = x + concat_j(o_j) Wo
    b = RMSNorm(x; g2)
    p = softmax(b Wr);  I = top-k(p);  w_i = p_i / sum_{j in I} p_j
    x = x + sum_{i in I, i held} w_i (SiLU(b Wg_i) * (b Wu_i)) Wd_i
    logits = RMSNorm(x_L; gf) W_head

Departures from the published description, each also the system's:

* a chip's share: only the experts `experts_held = (first, count)` add
  to a layer's output (the weights w_i are still normalised over all k
  chosen), and the vocabulary may be a slice — the deployment the
  configuration file states;
* `routing`: the top-k indices may be GIVEN (per layer), the weights
  then come from this file's own p at those indices.  With random
  weights the k-th and (k+1)-th probabilities of a row are often within
  a bfloat16 rounding of each other; a comparison of logits needs both
  sides on the same experts, and `routing_agreement` says how many
  choices differed and that each was such a near-tie;
* not in `config.json`, assumed (the configuration file lists them):
  q/k RMSNorm, block length, the linear schedule with one t a block,
  logits compared at the masked position itself (no shift).

For sizes that do not fit at once, `forward` takes one sequence at a
time (the caller loops) and walks the query heads one at a time;
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
# activations over float32 master weights, the flash kernels, grouped
# matmuls) against this file on the chip, at the timed sizes.  Each
# limit lies between two readings (my chip runs, PR 28, call 33;
# PERF.md §6): the largest the system gave over 9 runs of 9 seeds, and
# what this file gives against itself with every matmul operand rounded
# to the precision below the stated one (`operand_dtype=
# "float8_e4m3fn"`), put through the same `compare` /
# `compare_gradients` by benchmark/tests/precision_readings.py — a
# system that computes in fp8 or int8 has to come out as not correct.
# The seeded weights' attention logits have a spread of 4
# (`assumed.seeded_weights`): a rounding of q and k moves a softmax
# weight by several percent, the system's readings and the control's
# alike (with logits of spread 2 the same two read 0.016 and 0.195).
#
# LOGITS: relative RMS difference of the logits at the probed masked
# positions — rows whose state is all attention output.  System 0.0465
# to 0.0503; fp8 operands 0.80.  LOGITS_FLOOR is the other side:
# logits that leave a bf16 matmul carry at least the rounding of the
# output (1e-3 of their RMS); under the floor the system did NOT
# compute in bfloat16 as the configuration says.
LOGITS_TOLERANCE = 0.15
LOGITS_FLOOR = 1e-4
# LOSS: relative difference of the 1/t-weighted loss.  An untrained
# model's log-softmax hardly moves with its logits (fp8 operands move
# it by 1.0e-3, the system by at most 2.0e-4): a weak witness of
# precision and a strong one of the objective (weights, masks, the
# divisor), held to the accepted cells' 2e-3.  fp8 fails by the other
# limits, not by this one.
LOSS_TOLERANCE = 2e-3
# GRADIENTS: relative L2 difference of each named leaf's gradient on
# one sequence, a limit a leaf (`compare_gradients`; the key is the
# end of the leaf's name).  A leaf's gradient is a sum over 8,192 rows
# of terms of both signs, so a rounding of the operands moves it more
# than it moves a logit.  That the gap is the stated precision's and
# not the program's: the program's own loss function with its cast off
# and matmuls at `highest` reads 0.0033 / 0.025 / 0.0026 / 0.0022
# against this file (same order as below), and this file with
# bfloat16 operands — no kernel, no walk, cotangents not rounded —
# reads 0.118 / 0.136 / 0.194 / 0.197 where the system read 0.212 /
# 0.199 / 0.264 / 0.271 on that seed (float32 on the CPU: XLA drops
# the rounding on a TPU).  System, largest of 9 runs | fp8 operands:
GRAD_TOLERANCE = {
    "moe.gate_weight": 0.6,         # 0.378 | 0.94
    "moe.w_down": 0.55,             # 0.243 | 1.27
    "q_norm.weight": 0.65,          # 0.330 | 1.31
    "embed_tokens.weight": 0.6,     # 0.283 | 1.26
}
# A (row, slot) choice that differs from this file's own top-k must be
# a near-tie: the reference probability of the system's pick within
# this relative distance of the reference's k-th largest (the distance
# cannot pass 1).  Over the 1.5e6 choices of a comparison the system's
# largest read 0.33 to 0.46 (1.5% of the picks differ: the router's
# input at a mask row is all attention output, 5% off as the logits
# are); this file's own router with bfloat16 operands 0.26, with fp8
# operands 0.98 (float32 on the CPU).
NEAR_TIE = 0.7


def _r(cfg, x):
    """A matmul operand as the reference reads it: untouched, or —
    `operand_dtype`, for the readings PERF.md sets the tolerances from —
    rounded to a lower precision first, saturating at the type's
    largest value as fp8 casts do (float32 accumulation stays).  The
    gradient passes straight through the rounding: a cotangent cast to
    float8 without a scale underflows to zero, which would read as a
    gradient of nothing and not as one computed in fp8."""
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


def _rope(x, positions, theta):
    """x (B, R, H, D), rotate-half convention."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv      # (R, D/2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block_diffusion_mask(seq: int, block: int) -> np.ndarray:
    """(2 S, 2 S) bool over rows `[x_t ‖ x_0]`, from the definition."""
    i = np.arange(2 * seq)
    blk, noisy = (i % seq) // block, i < seq
    bi, bj = blk[:, None], blk[None, :]
    ni, nj = noisy[:, None], noisy[None, :]
    return np.where(ni, np.where(nj, bj == bi, bj < bi), ~nj & (bj <= bi))


def _attention(cfg, p, pre, x, positions, mask, remat=False):
    b, r, _ = x.shape
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    x = _r(cfg, x)
    q = (x @ _r(cfg, p[pre + "q_proj.weight"])).reshape(b, r, hq, d)
    k = (x @ _r(cfg, p[pre + "k_proj.weight"])).reshape(b, r, hkv, d)
    v = (x @ _r(cfg, p[pre + "v_proj.weight"])).reshape(b, r, hkv, d)
    if pre + "q_norm.weight" in p:
        q = _rms_norm(q, p[pre + "q_norm.weight"], eps)
        k = _rms_norm(k, p[pre + "k_norm.weight"], eps)
    q = _rope(q, positions, cfg["rope_theta"])
    k = _rope(k, positions, cfg["rope_theta"])
    group = hq // hkv
    q, k, v = _r(cfg, q), _r(cfg, k), _r(cfg, v)
    k_t, v_t = k.transpose(2, 0, 1, 3), v.transpose(2, 0, 1, 3)

    def head(args):             # one query head against its kv head
        q_j, kv = args                                      # (B, R, D)
        scores = jnp.einsum("bqd,bkd->bqk", q_j, k_t[kv]) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", _r(cfg, probs), v_t[kv])

    # a head at a time, so that one (2 S)^2 score matrix exists at once
    out = jax.lax.map(jax.checkpoint(head) if remat else head,
                      (q.transpose(2, 0, 1, 3), jnp.arange(hq) // group))
    return _r(cfg, out.transpose(1, 2, 0, 3).reshape(b, r, hq * d)) \
        @ _r(cfg, p[pre + "out_proj.weight"])


def route(cfg, wr, x, given=None):
    """x (T, H) -> (experts (T, k), weights (T, k), p (T, n_routed)):
    softmax over all experts, top-k (or the `given` indices), the k
    weights renormalised where `norm_topk_prob`."""
    probs = jax.nn.softmax(_r(cfg, x) @ _r(cfg, wr), axis=-1)
    experts = given if given is not None else jax.lax.top_k(
        probs, cfg["num_experts_per_tok"])[1]
    weights = jnp.take_along_axis(probs, experts, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / weights.sum(-1, keepdims=True)
    return experts, weights, probs


def moe_layer(cfg, p, pre, x, held, given=None, remat=False):
    """The expert layer's output for rows x (T, H): the part the
    experts `held = (first, count)` give.  -> (out, experts, p)."""
    experts, weights, probs = route(cfg, p[pre + "gate_weight"], x, given)
    first, count = held
    x = _r(cfg, x)

    def expert(args):           # one held expert, on the rows that chose it
        e, wg, wu, wd = args
        w_e = jnp.sum(jnp.where(experts == first + e, weights, 0.0), -1)
        act = jax.nn.silu(x @ _r(cfg, wg)) * (x @ _r(cfg, wu))
        return w_e[:, None] * (_r(cfg, act) @ _r(cfg, wd))

    # a loop over the held experts (one traced body: a Python loop
    # compiles count copies of it, minutes at 16 experts x 6 layers)
    out = jnp.sum(jax.lax.map(
        jax.checkpoint(expert) if remat else expert,
        (jnp.arange(count), p[pre + "w_gate"], p[pre + "w_up"],
         p[pre + "w_down"])), axis=0)
    return out, experts, probs


def _layer(cfg, p, i, x, positions, mask, held, given, remat=False):
    pre = f"model.layers.{i}."
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, p, pre + "self_attn.",
                       _rms_norm(x, p[pre + "input_layernorm.weight"], eps),
                       positions, mask, remat)
    h = _rms_norm(x, p[pre + "post_attention_layernorm.weight"], eps)
    if pre + "moe.gate_weight" not in p:
        h = _r(cfg, h)
        act = jax.nn.silu(h @ _r(cfg, p[pre + "mlp.gate_proj.weight"])) \
            * (h @ _r(cfg, p[pre + "mlp.up_proj.weight"]))
        return x + _r(cfg, act) @ _r(
            cfg, p[pre + "mlp.down_proj.weight"]), None, None
    b, r, hid = h.shape
    out, experts, probs = moe_layer(cfg, p, pre + "moe.", h.reshape(-1, hid),
                                    held, given, remat)
    return x + out.reshape(b, r, hid), experts, probs


def _held(cfg):
    held = cfg.get("experts_held")
    return tuple(held) if held else (0, cfg["num_experts"])


def _key(cfg):
    keep = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "num_hidden_layers",
            "block_length", "operand_dtype")
    return tuple((k, cfg[k]) for k in keep if k in cfg) \
        + (("experts_held", _held(cfg)),)


def _run(cfg, p, batch, routing, remat):
    """-> (loss, (mean CE, logits (B, S, V) of the noisy half, experts
    [(T, k)] and probabilities [(T, n)] of every sparse layer))."""
    with jax.default_matmul_precision("highest"):
        noisy, clean = batch["noisy_ids"], batch["clean_ids"]
        seq = noisy.shape[1]
        ids = jnp.concatenate([noisy, clean], axis=1)
        positions = jnp.tile(jnp.arange(seq), 2)
        mask = jnp.asarray(block_diffusion_mask(seq, cfg["block_length"]))
        x = p["model.embed_tokens.weight"][ids]
        experts, probs = [], []
        for i in range(cfg["num_hidden_layers"]):
            given = None if routing is None else routing[i]
            f = lambda p, x, given, i=i: _layer(
                cfg, p, i, x, positions, mask, _held(cfg), given, remat)
            x, e, pr = (jax.checkpoint(f) if remat else f)(p, x, given)
            if e is not None:
                experts.append(e)
                probs.append(pr)
        x = _rms_norm(x[:, :seq], p["model.norm.weight"],
                      cfg["rms_norm_eps"])
        logits = _r(cfg, x) @ _r(cfg, p["lm_head.weight"])
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, clean[..., None], axis=-1)[..., 0]
        m = batch["masked"].astype(jnp.float32)
        n = jnp.maximum(m.sum(), 1.0)
        loss = jnp.sum(nll * m * batch["inv_t"]) / n
        return loss, (jnp.sum(nll * m) / n, logits, experts, probs)


def _f32(params):
    return {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}


@functools.partial(jax.jit, static_argnums=(0, 4))
def _forward(key, params, batch, routing, remat):
    return _run(dict(key), params, batch, routing, remat)


def forward(config: dict, params: dict, batch: dict, routing=None):
    """`batch`: clean_ids, noisy_ids (B, S) int32, masked (B, S) bool,
    inv_t (B, S) float32.  `routing`: per layer (T, k) expert indices
    to use, T = B * 2 S.  -> {"loss", "ce", "logits" (B, S, V), "experts",
    "probs"} in float32."""
    loss, (ce, logits, experts, probs) = _forward(
        _key(config), _f32(params), batch, routing, False)
    return {"loss": loss, "ce": ce, "logits": logits, "experts": experts,
            "probs": probs}


@functools.partial(jax.jit, static_argnums=(0, 5))
def _grads(key, leaves, rest, batch, routing, remat):
    return jax.grad(lambda l: _run(dict(key), {**rest, **l}, batch,
                                   routing, remat)[0])(leaves)


def grads(config: dict, params: dict, batch: dict, routing=None,
          wrt=None, remat=False):
    """`jax.grad` of the loss with respect to the leaves named in `wrt`
    (default: all), as a dict."""
    params = _f32(params)
    names = list(params) if wrt is None else list(wrt)
    return _grads(_key(config), {k: params[k] for k in names},
                  {k: v for k, v in params.items() if k not in names},
                  batch, routing, remat)


def routing_agreement(experts, ref_experts, ref_probs, near_tie=NEAR_TIE):
    """How the system's choices `experts` (T, k) sit against this
    file's own: the share of (row, slot) picks that are not in the
    reference's top-k, and whether each of those is a near-tie — its
    reference probability within `near_tie` (relative) of the
    reference's k-th largest."""
    experts, ref_experts, ref_probs = (np.asarray(a) for a in (
        experts, ref_experts, ref_probs))
    differs = ~(experts[:, :, None] == ref_experts[:, None, :]).any(-1)
    kth = np.take_along_axis(ref_probs, ref_experts, axis=1).min(
        axis=1, keepdims=True)
    picked = np.take_along_axis(ref_probs, experts, axis=1)
    gap = np.where(differs, np.abs(picked - kth) / kth, 0.0)
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


def compare(loss: float, logits, ref_loss: float, ref_logits) -> dict:
    """System against reference: the 1/t-weighted loss and the logits at
    the probed masked positions (arrays of the same shape)."""
    diff = rel_rms(logits, ref_logits)
    loss_diff = abs(loss - ref_loss) / abs(ref_loss)
    return {
        "ok": bool(LOGITS_FLOOR < diff < LOGITS_TOLERANCE
                   and loss_diff < LOSS_TOLERANCE),
        "logits_rel_rms": diff, "loss_rel": loss_diff,
        "loss": loss, "reference_loss": ref_loss,
    }
