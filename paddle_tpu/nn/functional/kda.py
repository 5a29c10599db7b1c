"""Kimi Delta Attention (arXiv:2510.26692) in plain XLA: the gated
delta-rule recurrence with a per-channel decay, a token at a time (the
fallback for shapes the kernels refuse, and the tests' oracle), and
the float32 statement of its chunked form (`_local`: the oracle of
what `ops/pallas/kda.py` makes in VMEM).  No cell runs this file's
chunked form: since PR 35 both halves of it, chunk-local and
chunk-sequential, are inside the kernels `kda_fwd` / `kda_bwd`.  Also
the layer's elementwise work around the scan in plain XLA (`edge_pre`,
`edge_post`, `short_conv`): the oracle of `ops/pallas/kda_edge.py`'s
two passes and the path for what those kernels refuse; and Gated
DeltaNet's work before its scan (`gdn_pre`): the oracle of
`kda_edge.gdn_pre` and its fallback, whose β and g (`gdn_gate`) run as
XLA on either path.

The recurrence, for one head (S in R^{dk x dv}, float32, S_0 = 0):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = scale * S_t^T q_t

With u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t) it reads
S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T.  Inside a chunk of C = 64
tokens, with G_i the gate cumulated from the chunk's first token and
S_0 the state entering the chunk:

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)      (j < i, else 0)
    T    = (I + A)^{-1}
    W    = T Diag(beta) (K . exp(G))        U0 = T Diag(beta) V
    U    = U0 - W S_0
    o    = Qg S_0 + Aqk U       Qg = scale q . exp(G)
                                Aqk_ij = scale sum_c q_ic k_jc exp(G_ic - G_jc)  (j <= i)
    S_C  = Diag(d) S_0 + Kg^T U             Kg = k . exp(G_C - G),  d = exp(G_C)

W, U0, Qg, Kg, Aqk and d need no state: `_local` makes them for
every chunk at once, as batched float32 matmuls (the kernels make them
a chunk and head at a time, beside the three lines that need S_0).

Every exponent is a difference G_i - G_j with i >= j, or G_i alone: at
most 0.  exp(G_i - G_j) sits INSIDE the contraction over channels; a
factorised matmul (q . exp(G_i - G_ref)) (k . exp(G_ref - G_j))^T
keeps both exponents at most 0 only while G_ref lies between the two,
so the chunk is cut into sub-blocks of 16 rows: a sub-block of rows
against every EARLIER sub-block takes G_ref at its own first row, and
the four diagonal sub-blocks are formed from explicit pairwise
differences.  1 / exp(G) is never formed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 64
SUB = 16
_HI = jax.lax.Precision.HIGHEST


def recurrent(q, k, v, g, beta, scale):
    """The recurrence itself, a token at a time, in float32.  q, k, g
    (B, S, H, dk), v (B, S, H, dv), beta (B, S, H) -> o (B, S, H, dv)
    float32."""
    f32 = lambda a: jnp.moveaxis(a.astype(jnp.float32), 1, 0)
    b, _, h, dk = q.shape

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum(
            "bhk,bhkv->bhv", k_t, state, precision=_HI))
        state = state + k_t[..., None] * u[..., None, :]
        return state, scale * jnp.einsum("bhk,bhkv->bhv", q_t, state,
                                         precision=_HI)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                        tuple(f32(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _substitute(a):
    """(I + a)^{-1} for strictly lower-triangular a (..., n, n), by
    forward substitution a row at a time: backward stable, where the
    finite Neumann product is not once keys repeat."""
    n = a.shape[-1]
    t = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    for i in range(1, n):
        t = t.at[..., i, :].add(-jnp.einsum("...j,...jc->...c",
                                            a[..., i, :], t, precision=_HI))
    return t


def _merge(ta, tb, a21):
    """The lower-left block of [[La, 0], [a21, Lb]]^{-1}."""
    mm = functools.partial(jnp.matmul, precision=_HI)
    return -mm(mm(tb, a21), ta)


def _stack2(ta, tb, tba):
    z = jnp.zeros_like(tba)
    return jnp.concatenate([jnp.concatenate([ta, z], -1),
                            jnp.concatenate([tba, tb], -1)], -2)


@jax.custom_vjp
def inv_unit_lower(a):
    """(I + a)^{-1} for strictly lower-triangular a (..., 64, 64):
    the four diagonal 16-blocks by substitution, merged twice."""
    n = a.shape[-1] // SUB
    a4 = a.reshape(a.shape[:-2] + (n, SUB, n, SUB))
    t = _substitute(jnp.stack([a4[..., i, :, i, :] for i in range(n)], -3))
    blocks = [t[..., i, :, :] for i in range(n)]
    size = SUB
    while len(blocks) > 1:
        blocks = [_stack2(ta, tb, _merge(
            ta, tb, a[..., (2 * i + 1) * size:(2 * i + 2) * size,
                      2 * i * size:(2 * i + 1) * size]))
            for i, (ta, tb) in enumerate(zip(blocks[::2], blocks[1::2]))]
        size *= 2
    return blocks[0]


def _inv_fwd(a):
    t = inv_unit_lower(a)
    return t, t


def _inv_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    mm = functools.partial(jnp.matmul, precision=_HI)
    return (-mm(mm(tt, dt), tt),)


inv_unit_lower.defvjp(_inv_fwd, _inv_bwd)


def _local(q, k, v, g, beta, scale):
    """The quantities of the chunked form that need no state, every
    chunk at once: (B, N, C, H, d) operands in float32 (C = 64) -> W,
    U0, Qg, Kg (B, N, C, H, d), Aqk (B, N, H, C, C), d (B, N, H, dk).
    The pairwise (16, 16, dk) temporaries make it a test-size
    function."""
    b, n, c, h, dk = q.shape
    nb = c // SUB
    gc = jnp.cumsum(g, axis=2)
    sub = lambda a: a.reshape((b, n, nb, SUB) + a.shape[3:])
    g4, k4 = sub(gc), sub(k)
    ref = g4[:, :, :, :1]                               # G at a sub-block's head
    e_in = jnp.exp(g4 - ref)
    # keys of EARLIER sub-blocks against each sub-block's head: the
    # later ones (exponent > 0) are masked out below, clamp them here
    k_ref = k[:, :, None] * jnp.exp(jnp.minimum(
        ref - gc[:, :, None], 0.0))                     # (B, N, nb, C, H, dk)
    off = lambda x: jnp.einsum("bnIihc,bnIjhc->bnhIij", sub(x) * e_in,
                               k_ref, precision=_HI)
    earlier = (jnp.arange(c)[None, None, :] // SUB
               < jnp.arange(nb)[:, None, None])         # (nb, 1, C)
    # the diagonal sub-blocks, from explicit pairwise differences
    rows, cols = jnp.arange(SUB)[:, None], jnp.arange(SUB)[None, :]
    seen = (rows >= cols)[..., None, None]              # (i, j, 1, 1)
    pair = jnp.exp(jnp.where(seen, g4[:, :, :, :, None]
                             - g4[:, :, :, None, :], 0.0))
    pair = jnp.where(seen, pair * k4[:, :, :, None, :], 0.0)
    diag = lambda x: jnp.einsum(
        "bnIijh->bnhIij", jnp.sum(sub(x)[:, :, :, :, None] * pair, axis=-1))
    eye = jnp.eye(nb, dtype=q.dtype)

    def whole(x):                                       # -> (B, N, H, C, C)
        full = jnp.where(earlier, off(x), 0.0).reshape(b, n, h, c, c)
        return full + jnp.einsum("bnhIij,IJ->bnhIiJj", diag(x),
                                 eye).reshape(b, n, h, c, c)

    beta_h = jnp.moveaxis(beta, 2, 3)[..., None]        # (B, N, H, C, 1)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    a = jnp.where(strict, whole(k) * beta_h, 0.0)
    t = inv_unit_lower(a)
    e_g = jnp.exp(gc)
    bk = beta[..., None] * k * e_g
    bv = beta[..., None] * v
    w = jnp.einsum("bnhij,bnjhc->bnihc", t, bk, precision=_HI)
    u0 = jnp.einsum("bnhij,bnjhc->bnihc", t, bv, precision=_HI)
    last = gc[:, :, -1]                                 # (B, N, H, dk)
    return (w, u0, scale * q * e_g, k * jnp.exp(last[:, :, None] - gc),
            scale * whole(q), jnp.exp(last))


def short_conv(x, taps):
    """Depthwise causal convolution along the sequence as shifted
    multiply-adds: x (B, S, D), taps (width, D) -> y_t = sum_i
    taps[i] x_{t - (width - 1) + i} (tap width - 1 on the token
    itself), zeros before the sequence.  Float32 sum, x's dtype out; no
    `conv` op and no transpose to channels-first."""
    width = taps.shape[0]
    xf = x.astype(jnp.float32)
    padded = jnp.pad(xf, ((0, 0), (width - 1, 0), (0, 0)))
    s = x.shape[1]
    y = sum(padded[:, i:i + s] * taps[i].astype(jnp.float32)
            for i in range(width))
    return y.astype(x.dtype)


def edge_pre(q_raw, k_raw, v_raw, f, q_taps, k_taps, v_taps, dt_bias, a_log):
    """The layer's work before the scan, in float32 with one rounding
    at the end: from the four projections (B, S, H * d) and the
    parameters (taps (width, H * d), dt_bias (H * d,), a_log (H,))

        q = unit(SiLU(conv(q_raw))), k likewise, v = SiLU(conv(v_raw))
        g = -exp(a_log)[h] softplus(f + dt_bias)

    with unit(y) = y rsqrt(sum over the head's channels of y^2 + 1e-6)
    -> q, k, v (B, S, H * d) in the operands' dtype, g the same in
    float32."""
    f32 = jnp.float32
    heads = a_log.shape[0]
    per_head = lambda a: a.reshape(a.shape[:2] + (heads, -1))

    def conv_silu(x, taps):
        return jax.nn.silu(short_conv(x.astype(f32), taps))

    def unit(y):
        y = per_head(y)
        return (y * jax.lax.rsqrt(jnp.sum(jnp.square(y), -1, keepdims=True)
                                  + 1e-6)).reshape(q_raw.shape)

    g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
        per_head(f.astype(f32) + dt_bias.astype(f32)))
    return (unit(conv_silu(q_raw, q_taps)).astype(q_raw.dtype),
            unit(conv_silu(k_raw, k_taps)).astype(k_raw.dtype),
            conv_silu(v_raw, v_taps).astype(v_raw.dtype),
            g.reshape(f.shape))


def gdn_pre(qkv, ba, taps, dt_bias, a_log, key_heads):
    """Gated DeltaNet's work between its projections and its scan, in
    float32 with one rounding of q, k, v at the end: from qkv = [q~ | k~
    | v~] (B, S, 2 Hk d + Hv d), ba = [b | a] (B, S, 2 Hv), the taps
    (width, 2 Hk d + Hv d), dt_bias and a_log (Hv,)

        [q', k', v] = SiLU(conv([q~ | k~ | v~]))
        q = unit(q'), k = unit(k')            per key head
        beta = sigmoid(b)
        g = -exp(a_log) softplus(a + dt_bias)  one scalar a value head

    -> q, k (B, S, Hk, d), v (B, S, Hv, dv) in qkv's dtype, g, beta (B,
    S, Hv) float32 — what `kda_attention` takes as a decay a head."""
    f32 = jnp.float32
    heads = a_log.shape[0]
    b, s, _ = qkv.shape
    y = jax.nn.silu(short_conv(qkv.astype(f32), taps))
    d = y.shape[-1] // (2 * key_heads + heads)          # 2 Hk d + Hv d
    q, k, v = jnp.split(y, [key_heads * d, 2 * key_heads * d], axis=-1)
    unit = lambda a: (lambda a: a * jax.lax.rsqrt(
        jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6))(
            a.reshape(b, s, key_heads, d))
    g, beta = gdn_gate(ba, dt_bias, a_log)
    return (unit(q).astype(qkv.dtype), unit(k).astype(qkv.dtype),
            v.reshape(b, s, heads, -1).astype(qkv.dtype), g, beta)


def gdn_gate(ba, dt_bias, a_log):
    """`gdn_pre`'s two scalars a value head and token: ba = [b | a] (B,
    S, 2 Hv), dt_bias and a_log (Hv,) -> g = -exp(a_log) softplus(a +
    dt_bias), beta = sigmoid(b), (B, S, Hv) float32 each."""
    f32 = jnp.float32
    heads = a_log.shape[0]
    ba = ba.astype(f32)
    beta = jax.nn.sigmoid(ba[..., :heads])
    g = -jnp.exp(a_log.astype(f32)) * jax.nn.softplus(
        ba[..., heads:] + dt_bias.astype(f32))
    return g, beta


def edge_post(o, gate, weight, epsilon, activation="sigmoid"):
    """The layer's work after the scan: RMSNorm over each head's
    channels times the learned scale `weight` (d,) times
    `activation`(gate) — "sigmoid" (Kimi Delta Attention) or "silu"
    (Gated DeltaNet) —, in float32 with one rounding at the end.  o,
    gate (B, S, H * d) -> (B, S, H * d) in o's dtype."""
    f32 = jnp.float32
    act = jax.nn.silu if activation == "silu" else jax.nn.sigmoid
    y = o.astype(f32).reshape(o.shape[:2] + (-1, weight.shape[0]))
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                          + epsilon) * weight.astype(f32)
    return (y.reshape(o.shape) * act(gate.astype(f32))).astype(o.dtype)
