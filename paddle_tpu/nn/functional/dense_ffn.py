"""The transformer's dense feed-forward block as plain XLA ops:

    out = dropout(act(x @ w1 + b1), p) @ w2 + b2

`F.fused_feedforward` is its only caller.  The dropout mask is a
stateless hash of (row, column, seed) — the attention kernels' lowbias32
(ops/pallas/attention.py:_keep_mask) — so it is the same under any
sharding and is recomputed, never stored, by the backward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _erf(x):
    """erf via Abramowitz-Stegun 7.1.26 (max abs err 1.5e-7), from
    abs/exp/mul only.  Whether `lax.erf` is faster on the chip is open
    (ROADMAP, named debts): swapping it changes bits."""
    a1, a2, a3 = 0.254829592, -0.284496736, 1.421413741
    a4, a5, p = -1.453152027, 1.061405429, 0.3275911
    s = jnp.sign(x)
    ax = jnp.abs(x)
    t = 1.0 / (1.0 + p * ax)
    poly = t * (a1 + t * (a2 + t * (a3 + t * (a4 + t * a5))))
    return s * (1.0 - poly * jnp.exp(-ax * ax))


def _act(h, activation):
    if activation == "gelu":
        # exact-erf gelu (the repo's GELU()/F.gelu default)
        return h * 0.5 * (1.0 + _erf(h * 0.7071067811865476))
    if activation == "gelu_tanh":
        return jax.nn.gelu(h, approximate=True)
    if activation == "relu":
        return jax.nn.relu(h)
    raise NotImplementedError(activation)


def _ffn_keep(seed, t0, f0, rows, cols, dropout_p):
    """Stateless keep mask for the (rows, cols) tile whose first
    element sits at absolute (t0, f0): lowbias32 on the coordinates.
    The one caller hashes the whole (T, F) array from (0, 0); the
    offsets stay because dropping the `0 +` changes the lowered text,
    and go with `_erf` when that is reopened (ROADMAP, named debts)."""
    r = (t0 + lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
         ).astype(jnp.uint32)
    c = (f0 + lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
         ).astype(jnp.uint32)
    x = (r * jnp.uint32(0x9E3779B1)) ^ (c * jnp.uint32(0x85EBCA77))
    x = x ^ (seed.astype(jnp.uint32) * jnp.uint32(0x165667B1))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(min(int(dropout_p * 2 ** 32), 2 ** 32 - 1))
    return x >= thresh


def dense_ffn(x, w1, b1, w2, b2, activation="gelu", dropout_p=0.0,
              dropout_seed=None):
    """x: (..., H); w1 (H, F); w2 (F, H).  Returns (..., H).
    `dropout_seed`: int32[1] (`_seed_from_key`), zeros when None."""
    lead = x.shape[:-1]
    H = x.shape[-1]
    F = w1.shape[1]
    T = 1
    for d in lead:
        T *= d
    xt = x.reshape(T, H)
    h = _act(jnp.dot(xt, w1, preferred_element_type=jnp.float32)
             .astype(x.dtype) + b1, activation)
    if dropout_p > 0.0:
        seed = (dropout_seed if dropout_seed is not None
                else jnp.zeros((1,), jnp.int32))
        keep = _ffn_keep(seed.reshape(()), 0, 0, T, F, dropout_p)
        h = jnp.where(keep, h / (1.0 - dropout_p), jnp.zeros_like(h))
    out = jnp.dot(h, w2, preferred_element_type=jnp.float32) \
        .astype(x.dtype) + b2
    return out.reshape(lead + (H,))
