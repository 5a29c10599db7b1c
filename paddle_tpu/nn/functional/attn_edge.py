"""The gated attention layer's elementwise work after its kernels in
plain XLA: the per-head sigmoid gate (`head_gate`) — the oracle of
`ops/pallas/attn_edge.py`'s pass and the path for what its kernels
refuse.  The rotation before the kernels is `F.rotary_embedding`."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def head_gate(o, g):
    """o (B, S, H, d) times sigmoid(g) (B, S, H) over each head's
    channels, in float32 with one rounding at the end -> o's shape and
    dtype."""
    return (o.astype(jnp.float32) * jax.nn.sigmoid(g)[..., None]).astype(
        o.dtype)
