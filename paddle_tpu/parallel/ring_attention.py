"""Ring attention — sequence/context parallelism over a mesh axis.

The reference has NO sequence parallelism (SURVEY.md §5.7: 'Absent in the
reference'); its long-sequence story is LoD ragged tensors + recurrent
sub-blocks.  This module is the TPU-native long-context design the survey
calls for: shard the sequence dimension across a mesh axis and rotate K/V
blocks around the ring with `jax.lax.ppermute` (one ICI hop per step),
computing blockwise online-softmax attention against each visiting block —
O(S/n) activation memory per chip, full-sequence attention semantics
(Ring Attention, Liu et al. 2023; blockwise parallel transformers).

Usage (inside or outside shard_map):

    attn = ring_attention(mesh, axis="sp")
    out = attn(q, k, v, is_causal=True)   # q,k,v (B, S, H, D) sharded on S

The returned callable runs under shard_map over `axis`; XLA lays the
ppermute on the ICI ring.
"""

from __future__ import annotations

import functools


def _block_attn(q, k, v, scale, causal_mask):
    """One local block pair: returns (unnormalized acc, rowmax m, rowsum l).

    q (B, Sq, H, D), k/v (B, Sk, H, D); causal_mask (Sq, Sk) bool or None.
    """
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal_mask is not None:
        s = jnp.where(causal_mask[None, None], s, -jnp.inf)
    m = jnp.max(s, axis=-1)                          # (B, H, Sq)
    # guard fully-masked rows: exp(-inf - -inf) -> exp(0); zero them via l
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1)                          # (B, H, Sq)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return acc.astype(jnp.float32), m_safe, l


def _combine(acc1, m1, l1, acc2, m2, l2):
    """Merge two partial online-softmax results."""
    import jax.numpy as jnp

    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = a1 * l1 + a2 * l2
    # broadcast (B,H,Sq) coefficients onto (B,Sq,H,D)
    b1 = jnp.transpose(a1, (0, 2, 1))[..., None]
    b2 = jnp.transpose(a2, (0, 2, 1))[..., None]
    return acc1 * b1 + acc2 * b2, m, l


def ring_attention_local(q, k, v, axis_name, is_causal=False, scale=None):
    """The per-shard body: call inside shard_map/pmap over `axis_name`.

    q/k/v: LOCAL sequence shards (B, S/n, H, D).  Rotates k/v around the
    ring; each step attends the local q against the visiting k/v block
    with global-position causal masking.
    """
    import jax
    import jax.numpy as jnp

    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    sq = q.shape[1]
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)

    rows = jnp.arange(sq)
    perm = [(i, (i + 1) % n) for i in range(n)]  # ring: send to next rank

    def causal_mask_for(src):
        # global positions: my rows = idx*sq + r ; visiting cols = src*sq + c
        q_pos = idx * sq + rows[:, None]
        k_pos = src * sq + rows[None, :]
        return q_pos >= k_pos

    def step(carry, i):
        acc, m, l, kk, vv = carry
        src = (idx - i) % n  # which rank's block is visiting
        if is_causal:
            mask = causal_mask_for(src)
        else:
            mask = None
        a2, m2, l2 = _block_attn(q, kk, vv, scale, mask)
        acc, m, l = _combine(acc, m, l, a2, m2, l2)
        kk = jax.lax.ppermute(kk, axis_name, perm)
        vv = jax.lax.ppermute(vv, axis_name, perm)
        return (acc, m, l, kk, vv), None

    b, _, h, _ = q.shape
    acc0 = jnp.zeros((b, sq, h, d), jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf)
    l0 = jnp.zeros((b, h, sq))
    (acc, m, l, _, _), _ = jax.lax.scan(
        step, (acc0, m0, l0, k, v), jnp.arange(n))
    denom = jnp.transpose(jnp.maximum(l, 1e-20), (0, 2, 1))[..., None]
    return (acc / denom).astype(q.dtype)


def ring_attention(mesh, axis="sp"):
    """Build a full-array ring-attention callable: q/k/v (B, S, H, D)
    (any resident sharding); runs shard_map over `axis` with batch
    replicated and sequence sharded."""
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def call(q, k, v, is_causal=False, scale=None):
        fn = functools.partial(ring_attention_local, axis_name=axis,
                               is_causal=is_causal, scale=scale)
        spec = P(None, axis, None, None)
        return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

    return call
