"""Ulysses sequence parallelism — all-to-all context sharding.

The reference has no sequence parallelism (SURVEY.md §5.7 'Absent in
the reference'); alongside ring attention this is the other standard
long-context decomposition (DeepSpeed-Ulysses, Jacobs et al. 2023):

  * activations live SEQUENCE-sharded (B, S/n, H, D) on the `sp` axis
    (linear layers see S/n tokens — that is the memory win);
  * for attention, one `lax.all_to_all` re-shards heads instead:
    (B, S/n, H, D) -> (B, S, H/n, D), so every device computes FULL
    softmax attention for its head group — no online-softmax ring
    bookkeeping, exact attention by construction;
  * a second all_to_all transposes back to sequence sharding.

Trade-off vs ring attention (parallel/ring_attention.py): Ulysses
moves 2 all_to_alls of the activations per attention call and needs
num_heads % n == 0, while ring moves K/V n times with ppermute but
supports any head count; both ride ICI.  Ulysses wins when heads are
plentiful and sequence is extreme (its attention math is a plain
batched matmul — MXU-friendly, no per-step rescaling).
"""

from __future__ import annotations


def _full_attention(q, k, v, scale, mask=None, is_causal=False):
    """Plain softmax attention, (B, S, H, D) layout, fp32 softmax."""
    import jax
    import jax.numpy as jnp

    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if is_causal:
        S = q.shape[1]
        cm = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(cm[None, None], s, -jnp.inf)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            # (B, S) keep-mask -> -inf on masked keys
            s = jnp.where(mask[:, None, None, :], s, -jnp.inf)
        else:
            # (B, S) ADDITIVE key bias (0 keep / large-negative mask),
            # the dispatcher's _mask_as_key_bias convention
            s = s + mask[:, None, None, :].astype(s.dtype)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    # fully-masked rows (all -inf): zero output, not NaN — same guard
    # as ring_attention_local's m_safe/denom clamp
    row_ok = jnp.isfinite(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(row_ok, p, 0.0).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def ulysses_attention(mesh, axis="sp"):
    """-> attn(q, k, v, mask=None, is_causal=False), q/k/v (B, S, H, D)
    GLOBAL arrays sharded on S over `axis`; mask (B, S) replicated.

    The returned callable runs under shard_map over `axis`; inside an
    outer shard_map, use `ulysses_attention_local` directly.
    """
    import jax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def attn(q, k, v, mask=None, is_causal=False, scale=None):
        n = mesh.shape[axis]
        assert q.shape[2] % n == 0, (
            f"ulysses needs num_heads {q.shape[2]} divisible by the "
            f"{axis} axis size {n}; use ring attention otherwise")

        def local(q, k, v, mask):
            return ulysses_attention_local(q, k, v, axis, mask=mask,
                                           is_causal=is_causal,
                                           scale=scale)

        spec = P(None, axis)
        mask_spec = P()
        return shard_map(
            local, mesh=mesh,
            in_specs=(spec, spec, spec, mask_spec),
            out_specs=spec, check_vma=False)(q, k, v, mask)

    return attn


def ulysses_attention_local(q, k, v, axis, mask=None, is_causal=False,
                            scale=None):
    """Per-device body: q/k/v (B, S/n, H, D) local shards; mask (B, S)
    full (replicated).  Returns the local (B, S/n, H, D) output."""
    import math

    from jax import lax

    def seq_to_heads(x):
        # (B, S/n, H, D) -> (B, S, H/n, D): split heads, gather seq
        return lax.all_to_all(x, axis, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    out = _full_attention(qh, kh, vh, scale, mask=mask,
                          is_causal=is_causal)
    return heads_to_seq(out)
