"""Expert parallelism — Switch-style top-1 MoE over a mesh axis.

The reference has NO MoE/expert parallelism (SURVEY.md §2.9 "NOT
present in the reference"); like ring attention (§5.7) this is part of
the TPU-native scale story the survey calls for.  Design (Switch
Transformer, Fedus et al. 2021, and the GShard dispatch algebra):

  * experts are sharded over the `ep` mesh axis (each device holds
    n_experts / ep_size expert FFNs);
  * tokens are data-sharded over the same axis group; each shard
    routes its own tokens (top-1 gate), builds a capacity-bounded
    dispatch tensor with one-hot algebra (no host-side gather), and
    exchanges token groups with `jax.lax.all_to_all` — the single
    collective expert parallelism needs, riding ICI;
  * combine is the transpose of dispatch, weighted by the gate
    probability; dropped tokens (over capacity) contribute zero, the
    caller's residual connection carries them — standard Switch
    semantics;
  * the load-balance auxiliary loss is E * sum(f_e * p_e) over the
    LOCAL shard (Switch eq. 4); psum-averaging it over the axis is the
    caller's choice when composing the total loss.

Everything is einsum/one-hot algebra on static shapes: XLA tiles the
dispatch/combine contractions onto the MXU, and the same code runs
under jit on one device (ep_size=1) or under shard_map on a pod axis.
"""

from __future__ import annotations

import math


def init_moe_params(rng, n_experts, d_model, d_ff, dtype=None):
    """{wg, w1, b1, w2, b2} with experts stacked on dim 0 of w1/w2."""
    import jax.numpy as jnp
    import numpy as np

    r = np.random.RandomState(rng) if isinstance(rng, int) else rng
    s1 = math.sqrt(2.0 / d_model)
    s2 = math.sqrt(2.0 / d_ff)
    p = {
        "wg": r.uniform(-s1, s1, (d_model, n_experts)),
        "w1": r.uniform(-s1, s1, (n_experts, d_model, d_ff)),
        "b1": np.zeros((n_experts, d_ff)),
        "w2": r.uniform(-s2, s2, (n_experts, d_ff, d_model)),
        "b2": np.zeros((n_experts, d_model)),
    }
    dt = dtype or jnp.float32
    return {k: jnp.asarray(v, dt) for k, v in p.items()}


def _dispatch_mask(gate_probs, capacity):
    """gate_probs (T, E) -> (combine (T, E, C), gate (T,), aux scalar).

    One-hot dispatch algebra (GShard): token t goes to its argmax
    expert at the position given by its running rank there, dropped if
    the rank exceeds `capacity`.
    """
    import jax
    import jax.numpy as jnp

    n_experts = gate_probs.shape[-1]
    expert = jnp.argmax(gate_probs, axis=-1)               # (T,)
    gate = jnp.take_along_axis(gate_probs, expert[:, None],
                               axis=-1)[:, 0]              # (T,)
    onehot = jax.nn.one_hot(expert, n_experts,
                            dtype=gate_probs.dtype)        # (T, E)
    rank = jnp.cumsum(onehot, axis=0) - onehot             # rank within e
    rank_t = jnp.sum(rank * onehot, axis=-1)               # (T,)
    keep = rank_t < capacity
    pos = jax.nn.one_hot(rank_t.astype(jnp.int32), capacity,
                         dtype=gate_probs.dtype)           # (T, C)
    dispatch = onehot[:, :, None] * pos[:, None, :] \
        * keep[:, None, None].astype(gate_probs.dtype)     # (T, E, C)
    # Switch aux loss: fraction routed x mean prob, summed over experts
    f = jnp.mean(onehot, axis=0)
    pbar = jnp.mean(gate_probs, axis=0)
    aux = n_experts * jnp.sum(f * pbar)
    return dispatch, gate, aux


def switch_moe_local(params, x, n_experts, capacity_factor=1.25,
                     ep_axis=None):
    """Apply the MoE to LOCAL tokens x (T, H) -> (out (T, H), aux).

    With `ep_axis` (inside shard_map): params' w1/b1/w2/b2 hold only
    this shard's experts (leading dim n_experts / ep_size) and token
    groups are exchanged with all_to_all.  Without it: all experts are
    local (single-device execution, the parity oracle).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    t_tokens, d_model = x.shape
    capacity = int(math.ceil(t_tokens * capacity_factor / n_experts))
    capacity = max(capacity, 1)

    logits = x @ params["wg"].astype(x.dtype)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    dispatch, gate, aux = _dispatch_mask(probs, capacity)
    dispatch = dispatch.astype(x.dtype)

    # (E, C, H): expert-major token blocks
    xs = jnp.einsum("tec,th->ech", dispatch, x)

    ep = lax.psum(1, ep_axis) if ep_axis is not None else 1
    if ep_axis is not None:
        n_local = n_experts // ep
        # (ep, n_local, C, H) --all_to_all--> source-major blocks of
        # THIS device's experts
        xs = xs.reshape(ep, n_local, capacity, d_model)
        xs = lax.all_to_all(xs, ep_axis, split_axis=0, concat_axis=0,
                            tiled=False)
        # fold (src, C) into one token axis per local expert
        xs = xs.transpose(1, 0, 2, 3).reshape(n_local, ep * capacity,
                                              d_model)
    else:
        n_local = n_experts

    h = jnp.einsum("ets,esf->etf", xs, params["w1"].astype(x.dtype))
    h = jax.nn.gelu(h + params["b1"][:, None, :].astype(x.dtype))
    y = jnp.einsum("etf,efs->ets", h, params["w2"].astype(x.dtype))
    y = y + params["b2"][:, None, :].astype(x.dtype)

    if ep_axis is not None:
        y = y.reshape(n_local, ep, capacity, d_model) \
             .transpose(1, 0, 2, 3)
        y = lax.all_to_all(y, ep_axis, split_axis=0, concat_axis=0,
                           tiled=False)
        y = y.reshape(n_experts, capacity, d_model)

    out = jnp.einsum("tec,ech->th", dispatch, y)
    return out * gate[:, None].astype(x.dtype), aux


def build_switch_moe(mesh, n_experts, d_model, d_ff, ep_axis="ep",
                     dp_axis=None, capacity_factor=1.25, seed=0,
                     dtype=None):
    """-> (apply, params): apply(params, x) for x (B, S, H).

    Experts sharded over `ep_axis` (w1/b1/w2/b2 leading dim), tokens
    sharded over dp_axis x ep_axis, gate weights replicated; returns
    (out (B, S, H), aux_loss scalar psum-averaged over the token
    shards).
    """
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    assert n_experts % mesh.shape[ep_axis] == 0, \
        (n_experts, mesh.shape)
    n_shards = mesh.shape[ep_axis] * (
        mesh.shape[dp_axis] if dp_axis else 1)
    params = init_moe_params(seed, n_experts, d_model, d_ff,
                             dtype=dtype)
    token_axes = (dp_axis, ep_axis) if dp_axis else ep_axis
    p_spec = {"wg": P(), "w1": P(ep_axis), "b1": P(ep_axis),
              "w2": P(ep_axis), "b2": P(ep_axis)}
    def local(params, x):
        b, s, h = x.shape
        out, aux = switch_moe_local(
            params, x.reshape(b * s, h), n_experts,
            capacity_factor=capacity_factor, ep_axis=ep_axis)
        axes = [a for a in (dp_axis, ep_axis) if a]
        for a in axes:
            aux = jax.lax.pmean(aux, a)
        return out.reshape(b, s, h), aux

    shard_apply = shard_map(local, mesh=mesh,
                            in_specs=(p_spec, P(token_axes)),
                            out_specs=(P(token_axes), P()),
                            check_vma=False)

    def apply(params, x):
        assert x.shape[0] % n_shards == 0, (
            f"batch dim {x.shape[0]} must divide the {n_shards} "
            "token shards (dp x ep)")
        return shard_apply(params, x)

    return apply, params
