"""Expert parallelism: two expert layers over a mesh axis.

The reference has NO MoE/expert parallelism (SURVEY.md §2.9 "NOT
present in the reference"); like ring attention (§5.7) this is part of
the TPU-native scale story the survey calls for.

`routed_moe_local` (`nn.RoutedMoE`; the second half of this file) is
the layer of today's open expert models: top-k of n_routed, dropless,
told which experts it holds — a sort of the visits by expert, grouped
matmuls over a walk of the sorted order, no capacity and no (T, E, C)
tensor.  New models use it.

`switch_moe_local` (`nn.SwitchMoE`; the first half) is kept for the
capacity-drop semantics of the Switch Transformer (Fedus et al. 2021,
with the GShard dispatch algebra) only — top-1, a capacity, dropped
tokens — and shares no code with the other:

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

There everything is einsum/one-hot algebra on static shapes: XLA tiles
the dispatch/combine contractions onto the MXU, and the same code runs
under jit on one device (ep_size=1) or under shard_map on a pod axis.
"""

from __future__ import annotations

import functools
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


# ---------------------------------------------------------------------------
# Dropless top-k routing over the experts a device holds
# ---------------------------------------------------------------------------
#
# The layer of today's open expert models (gated SiLU experts, softmax
# router, k of n_routed per token, the k weights renormalised), told
# which experts it HOLDS: it routes over all n_routed, computes the
# visits that land on its own `count` experts starting at `first`, and
# leaves out what absent experts would have added.  One chip of an
# expert-parallel group runs it as it is, without an exchange; under
# `ep_axis` the shards exchange rows with `all_to_all`.
#
# No (T, E, C) tensor, no capacity, no dropped token: the T*k visits
# are sorted by expert (absent ones last) and the sorted order is
# walked in chunks of `chunk` visits — gather the chunk's rows, three
# grouped matmuls over its ragged groups (`jax.lax.ragged_dot`, a
# Mosaic grouped-matmul call on a TPU), scatter-add the weighted
# results.  The walk is a loop over the ceil(held visits / chunk)
# chunks that hold a held visit and no further, so the work follows the
# visits that landed here while every shape stays static, and the
# memory is a chunk's whatever the routing does.  The backward pass
# walks the same chunks (`jax.vjp` of one chunk's function), which is
# why the walk is a `custom_vjp`: reverse mode through the loop would
# store a dense expert-weight cotangent per chunk.
#
# The visit PLAN — the sorted order, its rows and expert ids, the
# number of held visits, the visits' weights — is what the walk reads
# and all it keeps of the routing.  Where the shapes allow, it comes
# from ONE sort of one int32 key a visit, `(expert << bits) | index`
# (`_packed_key_bits`): the keys are distinct, so the order is the
# stable one, and order and expert id are bit fields of the sorted key.
# Its arrays, and the router's choice they are made from, carry the
# `jax.ad_checkpoint.checkpoint_name` "moe_plan": a model that
# recomputes its layers under `jax.checkpoint(policy=
# save_only_these_names("moe_plan"))` chooses and sorts once a layer a
# step, not once a pass, and its backward pass differentiates the
# choice its forward pass made.

def init_routed_moe_params(rng, n_routed, d_model, d_ff, held=None,
                           dtype=None):
    """{wr (H, n_routed), wg / wu (count, H, F), wd (count, F, H)} for
    the experts `held = (first, count)` (default: all)."""
    import jax.numpy as jnp
    import numpy as np

    r = np.random.RandomState(rng) if isinstance(rng, int) else rng
    count = n_routed if held is None else held[1]
    s1, s2 = d_model ** -0.5, d_ff ** -0.5
    p = {"wr": r.normal(0, s1, (d_model, n_routed)),
         "wg": r.normal(0, s1, (count, d_model, d_ff)),
         "wu": r.normal(0, s1, (count, d_model, d_ff)),
         "wd": r.normal(0, s2, (count, d_ff, d_model))}
    return {k: jnp.asarray(v, dtype or jnp.float32) for k, v in p.items()}


def route_top_k(x, wr, top_k, renormalize=True, scoring="softmax",
                bias=None, scale=1.0):
    """x (T, H), wr (H, n_routed) -> (experts (T, k) int32, weights
    (T, k) float32): scores over all n_routed and top-k in float32,
    the k weights divided by their sum where `renormalize`.

    `scoring` "softmax": the scores are the softmax of the logits.
    "sigmoid" (DeepSeek-V3 §2.1.2): each expert's score is the sigmoid
    of its logit; the k experts are the top-k of `score + bias` —
    `bias` (n_routed,) the selection bias the training step moves
    towards a balanced load, a constant here: no gradient reaches it —
    while the weights are the SCORES at those k (the bias chooses and
    never weighs), renormalised, times `scale` (the routed scaling
    factor).

    The choice is made once: `experts` carries the visit plan's name
    ("moe_plan"), and the weights are read from the scores AT those
    ids, so a layer recomputed under a policy that keeps the plan
    differentiates the router at the forward pass's choice — a second
    top-k over recomputed scores may order near-ties otherwise, and the
    kept plan's visits would meet another expert's weight."""
    import jax
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    with jax.named_scope("router"):
        logits = jnp.dot(x, wr.astype(x.dtype),
                         preferred_element_type=jnp.float32)
        if scoring == "softmax":
            probs = choose_by = jax.nn.softmax(logits, axis=-1)
        elif scoring == "sigmoid":
            from ..profiler import stat_add

            stat_add("moe_sigmoid_router_total")
            probs = choose_by = jax.nn.sigmoid(logits)
            if bias is not None:
                choose_by = probs + jax.lax.stop_gradient(
                    bias.astype(jnp.float32))
        else:
            raise ValueError(f"scoring {scoring!r}: softmax or sigmoid")
        experts = checkpoint_name(
            jax.lax.top_k(choose_by, top_k)[1].astype(jnp.int32),
            "moe_plan")
        # compares and sums, forward and backward: no T*k-sized gather
        # or scatter-add
        chosen = experts[:, :, None] == jnp.arange(wr.shape[1],
                                                   dtype=jnp.int32)
        weights = jnp.sum(jnp.where(chosen, probs[:, None, :], 0), axis=-1)
        if renormalize:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        if scale != 1.0:
            weights = weights * scale
    return experts, weights


def router_load(experts, n_routed):
    """(n_routed,) int32: the rows each of ALL the router's outputs was
    chosen by, held here or not — what the selection bias's update
    reads.  Compares and sums, as `_rows_per_expert`."""
    import jax

    with jax.named_scope("router"):
        return jax.lax.stop_gradient(
            _rows_per_expert(experts.reshape(-1), n_routed))


def update_selection_bias(bias, load, rate):
    """DeepSeek-V3's auxiliary-loss-free balancing, one step: an expert
    that got fewer rows than the mean becomes likelier to be chosen, one
    that got more less so — `bias + rate * sign(mean(load) - load)`,
    `load` (..., n_routed) the counts `router_load` gives."""
    import jax.numpy as jnp

    load = load.astype(jnp.float32)
    return bias + rate * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load).astype(bias.dtype)


def _chunk_ffn(xs, eid, w, wg, wu, wd):
    """w * down(silu(gate(x)) * up(x)) in float32, of rows `xs` (C, H)
    sorted by local expert id `eid` (C,), absent rows (id == count)
    last; `w` (C,) the rows' weights.  Rows past the groups are
    whatever the grouped matmul left there: every product is masked, so
    that they are zeros in and out, forward and backward.

    All of it under the scope `experts`, the masks and the weighting
    too: XLA makes the grouped-matmul kernels without the program's
    names, and `obs.opprof` names such an instruction after the one
    that reads its result."""
    import jax
    import jax.numpy as jnp

    count = wg.shape[0]
    valid = (eid < count)[:, None]
    with jax.named_scope("experts"):
        sizes = jnp.bincount(eid, length=count + 1)[:count].astype(
            jnp.int32)
        xs = jnp.where(valid, xs, 0)
        gate = jnp.where(valid, jax.lax.ragged_dot(xs, wg, sizes), 0)
        up = jnp.where(valid, jax.lax.ragged_dot(xs, wu, sizes), 0)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(xs.dtype)
        y = jnp.where(valid, jax.lax.ragged_dot(act, wd, sizes), 0)
        return y.astype(jnp.float32) * w[:, None]


def _walk(n_valid, chunk, carry, active):
    """`carry = active(carry, start)` for the ceil(n_valid / chunk)
    chunks of the sorted visits that hold a held one, in order; the
    chunks after them are not visited."""
    from jax import lax

    return lax.fori_loop(0, (n_valid + chunk - 1) // chunk,
                         lambda c, carry: active(carry, c * chunk), carry)


@functools.cache
def _make_visits_ffn():
    """The chunk walk as a `custom_vjp` (built on first use: this
    module imports jax inside its functions)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def cuts(start, chunk, *arrays):
        return (lax.dynamic_slice_in_dim(a, start, chunk) for a in arrays)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(9,))
    def visits_ffn(x, wg, wu, wd, w, order, tok, eid, n_valid, chunk):
        """out[t] = sum over held visits v of token t of w[v] *
        ffn_{eid[v]}(x[t]): x (T, H); `order`, `tok`, `eid` (M,) the
        plan's visits, their rows and expert ids in expert-sorted
        order, M a multiple of `chunk`; w (M,) the weights BY VISIT
        (unsorted: a chunk gathers its own) -> (out (T, H) in x's
        dtype, visits computed)."""
        count = wg.shape[0]

        def active(carry, start):
            out, done = carry
            order_c, tok_c, eid_c = cuts(start, chunk, order, tok, eid)
            with jax.named_scope("dispatch"):
                xs, w_c = x[tok_c], w[order_c]
            y = _chunk_ffn(xs, eid_c, w_c, wg, wu, wd)
            with jax.named_scope("combine"):
                out = out.at[tok_c].add(y)
            return out, done + jnp.sum(eid_c < count, dtype=jnp.int32)

        out, done = _walk(n_valid, chunk,
                          (jnp.zeros(x.shape, jnp.float32), jnp.int32(0)),
                          active)
        return out.astype(x.dtype), done

    def fwd(*args):
        return visits_ffn(*args), args[:-1]

    def bwd(chunk, res, cts):
        x, wg, wu, wd, w, order, tok, eid, n_valid = res
        dout = cts[0]

        def active(carry, start):
            dx, dw, dwg, dwu, dwd = carry
            order_c, tok_c, eid_c = cuts(start, chunk, order, tok, eid)
            with jax.named_scope("dispatch"):
                xs, w_c = x[tok_c], w[order_c]
            with jax.named_scope("combine"):
                dy = dout[tok_c]
            _, vjp = jax.vjp(
                lambda xs, p: _chunk_ffn(xs, eid_c, **p), xs,
                {"w": w_c, "wg": wg, "wu": wu, "wd": wd})
            with jax.named_scope("experts"):
                dxs, grads = vjp(dy.astype(jnp.float32))
                dxs = dxs.astype(jnp.float32)
                dwg, dwu, dwd = (acc + grads[k].astype(jnp.float32)
                                 for acc, k in ((dwg, "wg"), (dwu, "wu"),
                                                (dwd, "wd")))
            with jax.named_scope("dispatch"):
                dx = dx.at[tok_c].add(dxs)
                # a visit sits in one chunk: the walked chunks' weights
                # get their cotangent, the others keep their zero
                dw = dw.at[order_c].set(grads["w"], unique_indices=True)
            return dx, dw, dwg, dwu, dwd

        f32 = lambda a: jnp.zeros(a.shape, jnp.float32)
        dx, dw, dwg, dwu, dwd = _walk(
            n_valid, chunk, (f32(x), f32(w), f32(wg), f32(wu), f32(wd)),
            active)
        return (dx.astype(x.dtype), dwg.astype(wg.dtype),
                dwu.astype(wu.dtype), dwd.astype(wd.dtype),
                dw.astype(w.dtype), None, None, None, None)

    visits_ffn.defvjp(fwd, bwd)
    return visits_ffn


def _visits_ffn(*args):
    return _make_visits_ffn()(*args)


def _packed_key_bits(count, visits):
    """Bits a visit's index takes in the packed sort key `(expert <<
    bits) | index`, or None where the largest key — expert `count`, a
    visit that lands elsewhere — does not fit int32: the plan is then
    made by a stable two-operand sort."""
    bits = max(visits - 1, 1).bit_length()
    return bits if (count + 1) << bits <= 1 << 31 else None


def _visit_plan(local_expert, weights, count, chunk, per_row=1):
    """The plan the walk reads.  `local_expert` (M,) int32 — `count`
    for a visit that lands elsewhere — and `weights` (M,), both by
    visit, visit v belonging to row v // per_row -> ((w, order, tok,
    eid, n_valid), chunk): the visits in the stable order of their
    expert, the rows and expert ids in that order, padded with absent
    visits of row 0 to a multiple of the chunk; the weights by visit,
    padded alike (a padding visit's `order` is its own index there, so
    that the order holds no index twice); the number of held visits.
    Every array is named "moe_plan" for `jax.checkpoint` policies."""
    import jax.numpy as jnp
    from jax.ad_checkpoint import checkpoint_name

    from ..profiler import stat_add

    m = local_expert.shape[0]
    chunk = min(chunk, m)
    pad = -m % chunk
    bits = _packed_key_bits(count, m)
    if bits is not None:
        stat_add("moe_plan_packed_total")
        key = jnp.sort((local_expert << bits)
                       | jnp.arange(m, dtype=jnp.int32))
        order, eid = key & ((1 << bits) - 1), key >> bits
    else:
        stat_add("moe_plan_two_operand_total")
        order = jnp.argsort(local_expert, stable=True).astype(jnp.int32)
        eid = local_expert[order]
    tok = order // per_row
    if pad:
        order = jnp.concatenate([order, jnp.arange(m, m + pad,
                                                   dtype=jnp.int32)])
        tok = jnp.concatenate([tok, jnp.zeros((pad,), jnp.int32)])
        eid = jnp.concatenate([eid, jnp.full((pad,), count, jnp.int32)])
        weights = jnp.concatenate([weights,
                                   jnp.zeros((pad,), weights.dtype)])
    n_valid = jnp.sum(local_expert < count, dtype=jnp.int32)
    return tuple(checkpoint_name(a, "moe_plan") for a in (
        weights, order, tok, eid, n_valid)), chunk


def _rows_per_expert(local_expert, count):
    """(count,) int32, the visits of `local_expert` (M,) on each held
    expert: `count` compares and sums over the visits (a `bincount`
    is a scatter-add of M ones)."""
    import jax.numpy as jnp

    held = jnp.arange(count, dtype=local_expert.dtype)
    return jnp.sum(local_expert[None, :] == held[:, None], axis=1,
                   dtype=jnp.int32)


def default_chunk(visits, held_share):
    """Visits a chunk of the walk: a fair router's held visits (`visits`
    x `held_share`) fit in two chunks with a quarter to spare, rounded
    up to 1024.  A chunk costs its full gather and scatter however few
    of its rows are held, so the expected load must not sit AT a chunk
    boundary, where every layer of every step tosses a coin for one
    more chunk (half the expected load a chunk did that at the sdar
    cell: 1% of spread in the step rate; my chip runs, PR 28).  Other
    loads pass `chunk`."""
    two_chunks = 1.25 * visits * held_share
    return min(visits, max(1024, -(-int(two_chunks / 2) // 1024) * 1024))


def routed_moe_local(params, x, top_k, held=None, ep_axis=None,
                     renormalize=True, chunk=None, routing=None,
                     scoring="softmax", scale=1.0):
    """The routed expert layer on LOCAL rows x (T, H) -> (out (T, H),
    stats, experts (T, k) the router chose).

    params: `wr` (H, n_routed) and the held experts' `wg`, `wu`
    (count, H, F), `wd` (count, F, H); with `scoring` "sigmoid"
    optionally `br` (n_routed,), the selection bias (`route_top_k`;
    `scale`: the routed scaling factor).  `held = (first, count)` says
    which of the n_routed experts those are (default: all); the result
    is the part of the layer's output the held experts give, weighted
    by the w_i normalised over all k chosen.  With `ep_axis` (inside
    shard_map): shard i holds experts [i * count, (i + 1) * count),
    every row's visits go to their owners through `all_to_all` and
    come back, and the result is the whole layer's.  `chunk`: visits a
    step of the walk (default: `default_chunk`; tests pass a small one
    to walk several chunks).  `routing`: (experts, weights) to use
    instead of the router's own — only the dropless test's skewed
    routing comes in this way; no layer or model hands it on.

    stats (count + 2,) int32: rows each held expert computed, the
    visits routed (T * k), the held visits computed — what the
    `moe_*` counters are fed from; held visits dropped = 0 follows
    from the first and the last."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    t, h = x.shape
    n_routed = params["wr"].shape[1]
    count = params["wg"].shape[0]
    experts, weights = routing if routing is not None else route_top_k(
        x, params["wr"], top_k, renormalize, scoring, params.get("br"),
        scale)
    wg, wu, wd = (params[k].astype(x.dtype) for k in ("wg", "wu", "wd"))
    if chunk is None:
        chunk = default_chunk(t * top_k, count / n_routed)

    if ep_axis is None:
        first = 0 if held is None else held[0]
        assert held is None or held[1] == count, (held, count)
        with jax.named_scope("dispatch"):
            local = jnp.where((experts >= first) & (experts < first + count),
                              experts - first, count).reshape(-1)
            plan, chunk = _visit_plan(local, weights.reshape(-1), count,
                                      chunk, per_row=top_k)
        out, done = _visits_ffn(x, wg, wu, wd, *plan, chunk)
        rows = _rows_per_expert(local, count)
    else:
        ep = lax.psum(1, ep_axis)
        assert n_routed == ep * count, (n_routed, ep, count)
        # a destination's buffer holds every visit a shard could send it
        cap = t * min(top_k, count)
        flat_e, flat_w = experts.reshape(-1), weights.reshape(-1)
        with jax.named_scope("dispatch"):
            sent = []
            for d in range(ep):
                key = jnp.where(flat_e // count == d, flat_e % count, count)
                order = jnp.argsort(key, stable=True)[:cap]
                sent.append((order, key[order]))
            rows_x = lax.all_to_all(
                jnp.stack([x[o // top_k] for o, _ in sent]), ep_axis, 0, 0)
            rows_e = lax.all_to_all(
                jnp.stack([e for _, e in sent]), ep_axis, 0, 0).reshape(-1)
            plan, chunk = _visit_plan(
                rows_e, jnp.ones(rows_e.shape, jnp.float32), count, chunk)
        y, done = _visits_ffn(rows_x.reshape(ep * cap, h), wg, wu, wd,
                              *plan, chunk)
        with jax.named_scope("combine"):
            back = lax.all_to_all(y.reshape(ep, cap, h), ep_axis, 0, 0)
            out = jnp.zeros((t, h), jnp.float32)
            for (o, e), y_d in zip(sent, back):
                out = out.at[o // top_k].add(jnp.where(
                    (e < count)[:, None],
                    y_d.astype(jnp.float32) * flat_w[o][:, None], 0))
            out = out.astype(x.dtype)
        rows = _rows_per_expert(rows_e, count)
    stats = jnp.concatenate([
        rows, jnp.stack([jnp.int32(t * top_k), done])])
    return out, lax.stop_gradient(stats), experts
