"""Kimi Delta Attention's scan (arXiv:2510.26692): the chunked form of
the gated delta-rule recurrence with a per-channel decay, forward and
a hand-written chunked backward, as one `jax.custom_vjp`.

`nn/functional/kda.py` has the mathematics and the chunk-PARALLEL half
(`chunk_local`: W, U0, Qg, Kg, Aqk, d of every chunk at once, batched
float32 matmuls in XLA, differentiated by `jax.vjp`).  This file has
the chunk-SEQUENTIAL half, which XLA cannot do well: a `lax.scan` over
chunks would write and read every head's (128, 128) float32 state to
HBM each chunk.  Here the state lives in VMEM over an `"arbitrary"`
chunk axis of the grid (batch and heads `"parallel"`):

    kda_fwd   U = U0 - W S;  o = Qg S + Aqk U;  S <- Diag(d) S + Kg^T U
              and writes the state ENTERING every chunk (the backward's
              residual: S / 64 states of 64 KB a head)
    kda_bwd   the same chunks in reverse, carrying dS: from do and the
              saved state the cotangents of W, U0, Qg, Kg, Aqk and d

The state is kept transposed, (dv, dk): the decay then scales lanes
(a (1, dk) row), and every product is a plain, an A B^T or an A^T B
matmul of (64, 128) tiles.  Operands arrive in the projections' own
(B, S, H * 128) layout, a (64, 128) block a head and chunk.  All float32, the matmuls at full float32
precision; q, k, v and o are bfloat16 (the caller's dtype) at the
edge of `kda_attention`.

The kernels take heads of 128 channels (dk = dv = 128: a lane tile).
Anything else runs the recurrence a token at a time (`lax.scan`,
`kda_fallback_total`).  Off the TPU the kernels run under
`interpret=True` where asked (the CPU tests) and the fallback
otherwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...nn.functional.kda import (CHUNK, chunk_local, largest_divisor,
                                  recurrent)
from . import _common
from .attention import _compiler_params

HEAD_DIM = 128
_HEAD_GROUP = 8
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=_F32)


_NN = ((1,), (0,))      # a b
_NT = ((1,), (1,))      # a b^T
_TN = ((0,), (0,))      # a^T b


def _kda_fwd_kernel(w_ref, u0_ref, qg_ref, kg_ref, aqk_ref, d_ref,
                    o_ref, st_ref, s_scr):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    st = s_scr[...]                                 # S^T entering: (dv, dk)
    st_ref[0, 0, 0] = st
    u = u0_ref[0] - _dot(w_ref[0], st, _NT)         # (C, dv)
    o = _dot(qg_ref[0], st, _NT) + _dot(aqk_ref[0, 0, 0], u, _NN)
    o_ref[0] = o.astype(o_ref.dtype)
    s_scr[...] = st * d_ref[0, 0] + _dot(u, kg_ref[0], _TN)


def _kda_bwd_kernel(w_ref, u0_ref, qg_ref, kg_ref, aqk_ref, d_ref, st_ref,
                    do_ref, dw_ref, du_ref, dqg_ref, dkg_ref, daqk_ref,
                    dd_ref, ds_scr):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    ds = ds_scr[...]                                # dS^T leaving: (dv, dk)
    st = st_ref[0, 0, 0]                            # S^T entering
    w, qg, kg, aqk = w_ref[0], qg_ref[0], kg_ref[0], aqk_ref[0, 0, 0]
    do = do_ref[0].astype(_F32)
    u = u0_ref[0] - _dot(w, st, _NT)
    du = _dot(aqk, do, _TN) + _dot(kg, ds, _NT)     # (C, dv)
    du_ref[0] = du
    daqk_ref[0, 0, 0] = _dot(do, u, _NT)            # (C, C)
    dqg_ref[0] = _dot(do, st, _NN)                  # (C, dk)
    dkg_ref[0] = _dot(u, ds, _NN)
    dw_ref[0] = -_dot(du, st, _NN)
    dd_ref[0, 0] = jnp.sum(ds * st, axis=0, keepdims=True)
    ds_scr[...] = (_dot(do, qg, _TN) + ds * d_ref[0, 0]
                   - _dot(du, w, _TN))


def _specs(n, reverse):
    """Block specs over the grid (batch, head, chunk) for a (B, S, H *
    128) row operand, the (B, N, H, 64, 64) score blocks, the (B, N, 1,
    H * 128) decay rows and the (B, N, H, 128, 128) states; `reverse`
    walks the chunks from the last."""
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    rows = pl.BlockSpec((1, CHUNK, HEAD_DIM), lambda b, i, c: (b, at(c), i))
    scores = pl.BlockSpec((1, 1, 1, CHUNK, CHUNK),
                          lambda b, i, c: (b, at(c), i, 0, 0))
    decay = pl.BlockSpec((1, 1, 1, HEAD_DIM),
                         lambda b, i, c: (b, at(c), 0, i))
    state = pl.BlockSpec((1, 1, 1, HEAD_DIM, HEAD_DIM),
                         lambda b, i, c: (b, at(c), i, 0, 0))
    return rows, scores, decay, state


@functools.partial(jax.jit, static_argnames=("heads", "out_dtype",
                                             "interpret"))
def _kda_forward(w, u0, qg, kg, aqk, d, heads, out_dtype, interpret=False):
    """-> (o (B, S, H * 128) `out_dtype`, the transposed state entering
    every chunk (B, N, H, 128, 128) float32)."""
    b, n = aqk.shape[:2]
    rows, scores, decay, state = _specs(n, False)
    return pl.pallas_call(
        _kda_fwd_kernel, grid=(b, heads, n),
        in_specs=[rows, rows, rows, rows, scores, decay],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct(w.shape, out_dtype),
                   jax.ShapeDtypeStruct((b, n, heads, HEAD_DIM, HEAD_DIM),
                                        _F32)],
        scratch_shapes=[pltpu.VMEM((HEAD_DIM, HEAD_DIM), _F32)],
        compiler_params=_compiler_params(), interpret=interpret, name="kda_fwd",
    )(w, u0, qg, kg, aqk, d)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def _kda_backward(w, u0, qg, kg, aqk, d, states, do, heads,
                  interpret=False):
    """-> the cotangents of (w, u0, qg, kg, aqk, d), float32; `do` (B,
    S, H * 128)."""
    b, n = aqk.shape[:2]
    rows, scores, decay, state = _specs(n, True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, _F32)
    return pl.pallas_call(
        _kda_bwd_kernel, grid=(b, heads, n),
        in_specs=[rows, rows, rows, rows, scores, decay, state, rows],
        out_specs=[rows, rows, rows, rows, scores, decay],
        out_shape=[like(w), like(u0), like(qg), like(kg), like(aqk),
                   like(d)],
        scratch_shapes=[pltpu.VMEM((HEAD_DIM, HEAD_DIM), _F32)],
        compiler_params=_compiler_params(), interpret=interpret, name="kda_bwd",
    )(w, u0, qg, kg, aqk, d, states, do)


def _head_groups(h):
    """(groups, heads a group): heads are independent, and the float32
    chunk-local quantities and their cotangents (eleven (B, S, heads x
    128) arrays in the backward pass) exist for one group at a time."""
    hg = largest_divisor(h, _HEAD_GROUP)
    return h // hg, hg


def _take(a, i, hg):
    return jax.lax.dynamic_slice_in_dim(a, i * hg, hg, axis=2)


def _put(whole, part, i, hg):
    return jax.lax.dynamic_update_slice_in_dim(whole, part, i * hg, axis=2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_chunked(q, k, v, g, beta, scale, interpret):
    return _chunked_fwd(q, k, v, g, beta, scale, interpret)[0]


def _chunked_fwd(q, k, v, g, beta, scale, interpret):
    b, s, h, _ = q.shape
    groups, hg = _head_groups(h)

    def group(i, carry):
        o, states = carry
        local = chunk_local(*(_take(a, i, hg) for a in (q, k, v, g, beta)),
                            scale)
        o_g, st_g = _kda_forward(*local, heads=hg, out_dtype=v.dtype,
                                 interpret=interpret)
        return (_put(o, o_g.reshape(b, s, hg, HEAD_DIM), i, hg),
                _put(states, st_g, i, hg))

    o, states = jax.lax.fori_loop(0, groups, group, (
        jnp.zeros((b, s, h, HEAD_DIM), v.dtype),
        jnp.zeros((b, s // CHUNK, h, HEAD_DIM, HEAD_DIM), _F32)))
    return o, (q, k, v, g, beta, states)


def _chunked_bwd(scale, interpret, res, do):
    q, k, v, g, beta, states = res
    b, s, h, _ = q.shape
    groups, hg = _head_groups(h)

    def group(i, grads):
        x = tuple(_take(a, i, hg) for a in (q, k, v, g, beta))
        local, vjp = jax.vjp(lambda *a: chunk_local(*a, scale), *x)
        d_local = _kda_backward(
            *local, _take(states, i, hg),
            _take(do, i, hg).reshape(b, s, -1), heads=hg,
            interpret=interpret)
        return tuple(_put(whole, part.astype(whole.dtype), i, hg)
                     for whole, part in zip(grads, vjp(tuple(d_local))))

    return jax.lax.fori_loop(0, groups, group, tuple(
        jnp.zeros_like(a) for a in (q, k, v, g, beta)))


_kda_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def kda_attention(q, k, v, g, beta, scale=None, interpret=False):
    """o_t = scale S_t^T q_t of the gated delta-rule recurrence
    (nn/functional/kda.py).  q, k (B, S, H, dk) — the caller has
    normalised them —, v (B, S, H, dv), g (B, S, H, dk) <= 0 the log
    decay, beta (B, S, H) in (0, 1) -> o (B, S, H, dv) in v's dtype.

    dk = dv = 128 on a TPU (or under `interpret`): the chunked scan,
    `kda_chunked_total` += 1 and `kda_chunks_total` += the chunks it
    walks; a length that is no multiple of 64 is padded with rows of g
    = 0, beta = 0, k = 0, which leave the state as it is; the heads go
    through 8 at a time.  Otherwise
    the recurrence a token at a time: `kda_fallback_total` += 1 where
    the kernels refused the shape, uncounted off the TPU (as the flash
    kernels' XLA path is).  Counted where traced."""
    from ...profiler import stat_add

    dk, dv = q.shape[-1], v.shape[-1]
    scale = float(dk ** -0.5 if scale is None else scale)
    g = g.astype(_F32)
    kernels = interpret or _common.on_tpu()
    if not (kernels and dk == dv == HEAD_DIM):
        if kernels:     # refused by shape, not by platform
            stat_add("kda_fallback_total")
        return recurrent(q, k, v, g, beta, scale).astype(v.dtype)
    s = q.shape[1]
    pad = -s % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad))
                                    + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    stat_add("kda_chunked_total")
    stat_add("kda_chunks_total", (s + pad) // CHUNK)
    return _kda_chunked(q, k, v, g, beta, scale, bool(interpret))[:, :s]
