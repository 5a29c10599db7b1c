"""The gated window / full attention layer's elementwise work around its
flash kernels, as ONE pass over HBM each way: what
`nn.GatedWindowAttention` does between its projections and
`flash_attention` (`rope`: the rotation of q and k), and between
`flash_attention` and the output projection (`head_gate`: the per-head
sigmoid gate), each a `jax.custom_vjp` over two Pallas kernels on the
projections' own (B, S, H * 128) layout.  `F.rotary_embedding` and
`nn/functional/attn_edge.py:head_gate` state both in plain XLA (the
tests' oracle and the path for what the kernels refuse).

    rope_fwd        y = x C + roll(x, -r/2) S1 + roll(x, +r/2) S2 on a
                    head's 128 lanes, r = rotary_dim: C = cos over both
                    halves of the first r lanes and 1 past them, S1 =
                    -sin in the first half, S2 = +sin in the second,
                    0 elsewhere (one table S1 + S2 and one roll where
                    r = 128)
    rope_bwd        the same program on the cotangent with the sines
                    negated: the rotation by the opposite angle
    head_gate_fwd   y = o s[head], s = sigmoid(g) float32 (B, S, H)
    head_gate_bwd   do = dy s[head], ds = sum over a head's lanes of
                    dy o, from o, s and dy in one pass

A grid step is one (batch, tile of `ROW_TILE` rows, block of heads), the
block of heads innermost: the tables' and s's block index does not
change along it, so they are fetched once a row tile.  The angles are
`F.rotary_embedding`'s — float32 positions x inverse frequencies, cos
and sin float32, times the amplitude — made once a call in XLA as (S,
128) float32 lane tables; they, not q or k, are what the backward
keeps.  Every intermediate is float32 in VMEM with one rounding at the
store.

The gate's per-head broadcast and its 128-lane sums run on the
otherwise idle MXU: s (rows, H), split in three bfloat16 terms whose
sum it is, times a 0/1 matrix (H, 128) with one row of ones is s's
column over a head's lanes, exactly; dy o, split likewise, times the
matrix's transpose adds a head's lanes up into its column of ds in
float32.

The kernels take heads of 128 channels, rotate-half pairs and an even
`rotary_dim` <= 128.  Anything else, and everything off the TPU that
does not ask for `interpret`, runs the XLA statement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...nn.functional import attn_edge as _xla
from . import _common
from .attention import _compiler_params
from .kda import _F32, HEAD_DIM, _lanes
from .kda_edge import ROW_TILE, _STEP, _like, _padded, _walk

_BF16 = jnp.bfloat16
_GATE_STEP = 128        # rows a matmul of the gate kernels' walk, at most


def _blocks(shape, tile):
    """(heads a grid step — 8, 4, 2 or 1 —, the grid) for a (B, S, H *
    128) row operand: row tiles outer, blocks of heads inner."""
    b, s, w = shape
    heads = next(n for n in (8, 4, 2, 1) if w // HEAD_DIM % n == 0)
    return heads, (b, s // tile, w // (heads * HEAD_DIM))


# -- before the kernels: the rotation -----------------------------------------

def _rope_kernel(x_ref, *refs, heads, tile, shifts, backward):
    *table_refs, y_ref = refs

    def step(r0):
        at = pl.ds(r0, _STEP)
        cos, *sines = (t[0, at, :] for t in table_refs)
        for h in range(heads):
            lanes = _lanes(h)
            x = x_ref[0, at, lanes].astype(_F32)
            y = x * cos
            for shift, sin in zip(shifts, sines):
                turned = pltpu.roll(x, shift, 1) * sin
                y = y - turned if backward else y + turned
            y_ref[0, at, lanes] = y.astype(y_ref.dtype)

    _walk(tile, step)


@functools.partial(jax.jit,
                   static_argnames=("half", "backward", "tile", "interpret"))
def _rotate(x, tables, half, backward=False, tile=ROW_TILE, interpret=False):
    """x (B, S, H * 128), tables 2 or 3 of (1 | B, S, 128) float32 -> x
    rotated, or with `backward` its cotangent pulled back."""
    heads, grid = _blocks(x.shape, tile)
    rows = pl.BlockSpec((1, tile, heads * HEAD_DIM), lambda b, i, j: (b, i, j))
    per_batch = tables[0].shape[0] > 1
    table = pl.BlockSpec((1, tile, HEAD_DIM),
                         lambda b, i, j: (b if per_batch else 0, i, 0))
    shifts = (half,) if len(tables) == 2 else (HEAD_DIM - half, half)
    return pl.pallas_call(
        functools.partial(_rope_kernel, heads=heads, tile=tile, shifts=shifts,
                          backward=backward),
        grid=grid,
        in_specs=[rows] + [table] * len(tables), out_specs=rows,
        out_shape=_like(x),
        compiler_params=_compiler_params(("parallel",) * 3),
        interpret=interpret, name="rope_bwd" if backward else "rope_fwd",
    )(x, *tables)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _rope(q, k, tables, half, tile, interpret):
    return _rope_fwd(q, k, tables, half, tile, interpret)[0]


@_common.kernel_trace("attn_edge")
def _rope_fwd(q, k, tables, half, tile, interpret):
    turn = functools.partial(_rotate, tables=tables, half=half, tile=tile,
                             interpret=interpret)
    return (turn(q), turn(k)), tables


@_common.kernel_trace("attn_edge")
def _rope_bwd(half, tile, interpret, tables, cotangents):
    turn = functools.partial(_rotate, tables=tables, half=half, backward=True,
                             tile=tile, interpret=interpret)
    dq, dk = cotangents
    return turn(dq), turn(dk), tuple(jnp.zeros_like(t) for t in tables)


_rope.defvjp(_rope_fwd, _rope_bwd)


def _tables(positions, theta, rotary_dim, inv_freq, amplitude):
    """The lane tables (1 | B, S, 128) float32 of a rotate-half
    rotation over the first `rotary_dim` lanes of a head: (C, S1 + S2)
    where that is the whole head, else (C, S1, S2).  The angles are
    F.rotary_embedding's, operation for operation."""
    inv = theta ** (-jnp.arange(0, rotary_dim, 2, dtype=_F32) / rotary_dim) \
        if inv_freq is None else jnp.asarray(inv_freq, _F32)
    ang = jnp.asarray(positions).astype(_F32)[..., None] * inv
    if ang.ndim == 2:
        ang = ang[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    zero = jnp.zeros_like(sin)
    lanes = lambda *parts, rest: jnp.concatenate(
        parts + (jnp.full(ang.shape[:2] + (HEAD_DIM - rotary_dim,), rest,
                          _F32),), axis=-1)
    if rotary_dim == HEAD_DIM:
        return lanes(cos, cos, rest=1.0), lanes(-sin, sin, rest=0.0)
    return (lanes(cos, cos, rest=1.0), lanes(-sin, zero, rest=0.0),
            lanes(zero, sin, rest=0.0))


# -- after the kernels: the gate ----------------------------------------------

def _terms(x, n):
    """x float32 as n bfloat16 terms whose sum is x: exactly at n = 3,
    and at n = 2 where x is the product of two bfloat16 values."""
    out = []
    for _ in range(n):
        out.append(x.astype(_BF16))
        x = x - out[-1].astype(_F32)
    return out


def _head_rows(heads, head):
    """(H, 128) bfloat16, 1 in row `head`: s (rows, H) times it is s's
    column `head` over 128 lanes; x (rows, 128) times its transpose is
    x's lane sums in column `head` of (rows, H)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (heads, HEAD_DIM), 0)
    return (row == head).astype(_BF16)


def _over_lanes(s_terms, e):
    """The column of s that `e` names, over 128 lanes, float32."""
    return sum(jnp.dot(t, e, preferred_element_type=_F32) for t in s_terms)


def _gate_fwd_kernel(o_ref, s_ref, y_ref, *, heads, tile):
    first = pl.program_id(2) * heads
    rows = min(tile, _GATE_STEP)

    def step(r0):
        at = pl.ds(r0, rows)
        s_terms = _terms(s_ref[0, at, :], 3)
        for h in range(heads):
            lanes = _lanes(h)
            s = _over_lanes(s_terms, _head_rows(s_ref.shape[2], first + h))
            y_ref[0, at, lanes] = (o_ref[0, at, lanes].astype(_F32) * s
                                   ).astype(y_ref.dtype)

    _walk(tile, step, rows=rows)


def _gate_bwd_kernel(o_ref, s_ref, dy_ref, do_ref, ds_ref, *, heads, tile,
                     terms):
    first = pl.program_id(2) * heads
    rows = min(tile, _GATE_STEP)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def step(r0):
        at = pl.ds(r0, rows)
        s_terms = _terms(s_ref[0, at, :], 3)
        ds = jnp.zeros(s_terms[0].shape, _F32)
        for h in range(heads):
            lanes = _lanes(h)
            e = _head_rows(s_ref.shape[2], first + h)
            dy = dy_ref[0, at, lanes].astype(_F32)
            do_ref[0, at, lanes] = (dy * _over_lanes(s_terms, e)).astype(
                do_ref.dtype)
            for t in _terms(dy * o_ref[0, at, lanes].astype(_F32), terms):
                ds = ds + jax.lax.dot_general(
                    t, e, (((1,), (1,)), ((), ())),
                    preferred_element_type=_F32)
        ds_ref[0, at, :] += ds

    _walk(tile, step, rows=rows)


def _gate_specs(o, s, tile):
    heads, grid = _blocks(o.shape, tile)
    rows = pl.BlockSpec((1, tile, heads * HEAD_DIM), lambda b, i, j: (b, i, j))
    gates = pl.BlockSpec((1, tile, s.shape[2]), lambda b, i, j: (b, i, 0))
    return heads, grid, rows, gates


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gate_forward(o, s, tile=ROW_TILE, interpret=False):
    """o (B, S, H * 128), s (B, S, H) float32 -> y in o's dtype."""
    heads, grid, rows, gates = _gate_specs(o, s, tile)
    return pl.pallas_call(
        functools.partial(_gate_fwd_kernel, heads=heads, tile=tile),
        grid=grid, in_specs=[rows, gates], out_specs=rows,
        out_shape=_like(o),
        compiler_params=_compiler_params(("parallel",) * 3),
        interpret=interpret, name="head_gate_fwd",
    )(o, s)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _gate_backward(o, s, dy, tile=ROW_TILE, interpret=False):
    """-> do in o's dtype, ds (B, S, H) float32."""
    heads, grid, rows, gates = _gate_specs(o, s, tile)
    both_bf16 = o.dtype == dy.dtype == _BF16
    return pl.pallas_call(
        functools.partial(_gate_bwd_kernel, heads=heads, tile=tile,
                          terms=2 if both_bf16 else 3),
        grid=grid, in_specs=[rows, gates, rows], out_specs=[rows, gates],
        out_shape=[_like(o), _like(s)],
        compiler_params=_compiler_params(), interpret=interpret,
        name="head_gate_bwd",
    )(o, s, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gate(o, s, tile, interpret):
    return _gate_fwd(o, s, tile, interpret)[0]


@_common.kernel_trace("attn_edge")
def _gate_fwd(o, s, tile, interpret):
    return _gate_forward(o, s, tile=tile, interpret=interpret), (o, s)


@_common.kernel_trace("attn_edge")
def _gate_bwd(tile, interpret, res, dy):
    return tuple(_gate_backward(*res, dy, tile=tile, interpret=interpret))


_gate.defvjp(_gate_fwd, _gate_bwd)


# -- the two entry points -----------------------------------------------------

def _fused(head_dim, interpret, refused=False):
    """Whether an instance takes the kernels, counted where traced:
    `attn_edge_fused_total` += 1 if so, `attn_edge_fallback_total` += 1
    where the kernels refused the shape (not the platform: the XLA
    path off the TPU is uncounted, as `kda_edge`'s is)."""
    from ...profiler import stat_add

    kernels = interpret or _common.on_tpu()
    fused = kernels and head_dim == HEAD_DIM and not refused
    if kernels:
        stat_add("attn_edge_fused_total" if fused
                 else "attn_edge_fallback_total")
    return fused


def _flat(x):
    return x.reshape(x.shape[:2] + (-1,))


@_common.kernel_trace("attn_edge")
def rope(q, k, positions, theta=10000.0, interleaved=False, rotary_dim=None,
         inv_freq=None, amplitude=1.0, interpret=False):
    """`F.rotary_embedding`'s rotation, its arguments and its results:
    q (B, S, H, d), k (B, S, Hkv, d), positions (B, S) | (S,) -> (q, k)
    rotated.

    d = 128, rotate-half pairs and an even `rotary_dim` on a TPU (or
    under `interpret`): `rope_fwd` once for q and once for k, and
    `rope_bwd` behind them; a length that is no multiple of `ROW_TILE`
    is padded with zero rows.  Otherwise the XLA statement."""
    from ...nn import functional as F
    from ...profiler import stat_add

    d = q.shape[-1]
    r = d if rotary_dim is None else rotary_dim
    if not _fused(d, interpret, refused=interleaved or r % 2 or r > d):
        return tuple(t._value for t in F.rotary_embedding(
            q, k, positions, theta, interleaved, rotary_dim=rotary_dim,
            inv_freq=inv_freq, amplitude=amplitude))
    if r < d:
        stat_add("rope_partial_total")      # as F.rotary_embedding counts
    s = q.shape[1]
    tables = _tables(positions, theta, r, inv_freq, amplitude)
    out = _rope(*_padded(ROW_TILE, _flat(q), _flat(k)),
                _padded(ROW_TILE, *tables), r // 2, ROW_TILE, bool(interpret))
    return tuple(y[:, :s].reshape(x.shape) for y, x in zip(out, (q, k)))


@_common.kernel_trace("attn_edge")
def head_gate(o, g, interpret=False):
    """o (B, S, H, d) times sigmoid(g) (B, S, H) over each head's
    channels -> o's shape and dtype (nn/functional/attn_edge.py:
    `head_gate`).  d = 128 on a TPU (or under `interpret`): the sigmoid
    stays XLA's, float32 (B, S, H), and `head_gate_fwd` /
    `head_gate_bwd` do the rest; otherwise the XLA statement."""
    if not _fused(o.shape[-1], interpret):
        return _xla.head_gate(o, g)
    s = o.shape[1]
    gates = jax.nn.sigmoid(g).astype(_F32)
    return _gate(*_padded(ROW_TILE, _flat(o), gates), ROW_TILE,
                 bool(interpret))[:, :s].reshape(o.shape)
