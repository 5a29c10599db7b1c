"""Kimi Delta Attention's scan (arXiv:2510.26692): the chunked form of
the gated delta-rule recurrence with a per-channel decay, forward and
a hand-written chunked backward, as one `jax.custom_vjp` over two
Pallas kernels.  `nn/functional/kda.py` has the mathematics, the
recurrence a token at a time (the fallback) and the float32 statement
of the chunked form (the tests' oracle); all of the chunked form that
a cell runs is here.

A grid step is one (batch, pair of heads, chunk of 64 tokens); the
chunk axis is `"arbitrary"` and each head's (128, 128) float32 state
lives in VMEM over it (a `lax.scan` over chunks would write and read
it to HBM each chunk).  Operands arrive as (64, 128) blocks a head of
the projections' own (B, S, H * 128) layout — q, k, v in the caller's
dtype, the log decay g in float32 — and beta as the chunk's (64, H)
block.  Everything a chunk needs besides is made in VMEM and never
leaves it:

    kda_fwd   G = cumsum(g); the scores A, Aqk by the sub-block rule
              (`_earlier_scores`, `_diagonal_scores`); T = (I + A)^-1
              (`_inverse`); Qg, Kg, d; then U = T (bv - bk S) (which is
              U0 - W S);  o = Qg S + Aqk U;  S <- Diag(d) S + Kg^T U.
              Writes o and the state ENTERING the chunk (the backward's
              only residual beside the operands: S / 64 states of 64 KB
              a head).
    kda_bwd   the same chunks in reverse, carrying dS: recomputes the
              chunk-local quantities, takes from do and the saved
              state the cotangents of U, Qg, Kg, Aqk and d, and pulls
              them back by hand to dq, dk, dv, dg, dbeta (`_bwd_walk`,
              `_bwd_scores`; the equations: docs/linear_attention.md).

A body waits on no single unit — the lane reductions of the scores on
the XLU, the matmuls of T and the walk on their own latencies — and
Mosaic keeps matmuls in program order.  So a step takes two heads and
issues, for both, first the matmuls that wait for nothing
(`_chunk_matmuls`), then each head's rest (`_chunk_local`, the walk;
the backward in two more stages): one head's lane reductions run under
the other's matmuls.  The order of the matmuls was set by bundle counts
of a sandbox compile for a v5e (PERF.md §6, PR 35).

The state is kept transposed, (dv, dk): the decay then scales lanes (a
(1, dk) row), and every product is a plain, an A B^T or an A^T B
matmul of (64, 128) tiles.  All float32, the matmuls at full float32
precision; q, k, v and o are bfloat16 (the caller's dtype) at the edge
of `kda_attention`.

The kernels take heads of 128 channels (dk = dv = 128: a lane tile).
Anything else runs the recurrence a token at a time (`lax.scan`,
`kda_fallback_total`).  Off the TPU the kernels run under
`interpret=True` where asked (the CPU tests) and the fallback
otherwise.

The same two kernels take the Gated DeltaNet form of the recurrence
(Yang et al., arXiv:2412.06464) as it is, in the instances `gdn_fwd` /
`gdn_bwd`:

  * a decay that is ONE scalar a head and token, g (B, S, Hv): it
    arrives as the chunk's (64, Hv) block beside beta's and is
    cumulated once a step for all heads.  The exponent of a score is
    then the same for every channel, so the scores are plain products
    times one (64, 64) matrix a head (`_head_decay_shared`):

        E = exp(min(G_i - G_j, 0));  [Pk; Pq] = [k; q] k^T, once a key
        head;  Mk = tril(-1)(Pk E),  Aqk = scale tril(0)(Pq E)

    with no sub-block rule, no per-lane exponentials and no key-row
    loops; their pull-back is four products with Dk = dMk E and Dq =
    dMq E, and G's share the row minus the column sums of dMk Mk + dMq
    Mq (`_head_decay_rows`, `_head_decay_keys`).  dg and dbeta leave as
    (B, N, Hv, 1, 64) rows that XLA lays out as (B, S, Hv).
    `kda_scalar_scores_total` counts the calls built in this form;
  * twice as many value heads as query/key heads, value head h reading
    key head h // 2: a grid step's two value heads are one key head's
    pair, q and k are fetched once a step by the index map, and the
    backward sums the pair's dq and dk in the step and writes them
    once.  Any other ratio has q and k repeated in HBM first
    (`kda_group_repeat_total`).

The two forms share the walk, T, U, o and the state update, and part
at the scores.  A per-channel decay over as many key as value heads is
the program it was: the forms are chosen by the operands' shapes at
trace time.
"""

from __future__ import annotations

import collections
import functools
import types

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...nn.functional.kda import CHUNK, SUB, recurrent
from . import _common
from .attention import _compiler_params

HEAD_DIM = 128
_BLOCKS = CHUNK // SUB
_HALF = SUB // 2
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=_F32)


_Form = collections.namedtuple("_Form", "group head_decay")
_Form.__doc__ = """What an instance's operands hold: `group` value heads a
key head (1 or 2), `head_decay` whether g is one scalar a head."""


_NN = ((1,), (0,))      # a b
_NT = ((1,), (1,))      # a b^T
_TN = ((0,), (0,))      # a^T b


# -- the chunk-local half, on values and scratch in VMEM ---------------------

def _positions():
    """Row and column index of a (64, 64) score block, and the column
    relative to the row's own 16-block: 0..15 inside the diagonal
    sub-block, negative in the earlier ones."""
    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    return row, col, col - (row - jnp.bitwise_and(row, SUB - 1))


def _halves(x):
    """(rows 0..7, rows 8..15) of each of the four sub-blocks of x (64,
    n): two (32, n)."""
    take = lambda first: jnp.concatenate(
        [x[i * SUB + first:i * SUB + first + _HALF] for i in range(_BLOCKS)],
        axis=0)
    return take(0), take(_HALF)


def _interleave(upper, lower):
    """The inverse of `_halves`."""
    pieces = []
    for i in range(_BLOCKS):
        rows = slice(i * _HALF, (i + 1) * _HALF)
        pieces += [upper[rows], lower[rows]]
    return jnp.concatenate(pieces, axis=0)


def _key_rows(step, carried, operands):
    """carried <- step(j, *carried, *operands) for the 16 key rows j of
    the four diagonal sub-blocks at once; all (64, n).  Key rows 8..15
    meet only the lower half of their sub-block (above the diagonal
    nothing counts), so those steps see (32, n).  Unrolled: the
    scheduler fills the matmuls' latencies with these rows."""
    for j in range(_HALF):
        carried = step(j, *carried, *operands)
    lower = tuple(_halves(a)[1] for a in carried)
    operands = tuple(_halves(a)[1] for a in operands)
    for j in range(_HALF, SUB):
        lower = step(j, *lower, *operands)
    return tuple(_interleave(_halves(a)[0], low)
                 for a, low in zip(carried, lower))


def _sub_rows(ref, j, rows=SUB):
    """Row j of each of the four sub-blocks of a (64, 128) scratch,
    each laid over `rows` rows."""
    return jnp.concatenate(
        [jnp.broadcast_to(ref[i * SUB + j:i * SUB + j + 1, :],
                          (rows, HEAD_DIM)) for i in range(_BLOCKS)], axis=0)


def _stack_rows(pieces):
    """Four (16, n) pieces (the first sub-block's are zero) -> (64, n)."""
    return jnp.concatenate([jnp.zeros_like(pieces[0])] + pieces, axis=0)


def _earlier_operands(q, k, gc, i):
    """The factorised operands of sub-block i's rows against every
    EARLIER sub-block, G_ref at the sub-block's first row: (the rows'
    decay exp(G - G_ref) (16, 128), the keys' exp(G_ref - G) (64, 128)
    — later keys clamped, the caller masks them —, [k; q] of the rows
    decayed (32, 128), the keys decayed (64, 128))."""
    rows = slice(i * SUB, (i + 1) * SUB)
    ref = gc[i * SUB:i * SUB + 1]
    e_in = jnp.exp(gc[rows] - ref)
    e_ref = jnp.exp(jnp.minimum(ref - gc, 0.0))
    return (e_in, e_ref,
            jnp.concatenate([k[rows] * e_in, q[rows] * e_in], axis=0),
            k * e_ref)


def _earlier_scores(q, k, gc, rel):
    """Mk_ij = sum_c k_ic k_jc exp(G_ic - G_jc) and Mq (q for the
    rows' k), (64, 64), over the EARLIER sub-blocks' keys (zero
    elsewhere): one matmul a sub-block of rows."""
    pieces = [_dot(*_earlier_operands(q, k, gc, i)[2:], _NT)
              for i in range(1, _BLOCKS)]
    return (jnp.where(rel < 0, _stack_rows([p[:SUB] for p in pieces]), 0.0),
            jnp.where(rel < 0, _stack_rows([p[SUB:] for p in pieces]), 0.0))


def _diagonal_scores(mk, mq, q, k, gc, g_scr, k_scr, rel):
    """Adds the four diagonal sub-blocks, from explicit pairwise
    differences, a key row at a time (`g_scr`, `k_scr` hold gc and k
    for the row reads); above the diagonal the exponent is clamped
    (finite) and the caller's mask drops the entry."""
    def key_row(j, mk, mq, q, k, gc, rel):
        rows = gc.shape[0] // _BLOCKS
        pair = jnp.exp(jnp.minimum(gc - _sub_rows(g_scr, j, rows), 0.0)) \
            * _sub_rows(k_scr, j, rows)
        hit = rel == j
        return (jnp.where(hit, jnp.sum(k * pair, axis=1, keepdims=True), mk),
                jnp.where(hit, jnp.sum(q * pair, axis=1, keepdims=True), mq))

    return _key_rows(key_row, (mk, mq), (q, k, gc, rel))


def _inverse(a, row, col, rel):
    """(I + a)^-1 for strictly lower-triangular a (64, 64): forward
    substitution on the four diagonal 16-blocks at once (a column at a
    time: X <- X - a[:, j] X[j, :], the elimination form of it), then
    the two block merges, lower-left <- -Tb a21 Ta, as matmuls of the
    block-diagonal X's rows against the masked a.  The substitution
    runs on the four blocks side by side, (16, 64): a step is two
    vregs, its column of a laid over each block's lanes by one lane
    gather."""
    diagonal = (rel >= 0) & (rel < SUB)
    blocks = lambda m: sum(jnp.where(diagonal, m, 0.0)[i * SUB:(i + 1) * SUB]
                           for i in range(_BLOCKS))
    a4, x4 = blocks(a), blocks((row == col).astype(_F32))
    lane = jax.lax.broadcasted_iota(jnp.int32, a4.shape, 1)
    first = lane - jnp.bitwise_and(lane, SUB - 1)
    for j in range(SUB - 1):
        column = jnp.take_along_axis(a4, first + j, axis=1)
        x4 = x4 - column * jnp.broadcast_to(x4[j:j + 1], x4.shape)
    x = jnp.where(diagonal, jnp.concatenate([x4] * _BLOCKS, axis=0), 0.0)
    for size in (SUB, 2 * SUB):
        # the lower-left blocks' rows alone: those of the odd blocks
        odd = [slice(i, i + size) for i in range(size, CHUNK, 2 * size)]
        lower_left = (jnp.bitwise_and(row, size) != 0) & (
            (col - jnp.bitwise_and(col, size - 1))
            == (row - jnp.bitwise_and(row, 2 * size - 1)))
        rows = jnp.concatenate([x[r] for r in odd], axis=0)
        rows = rows - _dot(_dot(rows, jnp.where(lower_left, a, 0.0), _NN), x,
                           _NN)
        pieces = []
        for n, r in enumerate(odd):
            pieces += [x[r.start - size:r.start],
                       rows[n * size:(n + 1) * size]]
        x = jnp.concatenate(pieces, axis=0)
    return x


def _head_column(b, head):
    """A head's column of a chunk's (64, H) block (beta's, G's), as a
    (64, 1) column."""
    lane = jax.lax.broadcasted_iota(jnp.int32, b.shape, 1)
    return jnp.sum(jnp.where(lane == head, b, 0.0), axis=1, keepdims=True)


def _lanes(h):
    """The h-th head's channels of a grid step's (64, heads x 128) block."""
    return slice(h * HEAD_DIM, (h + 1) * HEAD_DIM)


def _chunk_matmuls(h, heads, q_ref, k_ref, v_ref, g_ref, beta_ref, st, g_scr,
                   k_scr, scale, form=_Form(1, False), shared=None):
    """The first half of a head's chunk: the cumulated gate, what is
    elementwise in it, and the matmuls that wait for nothing else —
    the scores against earlier sub-blocks and [bk; Qg] S for the state
    entering, S^T = `st` (dv, dk).  A grid step issues these for all
    its heads before any head's second half (`_chunk_local`): matmuls
    keep their program order, so the second head's are then not behind
    the first head's lane reductions.  `form.group` value heads read
    one key head.  `form.head_decay`: `shared` is what the step's
    heads share (`_head_decay_shared`); a head's G is its column of the
    step's cumulated (64, H) block broadcast to its lanes, and its
    scores are its key head's [k; q] k^T times E_ij = exp(G_i - G_j),
    one (64, 64) exponential; `g_scr` and `k_scr` are not read."""
    q, k = (r[0, :, _lanes(h // form.group)].astype(_F32)
            for r in (q_ref, k_ref))
    v = v_ref[0, :, _lanes(h)].astype(_F32)
    head = pl.program_id(1) * heads + h
    beta = _head_column(beta_ref[0], head)
    row, col, rel = _positions()
    if form.head_decay:
        gc = jnp.broadcast_to(_head_column(shared.gc, head),
                              (CHUNK, HEAD_DIM))
    else:
        lower = (col <= row).astype(_F32)
        gc = _dot(lower, g_ref[0, :, _lanes(h)], _NN)
        g_scr[...] = gc
        k_scr[...] = k
    e_g = jnp.exp(gc)
    last = gc[CHUNK - 1:]
    e_out = jnp.exp(last - gc)
    x = types.SimpleNamespace(
        q=q, k=k, v=v, beta=beta, gc=gc, e_g=e_g, e_out=e_out, st=st,
        positions=(row, col, rel), g_scr=g_scr, k_scr=k_scr, form=form,
        bk=beta * k * e_g, bv=beta * v, qg=scale * q * e_g, kg=k * e_out,
        d=jnp.exp(last), scale=scale)
    if form.head_decay:
        # above the diagonal the exponent is clamped (finite) and the
        # caller's masks drop the entry
        x.p = shared.p[h // form.group]
        x.e = jnp.exp(jnp.minimum(gc[:, :CHUNK] - shared.g_rows[h:h + 1],
                                  0.0))
    else:
        x.earlier = _earlier_scores(q, k, gc, rel)
    x.with_state = _dot(jnp.concatenate([x.bk, x.qg], axis=0), st, _NT)
    return x


def _head_decay_shared(q_ref, k_ref, g_ref, heads, form):
    """What a grid step's heads share where the decay is a scalar a
    head: the chunk's cumulated decay G of every head (64, H) and the
    step's heads' rows of it (8, 64) — the rows picked by an exact
    matmul with one-hot rows, so that G_i - G_i is exactly 0 —, and
    each key head's [k; q] k^T (128, 64), the scores before the decay,
    one product for its `form.group` value heads."""
    row, col, _ = _positions()
    gc = _dot((col <= row).astype(_F32), g_ref[0], _NN)
    pick = jax.lax.broadcasted_iota(jnp.int32, (8, gc.shape[1]), 1) == (
        pl.program_id(1) * heads
        + jax.lax.broadcasted_iota(jnp.int32, (8, gc.shape[1]), 0))
    products = []
    for j in range(heads // form.group):
        q, k = (r[0, :, _lanes(j)].astype(_F32) for r in (q_ref, k_ref))
        products.append(_dot(jnp.concatenate([k, q], axis=0), k, _NT))
    return types.SimpleNamespace(gc=gc, g_rows=_dot(pick.astype(_F32), gc,
                                                    _NT), p=products)


def _chunk_local(x):
    """The second half: the diagonal sub-blocks (a decay a head: the
    products times E), T, and U = T (bv - bk S) (= U0 - W S without
    forming W = T bk and U0 = T bv), (C, dv)."""
    row, col, rel = x.positions
    if x.form.head_decay:
        x.mk = jnp.where(col < row, x.p[:CHUNK] * x.e, 0.0)
        x.mq = jnp.where(col <= row, x.p[CHUNK:] * x.e, 0.0)
        x.aqk = x.scale * x.mq
    else:
        mk, mq = _diagonal_scores(*x.earlier, x.q, x.k, x.gc, x.g_scr,
                                  x.k_scr, rel)
        x.mk = jnp.where(col < row, mk, 0.0)
        x.aqk = jnp.where(col <= row, x.scale * mq, 0.0)
    x.t = _inverse(x.beta * x.mk, row, col, rel)
    x.u = _dot(x.t, x.bv - x.with_state[:CHUNK], _NN)
    return x


def _scratch_at(scratch, h):
    """(g_scr, k_scr) of head h, where the form has them."""
    return tuple(r.at[h] for r in scratch[:2]) if scratch else (None, None)


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, st_ref,
                    s_scr, *scratch, scale, heads, form):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    # the step's heads are independent: one's matmuls fill the waits of
    # another's lane reductions
    shared = (_head_decay_shared(q_ref, k_ref, g_ref, heads, form)
              if form.head_decay else None)
    xs = [_chunk_matmuls(h, heads, q_ref, k_ref, v_ref, g_ref, beta_ref,
                         s_scr[h], *_scratch_at(scratch, h), scale, form,
                         shared)
          for h in range(heads)]
    for h, x in enumerate([_chunk_local(x) for x in xs]):
        st_ref[0, 0, h] = x.st                      # S^T entering: (dv, dk)
        o = x.with_state[CHUNK:] + _dot(x.aqk, x.u, _NN)
        o_ref[0, :, _lanes(h)] = o.astype(o_ref.dtype)
        s_scr[h] = x.st * x.d + _dot(x.u, x.kg, _TN)


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, st_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                    ds_scr, *scratch, scale, heads, form):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        ds_scr[...] = jnp.zeros_like(ds_scr)

    shared = (_head_decay_shared(q_ref, k_ref, g_ref, heads, form)
              if form.head_decay else None)
    xs = []
    for h in range(heads):
        x = _chunk_matmuls(h, heads, q_ref, k_ref, v_ref, g_ref, beta_ref,
                           st_ref[0, 0, h], *_scratch_at(scratch, h), scale,
                           form, shared)
        # the walk's lines that wait for neither scores nor T: from do,
        # the state entering and dS^T leaving (dv, dk)
        x.ds, x.do = ds_scr[h], do_ref[0, :, _lanes(h)].astype(_F32)
        x.kg_ds = _dot(x.kg, x.ds, _NT)
        xs.append(x)
    for h, x in enumerate([_chunk_local(x) for x in xs]):
        _bwd_walk(h, x, dv_ref, dbeta_ref, ds_scr.at[h])
    if form.head_decay:
        for h, x in enumerate(xs):
            _head_decay_rows(h, x, dg_ref, dbeta_ref)
        _head_decay_keys(xs, dq_ref, dk_ref, form)
        return
    for h, x in enumerate(xs):
        _bwd_scores(h, x, dq_ref, dk_ref, dg_ref, scratch[2].at[h], form)
    if form.group > 1:      # a key head's value heads: dq, dk summed, once
        for j in range(heads // form.group):
            pair = xs[j * form.group:(j + 1) * form.group]
            for ref, name in ((dq_ref, "dq"), (dk_ref, "dk")):
                ref[0, :, _lanes(j)] = functools.reduce(
                    jnp.add, [getattr(x, name) for x in pair]).astype(
                        ref.dtype)


def _bwd_walk(h, x, dv_ref, dbeta_ref, ds_scr):
    """The second third of a head's backward step: through the walk's
    lines and T to the cotangents of the scores, dv, dbeta and dS."""
    k, e_g, beta, scale = x.k, x.e_g, x.beta, x.scale
    bk, qg, kg, aqk, t = x.bk, x.qg, x.kg, x.aqk, x.t
    row, col, _ = x.positions
    ds, st, do, u = x.ds, x.st, x.do, x.u
    du = _dot(aqk, do, _TN) + x.kg_ds               # (C, dv)
    d_aqk = _dot(do, u, _NT)                        # (C, C)
    d_kg = _dot(u, ds, _NN)                         # (C, dk)
    d_last = jnp.sum(ds * st, axis=0, keepdims=True) * x.d \
        + jnp.sum(d_kg * kg, axis=0, keepdims=True)  # of G's last row
    # through U = T (bv - bk S) and T = (I + A)^-1: with dZ = T^T dU,
    # dbv = dZ, dbk = -dZ S^T, dS -= bk^T dZ, dA = -T^T (dU Z^T) T^T =
    # -dZ U^T
    d_bv = _dot(t, du, _TN)
    do_dz = jnp.concatenate([do, -d_bv], axis=0)
    both = _dot(do_dz, st, _NN)
    d_qg, d_bk = both[:CHUNK], both[CHUNK:]         # (C, dk)
    ds_scr[...] = ds * x.d + _dot(do_dz, jnp.concatenate([qg, bk], axis=0),
                                  _TN)
    d_a = -jnp.where(col < row, _dot(d_bv, u, _NT), 0.0)
    if x.form.head_decay:   # a column, laid out with dg (`_head_decay_rows`)
        x.dbeta = (jnp.sum(d_bk * k * e_g + d_bv * x.v, axis=1, keepdims=True)
                   + jnp.sum(d_a * x.mk, axis=1, keepdims=True))
    else:
        ones = jnp.ones((8, HEAD_DIM), _F32)
        dbeta_ref[0, 0, h] = (
            _dot(ones, d_bk * k * e_g + d_bv * x.v, _NT)
            + _dot(ones[:, :CHUNK], d_a * x.mk, _NT))[:1]
    dv_ref[0, :, _lanes(h)] = (beta * d_bv).astype(dv_ref.dtype)
    x.d_mk = beta * d_a                             # strictly lower
    x.d_mq = jnp.where(col <= row, scale * d_aqk, 0.0)
    x.d_qg, x.d_bk, x.d_kg, x.d_last = d_qg, d_bk, d_kg, d_last


def _bwd_scores(h, x, dq_ref, dk_ref, dg_ref, col_scr, form):
    """The last third (a per-channel decay): the scores' cotangents back
    to q, k and G, and G's to g.  Grouped: the head's dq and dk stay on
    `x`, float32, for the caller to sum over the key head's value
    heads."""
    scale, g_scr, k_scr = x.scale, x.g_scr, x.k_scr
    q, k, gc, e_g, beta = x.q, x.k, x.gc, x.e_g, x.beta
    d_mk, d_mq, d_qg, d_bk, d_kg = x.d_mk, x.d_mq, x.d_qg, x.d_bk, x.d_kg
    row, col, rel = x.positions
    # the scores' rows against EARLIER sub-blocks, through their
    # factorised operands (G_ref's own cotangent is exactly zero: the
    # product does not depend on the reference)
    row_k, row_q, key = [], [], jnp.zeros_like(k)
    for i in range(1, _BLOCKS):
        rows = slice(i * SUB, (i + 1) * SUB)
        e_in, e_ref, stack, k_ref = _earlier_operands(q, k, gc, i)
        earlier = rel[rows] < 0
        d_m = jnp.concatenate([jnp.where(earlier, d_mk[rows], 0.0),
                               jnp.where(earlier, d_mq[rows], 0.0)], axis=0)
        d_stack = _dot(d_m, k_ref, _NN)
        row_k.append(d_stack[:SUB] * e_in)
        row_q.append(d_stack[SUB:] * e_in)
        key = key + _dot(d_m, stack, _TN) * e_ref
    row_k, row_q = _stack_rows(row_k), _stack_rows(row_q)

    def key_row(j, row_k, row_q, d_mk, d_mq, q, k, gc, rel):
        # the diagonal sub-blocks' column j: the same pairwise exponents
        # as the forward's; each (row, key) pair gives the row's q / k,
        # the key's k, and +- the same product to the two rows of G
        rows = gc.shape[0] // _BLOCKS
        decay = jnp.exp(jnp.minimum(gc - _sub_rows(g_scr, j, rows), 0.0))
        pair = decay * _sub_rows(k_scr, j, rows)
        hit = rel == j
        c_k = jnp.sum(jnp.where(hit, d_mk, 0.0), axis=1, keepdims=True)
        c_q = jnp.sum(jnp.where(hit, d_mq, 0.0), axis=1, keepdims=True)
        to_key = (c_k * k + c_q * q) * decay        # rows' shares of key j
        for i in range(_BLOCKS):
            col_scr[i * SUB + j:i * SUB + j + 1, :] = jnp.sum(
                to_key[i * rows:(i + 1) * rows], axis=0, keepdims=True)
        return row_k + c_k * pair, row_q + c_q * pair

    row_k, row_q = _key_rows(key_row, (row_k, row_q),
                             (d_mk, d_mq, q, k, gc, rel))
    key = key + col_scr[...]
    dq = scale * e_g * d_qg + row_q
    if form.group == 1:
        dq_ref[0, :, _lanes(h)] = dq.astype(dq_ref.dtype)
    dk = beta * e_g * d_bk + d_kg * x.e_out + row_k + key
    if form.group == 1:
        dk_ref[0, :, _lanes(h)] = dk.astype(dk_ref.dtype)
    else:
        x.dq, x.dk = dq, dk
    # G's cotangent: a row of G gains where it decays its own q / k and
    # loses where it is the key's; then the cumulation's transpose
    d_gc = (d_bk * x.bk + d_qg * x.qg - d_kg * x.kg + q * row_q
            + k * (row_k - key))
    dg_ref[0, :, _lanes(h)] = _dot((row <= col).astype(_F32), d_gc, _NN) \
        + x.d_last


def _head_decay_rows(h, x, dg_ref, dbeta_ref):
    """The last third for a decay a head, a value head's part: Dk = dMk
    * E and Dq = dMq * E, the elementwise shares of dq and dk, and dg
    and dbeta.  G_i gains the row sums of
    Pi = dMk * Mk + dMq * Mq and G_j loses its column sums; one exact
    matmul of a ones row lays dG's cumulation's transpose (lanes 0..63)
    and dbeta (lanes 64..127) out as rows."""
    row = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, HEAD_DIM), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (CHUNK, HEAD_DIM), 1)
    pi = x.d_mk * x.mk + x.d_mq * x.mq
    d_gc = (jnp.sum(pi, axis=1, keepdims=True)
            - jnp.sum(pi.T, axis=1, keepdims=True)
            + jnp.sum(x.d_bk * x.bk + x.d_qg * x.qg - x.d_kg * x.kg,
                      axis=1, keepdims=True))       # (64, 1)
    rows = _dot(jnp.ones((8, CHUNK), _F32), jnp.where(
        lane < CHUNK, jnp.where(lane <= row, d_gc, 0.0),
        jnp.where(lane - CHUNK == row, x.dbeta, 0.0)), _NN)[:1]
    dg_ref[0, 0, h] = rows[:, :CHUNK] + jnp.sum(x.d_last, axis=1,
                                                keepdims=True)
    dbeta_ref[0, 0, h] = rows[:, CHUNK:]
    x.d_k, x.d_q = x.d_mk * x.e, x.d_mq * x.e
    x.dq = x.scale * x.e_g * x.d_qg
    x.dk = x.beta * x.e_g * x.d_bk + x.d_kg * x.e_out


def _head_decay_keys(xs, dq_ref, dk_ref, form):
    """A key head's dq and dk: its value heads' Dk and Dq summed, then
    Dq k to q and Dk k + Dk^T k + Dq^T q to k, two matmuls."""
    for j in range(len(xs) // form.group):
        pair = xs[j * form.group:(j + 1) * form.group]
        total = lambda name: functools.reduce(
            jnp.add, [getattr(x, name) for x in pair])
        d = jnp.concatenate([total("d_k"), total("d_q")], axis=0)
        q, k = pair[0].q, pair[0].k
        rows = _dot(d, k, _NN)                      # (128, dk)
        keys = _dot(d, jnp.concatenate([k, q], axis=0), _TN)
        dq_ref[0, :, _lanes(j)] = (total("dq") + rows[CHUNK:]).astype(
            dq_ref.dtype)
        dk_ref[0, :, _lanes(j)] = (total("dk") + rows[:CHUNK] + keys).astype(
            dk_ref.dtype)


# -- the two calls -----------------------------------------------------------

def _form(q, v, g, beta) -> _Form:
    """The form of flat (B, S, W) operands, from their widths."""
    return _Form(v.shape[-1] // q.shape[-1], g.shape[-1] == beta.shape[-1])


def _specs(n, all_heads, heads, reverse):
    """Block specs over the grid (batch, group of `heads` heads, chunk)
    for a (B, S, H * 128) row operand, beta's (B, S, H), the (B, N, H,
    128, 128) states and the (B, N, H, 1, 64) rows of dbeta; `reverse`
    walks the chunks from the last."""
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    rows = pl.BlockSpec((1, CHUNK, heads * HEAD_DIM),
                        lambda b, i, c: (b, at(c), i))
    beta = pl.BlockSpec((1, CHUNK, all_heads), lambda b, i, c: (b, at(c), 0))
    state = pl.BlockSpec((1, 1, heads, HEAD_DIM, HEAD_DIM),
                         lambda b, i, c: (b, at(c), i, 0, 0))
    dbeta = pl.BlockSpec((1, 1, heads, 1, CHUNK),
                         lambda b, i, c: (b, at(c), i, 0, 0))
    return rows, beta, state, dbeta


def _form_specs(n, heads, reverse, form, rows, beta, dbeta):
    """(q / k blocks, g blocks, dg blocks) of `form`: a grouped step's
    key heads are `heads / group` lane tiles at the step's index; a
    decay a head comes and goes as beta does."""
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    keys = rows if form.group == 1 else pl.BlockSpec(
        (1, CHUNK, heads // form.group * HEAD_DIM),
        lambda b, i, c: (b, at(c), i))
    if form.head_decay:
        return keys, beta, dbeta
    return keys, rows, rows


def _heads_a_step(all_heads):
    """Heads a grid step: two where the count is even — independent
    chains for the scheduler to interleave —, else one."""
    return 2 if all_heads % 2 == 0 else 1


def _scratch(heads, rows):
    return pltpu.VMEM((heads, rows, HEAD_DIM), _F32)


def _forward_call(q, k, v, g, beta, scale, interpret, name):
    (b, s, _), all_heads = q.shape, beta.shape[-1]
    n, heads = s // CHUNK, _heads_a_step(all_heads)
    form = _form(q, v, g, beta)
    rows, beta_rows, state, dbeta_rows = _specs(n, all_heads, heads, False)
    keys, gates, _ = _form_specs(n, heads, False, form, rows, beta_rows,
                                 dbeta_rows)
    return pl.pallas_call(
        functools.partial(_kda_fwd_kernel, scale=scale, heads=heads,
                          form=form),
        grid=(b, all_heads // heads, n),
        in_specs=[keys, keys, rows, gates, beta_rows],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, n, all_heads, HEAD_DIM, HEAD_DIM),
                                        _F32)],
        scratch_shapes=[_scratch(heads, HEAD_DIM)]
        + [_scratch(heads, CHUNK)] * (0 if form.head_decay else 2),
        compiler_params=_compiler_params(), interpret=interpret,
        name=name,
    )(q, k, v, g, beta)


def _backward_call(q, k, v, g, beta, states, do, scale, interpret, name):
    (b, s, _), all_heads = q.shape, beta.shape[-1]
    n, heads = s // CHUNK, _heads_a_step(all_heads)
    form = _form(q, v, g, beta)
    rows, beta_rows, state, dbeta_rows = _specs(n, all_heads, heads, True)
    keys, gates, dgates = _form_specs(n, heads, True, form, rows, beta_rows,
                                      dbeta_rows)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    row_outs = (b, n, all_heads, 1, CHUNK)
    dq, dk, dv, dg, dbeta = pl.pallas_call(
        functools.partial(_kda_bwd_kernel, scale=scale, heads=heads,
                          form=form),
        grid=(b, all_heads // heads, n),
        in_specs=[keys, keys, rows, gates, beta_rows, state, rows],
        out_specs=[keys, keys, rows, dgates, dbeta_rows],
        out_shape=[like(q), like(k), like(v),
                   jax.ShapeDtypeStruct(row_outs, _F32)
                   if form.head_decay else like(g),
                   jax.ShapeDtypeStruct(row_outs, _F32)],
        scratch_shapes=[_scratch(heads, HEAD_DIM)]
        + [_scratch(heads, CHUNK)] * (0 if form.head_decay else 3),
        compiler_params=_compiler_params(), interpret=interpret,
        name=name,
    )(q, k, v, g, beta, states, do)
    # a chunk and head's dbeta (and a decay a head's dg) leaves the
    # kernel as a lane-dense row
    as_heads = lambda r: jnp.swapaxes(r[:, :, :, 0], 2, 3).reshape(
        b, s, all_heads)
    if form.head_decay:
        dg = as_heads(dg)
    return dq, dk, dv, dg, as_heads(dbeta)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _kda_forward(q, k, v, g, beta, scale, interpret=False):
    """q, k, v (B, S, H * 128), g the same in float32, beta (B, S, H)
    -> (o (B, S, H * 128) in v's dtype, the transposed state entering
    every chunk (B, N, H, 128, 128) float32)."""
    return _forward_call(q, k, v, g, beta, scale, interpret, "kda_fwd")


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _kda_backward(q, k, v, g, beta, states, do, scale, interpret=False):
    """-> dq, dk, dv (B, S, H * 128) in the operands' dtypes, dg the
    same in float32, dbeta (B, S, H) float32; `do` (B, S, H * 128)."""
    return _backward_call(q, k, v, g, beta, states, do, scale, interpret,
                          "kda_bwd")


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _gdn_forward(q, k, v, g, beta, scale, interpret=False):
    """The Gated DeltaNet instances: q, k (B, S, Hk * 128), v (B, S, Hv
    * 128), g (B, S, Hv) or (B, S, Hv * 128) float32, beta (B, S, Hv),
    Hv = Hk or 2 Hk -> as `_kda_forward`."""
    return _forward_call(q, k, v, g, beta, scale, interpret, "gdn_fwd")


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _gdn_backward(q, k, v, g, beta, states, do, scale, interpret=False):
    """-> dq, dk, dv in the operands' shapes and dtypes, dg in g's
    shape, float32, dbeta (B, S, Hv) float32."""
    return _backward_call(q, k, v, g, beta, states, do, scale, interpret,
                          "gdn_bwd")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_chunked(q, k, v, g, beta, scale, interpret):
    return _chunked_fwd(q, k, v, g, beta, scale, interpret)[0]


def _calls(q, v, g, beta):
    """The Kimi Delta Attention instances' jitted calls, or the Gated
    DeltaNet instances' (a decay a head, or grouped heads).  A call
    built with a decay a head takes its scores in the scalar form:
    `kda_scalar_scores_total` += 1."""
    from ...profiler import stat_add

    if g.ndim == beta.ndim:
        stat_add("kda_scalar_scores_total")
    if g.ndim == beta.ndim or v.shape[2] != q.shape[2]:
        return _gdn_forward, _gdn_backward
    return _kda_forward, _kda_backward


@_common.kernel_trace("kda_attention")
def _chunked_fwd(q, k, v, g, beta, scale, interpret):
    b, s = q.shape[:2]
    flat = tuple(a.reshape(b, s, -1) for a in (q, k, v, g))
    forward = _calls(q, v, g, beta)[0]
    o, states = forward(*flat, beta, scale=scale, interpret=interpret)
    return o.reshape(v.shape), (q, k, v, g, beta, states)


@_common.kernel_trace("kda_attention")
def _chunked_bwd(scale, interpret, res, do):
    q, k, v, g, beta, states = res
    b, s = q.shape[:2]
    flat = tuple(a.reshape(b, s, -1) for a in (q, k, v, g))
    backward = _calls(q, v, g, beta)[1]
    *grads, dbeta = backward(*flat, beta, states, do.reshape(b, s, -1),
                             scale=scale, interpret=interpret)
    return tuple(a.reshape(x.shape) for a, x in zip(grads, (q, k, v, g))) \
        + (dbeta,)


_kda_chunked.defvjp(_chunked_fwd, _chunked_bwd)


@_common.kernel_trace("kda_attention")
def kda_attention(q, k, v, g, beta, scale=None, interpret=False):
    """o_t = scale S_t^T q_t of the gated delta-rule recurrence
    (nn/functional/kda.py).  q, k (B, S, Hk, dk) — the caller has
    normalised them —, v (B, S, Hv, dv), g <= 0 the log decay, (B, S,
    Hv, dk) a channel (Kimi Delta Attention) or (B, S, Hv) a head
    (Gated DeltaNet), beta (B, S, Hv) in (0, 1) -> o (B, S, Hv, dv) in
    v's dtype.  Value head h reads key head h // (Hv / Hk).

    dk = dv = 128 on a TPU (or under `interpret`): the chunked scan,
    `kda_chunked_total` += 1 and `kda_chunks_total` += the chunks it
    walks; a length that is no multiple of 64 is padded with rows of g
    = 0, beta = 0, k = 0, which leave the state as it is.  A decay a
    head: `kda_head_decay_total` += 1; Hv = 2 Hk: `kda_grouped_heads_total`
    += 1; any other ratio: q and k repeated to Hv heads in HBM first,
    `kda_group_repeat_total` += 1.  Otherwise the recurrence a token at
    a time: `kda_fallback_total` += 1 where the kernels refused the
    shape, uncounted off the TPU (as the flash kernels' XLA path is).
    Counted where traced."""
    from ...profiler import stat_add

    dk, dv = q.shape[-1], v.shape[-1]
    scale = float(dk ** -0.5 if scale is None else scale)
    g = g.astype(_F32)
    group = v.shape[2] // q.shape[2]
    if group * q.shape[2] != v.shape[2]:
        raise ValueError(f"{v.shape[2]} value heads over {q.shape[2]} "
                         "query/key heads: no whole group")
    kernels = interpret or _common.on_tpu()
    if not (kernels and dk == dv == HEAD_DIM):
        if kernels:     # refused by shape, not by platform
            stat_add("kda_fallback_total")
        if g.ndim == 3:
            g = jnp.broadcast_to(g[..., None], g.shape + (dk,))
        if group > 1:
            q, k = (jnp.repeat(a, group, axis=2) for a in (q, k))
        return recurrent(q, k, v, g, beta, scale).astype(v.dtype)
    if g.ndim == 3:
        stat_add("kda_head_decay_total")
    if group == 2:
        stat_add("kda_grouped_heads_total")
    elif group > 2:
        stat_add("kda_group_repeat_total")
        q, k = (jnp.repeat(a, group, axis=2) for a in (q, k))
    s = q.shape[1]
    pad = -s % CHUNK
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad))
                                    + ((0, 0),) * (a.ndim - 2))
                            for a in (q, k, v, g, beta))
    stat_add("kda_chunked_total")
    stat_add("kda_chunks_total", (s + pad) // CHUNK)
    return _kda_chunked(q, k, v, g, beta.astype(_F32), scale,
                        bool(interpret))[:, :s]
