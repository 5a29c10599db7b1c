"""Kimi Delta Attention's and Gated DeltaNet's elementwise work around
their scan, as ONE pass over HBM each way: what `nn.KimiDeltaAttention`
does between its projections and `kda_attention` (`kda_pre`), and
between the scan and the output projection (`kda_post`); what
`nn.GatedDeltaNet` does between its projection and the scan
(`gdn_pre`) — each a `jax.custom_vjp` over two Pallas kernels on the
projections' own (B, S, H * 128) layout.  `nn/functional/kda.py`
states the passes in plain XLA (`edge_pre`, `edge_post`, `gdn_pre`:
the tests' oracle and the path for what the kernels refuse).

    kda_pre_fwd   q = unit(SiLU(conv(q_raw))), k likewise,
                  v = SiLU(conv(v_raw)),
                  g = -exp(A_log)[h] softplus(f + dt_bias)
    kda_pre_bwd   recomputes those from the same raw operands and
                  pulls dq, dk, dv, dg back to dq_raw, dk_raw, dv_raw,
                  df and the parameters' cotangents
    kda_post_fwd  y = RMSNorm_head(o) w sigmoid(gate)
    kda_post_bwd  do, dgate, dw from o, gate and dy
    gdn_post_fwd, the same two with SiLU(gate) for sigmoid(gate):
    gdn_post_bwd  Gated DeltaNet's gated head norm (`activation="silu"`)
    gdn_pre_fwd   q = unit(SiLU(conv(q~))), k likewise, v = SiLU(conv(v~)),
                  q~, k~, v~ read in place from the projection's output
                  [q~ | k~ | v~ | z] by three lane-block specs over it:
                  a block of key heads' q~ and k~ and their value heads'
                  v~ (Hv / Hk times as wide) a grid step
    gdn_pre_bwd   the same pull-back, over every lane block of the
                  projection a grid step: it writes the projection's
                  cotangent [dq~ | dk~ | dv~ | dz] whole, dz copied in,
                  so that no concatenate follows it; beta and g stay in
                  XLA (`gdn_gate`: 2 MB at the cell's shape)

A grid step is one (batch, block of heads, tile of `ROW_TILE` rows)
and walks the tile `_STEP` rows and a head at a time in a loop whose
body is a few vregs an operand: nothing of a tile but its operands and
results is in HBM, and every intermediate is float32 in VMEM with one
rounding at the store.

The convolution's taps reach 3 rows back, so a body is also handed
the 16-row block before its tile (a second operand of the same array;
zeros before the sequence) and lays [those rows; the tile] into a
float32 scratch that the loop reads at the four row offsets.  The
pull-back of the convolution reaches 3 rows AHEAD (dx_u = sum_i w_i
dc_{u + 3 - i}, dc the cotangent of the convolution's sum): the
backward call walks the tiles from the last, its sequence axis
`"arbitrary"`, and keeps the first 8 rows of dc of the tile after in
VMEM.  The parameters arrive as one (16, width) float32 block (rows 0-3,
4-7, 8-11 the taps of q, k, v; 12 dt_bias; 13 A_log by lane) and their
cotangents leave as the same rows' sums, still split over the 8
sublanes, in a block that stays in VMEM over the sequence axis; XLA
adds the sublanes (and, for A_log, a head's lanes) up.

The kernels take heads of 128 channels and 4 taps.  Anything else, and
everything off the TPU that does not ask for `interpret`, runs the XLA
statement.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...nn.functional import kda as _xla
from . import _common
from .attention import _compiler_params
from .kda import _F32, HEAD_DIM, _lanes

ROW_TILE = 256          # rows a grid step
TAPS = 4
_STEP = 32              # rows a loop step
_HALO = 16              # rows handed of the tile before: a bfloat16 tile
_EDGE = 8               # of which a body keeps these: a float32 tile
_SUB = 8                # sublanes: a parameter's cotangent is 8 partial rows
_DT, _A, _PARAM_ROWS = 3 * TAPS, 3 * TAPS + 1, 16


def _sigmoid(x):
    return 0.5 + 0.5 * jnp.tanh(0.5 * x)


def _lane_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _shifted(x, rows):
    """x's rows moved down by `rows` (up if negative), around the end."""
    return pltpu.roll(x, rows % x.shape[0], 0) if rows else x


def _walk(tile, step, descending=False, rows=_STEP):
    """step(first row of `rows` rows) over a tile's rows, in a loop."""
    n = tile // rows

    def body(i, carry):
        step(pl.multiple_of((n - 1 - i if descending else i) * rows, rows))
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _fill(scr, x_ref, halo_ref, first):
    """scr (8 + tile, w) <- [the 8 rows before the tile, zeros before
    the sequence; the tile], float32."""
    before = halo_ref[0].astype(_F32)[_HALO - _EDGE:]
    scr[:_EDGE] = jnp.where(first, 0.0, before)
    scr[_EDGE:] = x_ref[0].astype(_F32)


def _conv(scr, p_ref, operand, r0, lanes):
    """The convolution's sum for rows r0.. of the tile in `scr`, and
    the rows it read: x_{t-3}, x_{t-2}, x_{t-1}, x_t."""
    rows = scr[pl.ds(r0, _EDGE + _STEP), lanes]
    xs = [_shifted(rows, TAPS - 1 - i)[_EDGE:] for i in range(TAPS)]
    taps = [p_ref[operand * TAPS + i:operand * TAPS + i + 1, lanes]
            for i in range(TAPS)]
    return sum(w * x for w, x in zip(taps, xs)), xs, taps


def _gate(f_ref, p_ref, at, lanes):
    """-exp(A_log) (1, 128), softplus(f + dt_bias) and its derivative
    sigmoid(f + dt_bias), (rows, 128)."""
    z = f_ref[0, at, lanes].astype(_F32) + p_ref[_DT:_DT + 1, lanes]
    return (-jnp.exp(p_ref[_A:_A + 1, lanes]),
            jnp.maximum(z, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(z))),
            _sigmoid(z))


def _add_rows(d_ref, row, lanes, x):
    """d_ref's partial sums of parameter row `row` += x's rows, 8 by 8."""
    at = slice(row * _SUB, (row + 1) * _SUB)
    d_ref[0, at, lanes] += sum(x[m:m + _SUB] for m in range(0, _STEP, _SUB))


def _silu_conv(scr, p_ref, operand, o_ref, r0, lanes, unit):
    """o_ref's rows r0.. on `lanes` <- SiLU(conv) of the tile in `scr`,
    of unit length over the lanes if `unit`."""
    c = _conv(scr, p_ref, operand, r0, lanes)[0]
    y = c * _sigmoid(c)
    if unit:
        y = y * jax.lax.rsqrt(_lane_sum(y * y) + 1e-6)
    o_ref[0, pl.ds(r0, _STEP), lanes] = y.astype(o_ref.dtype)


def _pull_back(scr, dc, p_ref, operand, d_ref, dx_ref, dp_ref, r0, lanes,
               unit):
    """`_silu_conv`'s pull-back for rows r0.. on `lanes`: d_ref's
    cotangent through the unit norm (if `unit`) and SiLU to the
    convolution's sum, kept in `dc`; dx_ref <- its transpose, which
    reads `dc` 3 rows ahead; the taps' cotangents into dp_ref."""
    at = pl.ds(r0, _STEP)
    c, xs, taps = _conv(scr, p_ref, operand, r0, lanes)
    s = _sigmoid(c)
    y = c * s
    d = d_ref[0, at, lanes].astype(_F32)
    if unit:                # through u = y r, r = rsqrt(|y|^2 + eps)
        r = jax.lax.rsqrt(_lane_sum(y * y) + 1e-6)
        u = y * r
        d = r * (d - u * _lane_sum(d * u))
    d = d * (s + y * (1.0 - s))             # SiLU'
    dc[at, lanes] = d
    for t in range(TAPS):
        _add_rows(dp_ref, operand * TAPS + t, lanes, d * xs[t])
    # row u's x met tap t in row u + 3 - t's sum
    ahead = dc[pl.ds(r0, _STEP + _EDGE), lanes]
    dx = sum(taps[t] * _shifted(ahead, t + 1 - TAPS)[:_STEP]
             for t in range(TAPS))
    dx_ref[0, at, lanes] = dx.astype(dx_ref.dtype)


# -- before the scan ---------------------------------------------------------

def _pre_fwd_kernel(q_ref, k_ref, v_ref, f_ref, qh_ref, kh_ref, vh_ref, p_ref,
                    qo_ref, ko_ref, vo_ref, g_ref, *scratch, heads, tile):
    first = pl.program_id(2) == 0
    operands = list(zip(scratch, (q_ref, k_ref, v_ref),
                        (qh_ref, kh_ref, vh_ref), (qo_ref, ko_ref, vo_ref)))
    for scr, x_ref, halo_ref, _ in operands:
        _fill(scr, x_ref, halo_ref, first)

    def step(r0):
        at = pl.ds(r0, _STEP)
        for h in range(heads):
            lanes = _lanes(h)
            for n, (scr, _, _, o_ref) in enumerate(operands):
                _silu_conv(scr, p_ref, n, o_ref, r0, lanes, unit=n < 2)
            a, softplus, _ = _gate(f_ref, p_ref, at, lanes)
            g_ref[0, at, lanes] = a * softplus

    _walk(tile, step)


def _pre_bwd_kernel(q_ref, k_ref, v_ref, f_ref, qh_ref, kh_ref, vh_ref, p_ref,
                    dq_ref, dk_ref, dv_ref, dg_ref,
                    dqr_ref, dkr_ref, dvr_ref, df_ref, dp_ref,
                    *scratch, heads, tile):
    i, n = pl.program_id(2), pl.num_programs(2)
    x_scr, dc_scr = scratch[:3], scratch[3:]
    operands = list(zip(x_scr, dc_scr, (q_ref, k_ref, v_ref),
                        (qh_ref, kh_ref, vh_ref), (dq_ref, dk_ref, dv_ref),
                        (dqr_ref, dkr_ref, dvr_ref)))
    for scr, _, x_ref, halo_ref, _, _ in operands:
        _fill(scr, x_ref, halo_ref, i == n - 1)     # tiles from the last

    @pl.when(i == 0)
    def _init():
        dp_ref[...] = jnp.zeros_like(dp_ref)
        for dc in dc_scr:           # nothing follows the sequence
            dc[tile:] = jnp.zeros((_EDGE, dc.shape[1]), _F32)

    def step(r0):
        at = pl.ds(r0, _STEP)
        for h in range(heads):
            lanes = _lanes(h)
            for m, (scr, dc, _, _, d_ref, dx_ref) in enumerate(operands):
                _pull_back(scr, dc, p_ref, m, d_ref, dx_ref, dp_ref, r0,
                           lanes, unit=m < 2)
            a, softplus, slope = _gate(f_ref, p_ref, at, lanes)
            dg = dg_ref[0, at, lanes] * a
            _add_rows(dp_ref, _A, lanes, dg * softplus)   # dg g: g' = g
            dz = dg * slope
            _add_rows(dp_ref, _DT, lanes, dz)
            df_ref[0, at, lanes] = dz.astype(df_ref.dtype)

    _walk(tile, step, descending=True)
    for dc in dc_scr:               # for the tile before
        dc[tile:] = dc[:_EDGE]


def _blocks(shape, tile):
    """(heads a grid step — 4, 2 or 1 —, their lanes, the grid) for a
    (B, S, H * 128) row operand."""
    b, s, w = shape
    heads = next(n for n in (4, 2, 1) if w // HEAD_DIM % n == 0)
    return heads, heads * HEAD_DIM, (b, w // (heads * HEAD_DIM), s // tile)


def _like(a):
    return jax.ShapeDtypeStruct(a.shape, a.dtype)


def _row_specs(tile, width, tiles, reverse):
    """Over the grid (batch, block of heads, tile): a (B, S, W) row
    operand's tile, the 16 rows before it, and the parameter block's
    and its cotangent's columns; `reverse` walks the tiles from the
    last."""
    at = (lambda i: tiles - 1 - i) if reverse else (lambda i: i)
    rows = pl.BlockSpec((1, tile, width), lambda b, j, i: (b, at(i), j))
    halo = pl.BlockSpec(
        (1, _HALO, width),
        lambda b, j, i: (b, jnp.maximum(at(i) * (tile // _HALO) - 1, 0), j))
    params = pl.BlockSpec((_PARAM_ROWS, width), lambda b, j, i: (0, j))
    dparams = pl.BlockSpec((1, _PARAM_ROWS * _SUB, width),
                           lambda b, j, i: (b, 0, j))
    return rows, halo, params, dparams


def _scratch(tile, width, n):
    return [pltpu.VMEM((tile + _EDGE, width), _F32)] * n


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _pre_forward(q, k, v, f, params, tile=ROW_TILE, interpret=False):
    """q, k, v, f (B, S, H * 128) raw, params (16, H * 128) float32 ->
    q, k, v in the operands' dtype, g float32."""
    heads, width, grid = _blocks(q.shape, tile)
    rows, halo, param_rows, _ = _row_specs(tile, width, grid[2], False)
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, heads=heads, tile=tile),
        grid=grid,
        in_specs=[rows] * 4 + [halo] * 3 + [param_rows],
        out_specs=[rows] * 4,
        out_shape=[_like(q), _like(k), _like(v),
                   jax.ShapeDtypeStruct(f.shape, _F32)],
        scratch_shapes=_scratch(tile, width, 3),
        compiler_params=_compiler_params(("parallel",) * 3),
        interpret=interpret, name="kda_pre_fwd",
    )(q, k, v, f, q, k, v, params)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _pre_backward(q, k, v, f, params, dq, dk, dv, dg, tile=ROW_TILE,
                  interpret=False):
    """-> dq_raw, dk_raw, dv_raw, df in the operands' dtypes and the
    parameter block's cotangent (16, H * 128) float32."""
    b, _, w = q.shape
    heads, width, grid = _blocks(q.shape, tile)
    rows, halo, param_rows, dparam_rows = _row_specs(tile, width, grid[2],
                                                     True)
    *grads, dparams = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, heads=heads, tile=tile),
        grid=grid,
        in_specs=[rows] * 4 + [halo] * 3 + [param_rows] + [rows] * 4,
        out_specs=[rows] * 4 + [dparam_rows],
        out_shape=[_like(q), _like(k), _like(v), _like(f),
                   jax.ShapeDtypeStruct((b, _PARAM_ROWS * _SUB, w), _F32)],
        scratch_shapes=_scratch(tile, width, 6),
        compiler_params=_compiler_params(), interpret=interpret,
        name="kda_pre_bwd",
    )(q, k, v, f, q, k, v, params, dq, dk, dv, dg)
    return (*grads, dparams.reshape(b, _PARAM_ROWS, _SUB, w).sum((0, 2)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _pre(q, k, v, f, params, tile, interpret):
    return _pre_fwd(q, k, v, f, params, tile, interpret)[0]


@_common.kernel_trace("kda_edge")
def _pre_fwd(q, k, v, f, params, tile, interpret):
    out = _pre_forward(q, k, v, f, params, tile=tile, interpret=interpret)
    return tuple(out), (q, k, v, f, params)


@_common.kernel_trace("kda_edge")
def _pre_bwd(tile, interpret, res, cotangents):
    return _pre_backward(*res, *cotangents, tile=tile, interpret=interpret)


_pre.defvjp(_pre_fwd, _pre_bwd)


# -- Gated DeltaNet before its scan ------------------------------------------

def _gdn_fwd_kernel(q_ref, k_ref, v_ref, qh_ref, kh_ref, vh_ref, pq_ref,
                    pk_ref, pv_ref, qo_ref, ko_ref, vo_ref, *scratch, heads,
                    group, tile):
    first = pl.program_id(2) == 0
    operands = list(zip(scratch, (q_ref, k_ref, v_ref),
                        (qh_ref, kh_ref, vh_ref), (pq_ref, pk_ref, pv_ref),
                        (qo_ref, ko_ref, vo_ref)))
    for scr, x_ref, halo_ref, _, _ in operands:
        _fill(scr, x_ref, halo_ref, first)

    def step(r0):
        for h in range(heads):      # key head h, then its value heads
            for n, (scr, _, _, p_ref, o_ref) in enumerate(operands):
                for head in ([h] if n < 2
                             else range(h * group, (h + 1) * group)):
                    _silu_conv(scr, p_ref, 0, o_ref, r0, _lanes(head),
                               unit=n < 2)

    _walk(tile, step)


def _gdn_bwd_kernel(x_ref, xh_ref, p_ref, dq_ref, dk_ref, dv_ref, *refs,
                    bounds, tile):
    # dz's ref is there where the projection has lanes past v~'s
    dz_ref, (dx_ref, dp_ref, x_scr, dc_scr) = (
        (refs[0], refs[1:]) if len(refs) == 5 else (None, refs))
    j, i, n = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        dp_ref[...] = jnp.zeros_like(dp_ref)
        dc_scr[tile:] = jnp.zeros((_EDGE, dc_scr.shape[1]), _F32)

    def pull_back(d_ref, unit):
        _fill(x_scr, x_ref, xh_ref, i == n - 1)     # tiles from the last

        def step(r0):
            for h in range(x_scr.shape[1] // HEAD_DIM):
                _pull_back(x_scr, dc_scr, p_ref, 0, d_ref, dx_ref, dp_ref,
                           r0, _lanes(h), unit)

        _walk(tile, step, descending=True)
        dc_scr[tile:] = dc_scr[:_EDGE]              # for the tile before

    for lo, hi, d_ref, unit in zip((0,) + bounds[:2], bounds,
                                   (dq_ref, dk_ref, dv_ref),
                                   (True, True, False)):
        pl.when((j >= lo) & (j < hi))(functools.partial(pull_back, d_ref,
                                                        unit))
    if dz_ref is not None:          # z's lanes: its cotangent as it came
        @pl.when(j >= bounds[2])
        def _z():
            dx_ref[...] = dz_ref[...]


def _gdn_layout(key_heads, width, rest):
    """(key heads a forward grid step, lanes a backward grid step) for a
    projection [q~ | k~ | v~ | rest] of `width` + `rest` lanes whose
    heads are 128 wide, or None where the blocks cannot tile it: a
    forward step reads a block of key heads' q~ and k~ and their value
    heads' v~, the v~ block starting where a block of its width does."""
    value_heads = width // HEAD_DIM - 2 * key_heads
    if value_heads <= 0 or value_heads % key_heads or rest % HEAD_DIM:
        return None
    group = value_heads // key_heads
    fwd = next((n for n in (4, 2, 1) if key_heads % n == 0
                and 2 * key_heads % (group * n) == 0), None)
    bwd = next(n for n in (8, 4, 2, 1)
               if key_heads % n == 0 and rest // HEAD_DIM % n == 0)
    return None if fwd is None else (fwd, bwd * HEAD_DIM)


@functools.partial(jax.jit, static_argnames=("key_heads", "width", "tile",
                                             "interpret"))
def _gdn_pre_forward(y, params, key_heads, width, tile=ROW_TILE,
                     interpret=False):
    """y (B, S, width + rest) the projection, params (8, width + rest)
    float32 (rows 0-3 the taps) -> q, k (B, S, Hk * 128), v (B, S, Hv *
    128) in y's dtype."""
    b, s, _ = y.shape
    heads = _gdn_layout(key_heads, width, y.shape[2] - width)[0]
    group = (width // HEAD_DIM - 2 * key_heads) // key_heads
    lanes = heads * HEAD_DIM
    grid = (b, key_heads // heads, s // tile)
    # the first lane block of q~, k~, v~, in blocks of their own width
    starts = ((lanes, 0), (lanes, key_heads // heads),
              (group * lanes, 2 * key_heads // (group * heads)))

    def specs(w, at):
        return (pl.BlockSpec((1, tile, w), lambda b, j, i: (b, i, at + j)),
                pl.BlockSpec((1, _HALO, w), lambda b, j, i: (
                    b, jnp.maximum(i * (tile // _HALO) - 1, 0), at + j)),
                pl.BlockSpec((2 * TAPS, w), lambda b, j, i: (0, at + j)))

    rows, halos, taps = zip(*(specs(w, at) for w, at in starts))
    out = [pl.BlockSpec((1, tile, w), lambda b, j, i: (b, i, j))
           for w, _ in starts]
    return pl.pallas_call(
        functools.partial(_gdn_fwd_kernel, heads=heads, group=group,
                          tile=tile),
        grid=grid,
        in_specs=[*rows, *halos, *taps], out_specs=out,
        out_shape=[jax.ShapeDtypeStruct((b, s, m * key_heads * HEAD_DIM),
                                        y.dtype) for m in (1, 1, group)],
        scratch_shapes=[pltpu.VMEM((tile + _EDGE, w), _F32)
                        for w, _ in starts],
        compiler_params=_compiler_params(("parallel",) * 3),
        interpret=interpret, name="gdn_pre_fwd",
    )(*(y,) * 6, *(params,) * 3)


@functools.partial(jax.jit, static_argnames=("key_heads", "width", "tile",
                                             "interpret"))
def _gdn_pre_backward(y, params, dq, dk, dv, dz, key_heads, width,
                      tile=ROW_TILE, interpret=False):
    """-> the projection's cotangent (B, S, width + rest) in y's dtype —
    [dq~ | dk~ | dv~ | dz], dz (B, S, rest) passed on as it came — and
    the taps' (4, width + rest) float32.  A grid step is one (batch,
    block of `lanes` of the projection, tile); each cotangent's index
    map holds the block it last read (or will first) where the step's
    lanes are not its own, so that nothing is fetched twice."""
    b, s, full = y.shape
    lanes = _gdn_layout(key_heads, width, full - width)[1]
    tiles = s // tile
    bounds = (key_heads * HEAD_DIM // lanes, 2 * key_heads * HEAD_DIM // lanes,
              width // lanes)
    at = lambda i: tiles - 1 - i                    # tiles from the last
    halo_at = lambda i: jnp.maximum(at(i) * (tile // _HALO) - 1, 0)

    def held(rows, lo, hi, row=at):
        def index(b, j, i):
            r = jnp.where(j < lo, row(0), jnp.where(j < hi, row(i),
                                                    row(tiles - 1)))
            return b, r, jnp.clip(j - lo, 0, hi - lo - 1)
        return pl.BlockSpec((1, rows, lanes), index)

    cotangents = [held(tile, lo, hi)
                  for lo, hi in zip((0,) + bounds, bounds + (full // lanes,))]
    operands = (y, y, params, dq, dk, dv) + ((dz,) if full > width else ())
    dy, dparams = pl.pallas_call(
        functools.partial(_gdn_bwd_kernel, bounds=bounds, tile=tile),
        grid=(b, full // lanes, tiles),
        in_specs=[held(tile, 0, bounds[2]), held(_HALO, 0, bounds[2], halo_at),
                  pl.BlockSpec((2 * TAPS, lanes), lambda b, j, i: (0, j)),
                  *cotangents[:len(operands) - 3]],
        out_specs=[pl.BlockSpec((1, tile, lanes),
                                lambda b, j, i: (b, at(i), j)),
                   pl.BlockSpec((1, TAPS * _SUB, lanes),
                                lambda b, j, i: (b, 0, j))],
        out_shape=[_like(y),
                   jax.ShapeDtypeStruct((b, TAPS * _SUB, full), _F32)],
        scratch_shapes=_scratch(tile, lanes, 2),
        compiler_params=_compiler_params(), interpret=interpret,
        name="gdn_pre_bwd",
    )(*operands)
    return dy, dparams.reshape(b, TAPS, _SUB, full).sum((0, 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _gdn(y, taps, key_heads, tile, interpret):
    return _gdn_fwd(y, taps, key_heads, tile, interpret)[0]


def _gdn_params(taps, full):
    """The taps (4, width) as the kernels' (8, full) float32 block."""
    return jnp.pad(taps.astype(_F32), ((0, TAPS), (0, full - taps.shape[1])))


@_common.kernel_trace("kda_edge")
def _gdn_fwd(y, taps, key_heads, tile, interpret):
    width = taps.shape[1]
    q, k, v = _gdn_pre_forward(y, _gdn_params(taps, y.shape[2]), key_heads,
                               width, tile=tile, interpret=interpret)
    return (q, k, v, y[..., width:]), (y, taps)


@_common.kernel_trace("kda_edge")
def _gdn_bwd(key_heads, tile, interpret, res, cotangents):
    y, taps = res
    width = taps.shape[1]
    dy, dtaps = _gdn_pre_backward(y, _gdn_params(taps, y.shape[2]),
                                  *cotangents, key_heads, width, tile=tile,
                                  interpret=interpret)
    return dy, dtaps[:, :width].astype(taps.dtype)


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


# -- after the scan ----------------------------------------------------------

def _activation(z, activation):
    """(the gate's activation of z, z's sigmoid)."""
    s = _sigmoid(z)
    return (z * s if activation == "silu" else s), s


def _post_fwd_kernel(o_ref, gate_ref, w_ref, y_ref, *, heads, tile, epsilon,
                     activation):
    def step(r0):
        at = pl.ds(r0, _STEP)
        for h in range(heads):
            lanes = _lanes(h)
            o = o_ref[0, at, lanes].astype(_F32)
            r = jax.lax.rsqrt(_lane_sum(o * o) * (1.0 / HEAD_DIM) + epsilon)
            gate = _activation(gate_ref[0, at, lanes].astype(_F32),
                               activation)[0]
            y_ref[0, at, lanes] = (o * r * w_ref[0:1, lanes] * gate).astype(
                y_ref.dtype)

    _walk(tile, step)


def _post_bwd_kernel(o_ref, gate_ref, w_ref, dy_ref, do_ref, dgate_ref,
                     dw_ref, *, heads, tile, epsilon, activation):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def step(r0):
        at = pl.ds(r0, _STEP)
        for h in range(heads):
            lanes = _lanes(h)
            o = o_ref[0, at, lanes].astype(_F32)
            r = jax.lax.rsqrt(_lane_sum(o * o) * (1.0 / HEAD_DIM) + epsilon)
            u = o * r
            z = gate_ref[0, at, lanes].astype(_F32)
            gate, s = _activation(z, activation)
            dy = dy_ref[0, at, lanes].astype(_F32)
            d = dy * gate                                   # of u w
            _add_rows(dw_ref, 0, lanes, d * u)
            d = d * w_ref[0:1, lanes]                       # of u
            du = d * u
            if activation == "silu":    # SiLU' = s (1 + z (1 - s))
                dz = dy * w_ref[0:1, lanes] * u * (s * (1.0 + z * (1.0 - s)))
            else:                       # sigmoid' = s (1 - s)
                dz = du * (1.0 - gate)
            dgate_ref[0, at, lanes] = dz.astype(dgate_ref.dtype)
            do_ref[0, at, lanes] = (
                r * (d - u * (_lane_sum(du) * (1.0 / HEAD_DIM)))).astype(
                    do_ref.dtype)

    _walk(tile, step)


def _post_specs(tile, width):
    rows = pl.BlockSpec((1, tile, width), lambda b, j, i: (b, i, j))
    weight = pl.BlockSpec((1, width), lambda b, j, i: (0, j))
    dweight = pl.BlockSpec((1, _SUB, width), lambda b, j, i: (b, 0, j))
    return rows, weight, dweight


_POST_NAME = {"sigmoid": "kda_post", "silu": "gdn_post"}


@functools.partial(jax.jit, static_argnames=("epsilon", "tile", "interpret",
                                             "activation"))
def _post_forward(o, gate, weight, epsilon, tile=ROW_TILE, interpret=False,
                  activation="sigmoid"):
    """o, gate (B, S, H * 128), weight (1, H * 128) float32 -> y in o's
    dtype."""
    heads, width, grid = _blocks(o.shape, tile)
    rows, weight_rows, _ = _post_specs(tile, width)
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, heads=heads, tile=tile,
                          epsilon=epsilon, activation=activation),
        grid=grid,
        in_specs=[rows, rows, weight_rows], out_specs=rows,
        out_shape=_like(o),
        compiler_params=_compiler_params(("parallel",) * 3),
        interpret=interpret, name=_POST_NAME[activation] + "_fwd",
    )(o, gate, weight)


@functools.partial(jax.jit, static_argnames=("epsilon", "tile", "interpret",
                                             "activation"))
def _post_backward(o, gate, weight, dy, epsilon, tile=ROW_TILE,
                   interpret=False, activation="sigmoid"):
    """-> do, dgate in the operands' dtypes, dweight (1, H * 128)
    float32."""
    heads, width, grid = _blocks(o.shape, tile)
    rows, weight_rows, dweight_rows = _post_specs(tile, width)
    do, dgate, dweight = pl.pallas_call(
        functools.partial(_post_bwd_kernel, heads=heads, tile=tile,
                          epsilon=epsilon, activation=activation),
        grid=grid,
        in_specs=[rows, rows, weight_rows, rows],
        out_specs=[rows, rows, dweight_rows],
        out_shape=[_like(o), _like(gate),
                   jax.ShapeDtypeStruct((o.shape[0], _SUB, o.shape[2]),
                                        _F32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name=_POST_NAME[activation] + "_bwd",
    )(o, gate, weight, dy)
    return do, dgate, dweight.sum((0, 1))[None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _post(o, gate, weight, epsilon, tile, interpret, activation):
    return _post_fwd(o, gate, weight, epsilon, tile, interpret,
                     activation)[0]


@_common.kernel_trace("kda_edge")
def _post_fwd(o, gate, weight, epsilon, tile, interpret, activation):
    y = _post_forward(o, gate, weight, epsilon=epsilon, tile=tile,
                      interpret=interpret, activation=activation)
    return y, (o, gate, weight)


@_common.kernel_trace("kda_edge")
def _post_bwd(epsilon, tile, interpret, activation, res, dy):
    return _post_backward(*res, dy, epsilon=epsilon, tile=tile,
                          interpret=interpret, activation=activation)


_post.defvjp(_post_fwd, _post_bwd)


# -- the two entry points ----------------------------------------------------

def _fused(head_dim, interpret, refused=False):
    """Whether an instance takes the kernels, counted where traced:
    `kda_edge_fused_total` += 1 if so, `kda_edge_fallback_total` += 1
    where the kernels refused the shape (not the platform: the XLA
    path off the TPU is uncounted, as `kda_attention`'s is)."""
    from ...profiler import stat_add

    kernels = interpret or _common.on_tpu()
    fused = kernels and head_dim == HEAD_DIM and not refused
    if kernels:
        stat_add("kda_edge_fused_total" if fused
                 else "kda_edge_fallback_total")
    return fused


def _padded(tile, *rows):
    """Row operands (B, S, W) with S padded to a multiple of the tile."""
    pad = -rows[0].shape[1] % tile
    return tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) if pad else a
                 for a in rows)


@_common.kernel_trace("kda_edge")
def kda_pre(q_raw, k_raw, v_raw, f, q_taps, k_taps, v_taps, dt_bias, a_log,
            interpret=False, tile=ROW_TILE):
    """The scan's operands from the layer's projections: q_raw, k_raw,
    v_raw, f (B, S, H * d); the three convolutions' taps (width, H *
    d), dt_bias (H * d,), a_log (H,) -> q, k, v (B, S, H * d) in the
    operands' dtype, g (B, S, H * d) float32 <= 0
    (nn/functional/kda.py: `edge_pre` states them).

    d = 128 and width = 4 on a TPU (or under `interpret`): one kernel,
    `kda_pre_fwd`, and `kda_pre_bwd` behind it; a length that is no
    multiple of the row tile is padded with zero rows, which no earlier
    row reads.  Otherwise the XLA statement.  `tile` is there for the
    tests, which cross tile boundaries at a few dozen rows."""
    heads = a_log.shape[0]
    if not _fused(q_raw.shape[-1] // heads, interpret,
                  refused=q_taps.shape[0] != TAPS):
        return jax.checkpoint(_xla.edge_pre)(
            q_raw, k_raw, v_raw, f, q_taps, k_taps, v_taps, dt_bias, a_log)
    width = q_raw.shape[-1]
    params = jnp.concatenate(
        [t.astype(_F32) for t in (q_taps, k_taps, v_taps)]
        + [dt_bias.astype(_F32)[None],
           jnp.repeat(a_log.astype(_F32), HEAD_DIM)[None],
           jnp.zeros((_PARAM_ROWS - _A - 1, width), _F32)])
    s = q_raw.shape[1]
    out = _pre(*_padded(tile, q_raw, k_raw, v_raw, f), params, tile,
               bool(interpret))
    return tuple(a[:, :s] for a in out)


@_common.kernel_trace("kda_edge")
def gdn_pre(qkvz, ba, taps, dt_bias, a_log, key_heads, interpret=False,
            tile=ROW_TILE):
    """Gated DeltaNet's work between its projections and its scan
    (nn/functional/kda.py: `gdn_pre` states it) from the projection's
    whole output qkvz = [q~ | k~ | v~ | z] (B, S, 2 Hk d + Hv d + rest),
    ba (B, S, 2 Hv), the taps (width, 2 Hk d + Hv d), dt_bias and a_log
    (Hv,) -> q, k (B, S, Hk, d), v (B, S, Hv, d) in qkvz's dtype, g,
    beta (B, S, Hv) float32, and z = the projection's lanes past the
    taps' (B, S, rest), passed on: its cotangent and those of q~, k~,
    v~ leave as one array.

    d = 128 and width = 4 on a TPU (or under `interpret`): q, k, v from
    `gdn_pre_fwd`, which reads q~, k~, v~ in place, and `gdn_pre_bwd`
    behind it; a length that is no multiple of the row tile is padded
    with zero rows.  g and beta are XLA's either way (`gdn_gate`, 2 MB
    at the cell's shape).  Otherwise the XLA statement."""
    b, s, full = qkvz.shape
    width = taps.shape[1]
    d = width // (2 * key_heads + a_log.shape[0])
    layout = (_gdn_layout(key_heads, width, full - width)
              if d == HEAD_DIM else None)
    if not _fused(d, interpret, refused=taps.shape[0] != TAPS
                  or layout is None):
        return jax.checkpoint(_xla.gdn_pre, static_argnums=(5,))(
            qkvz[..., :width], ba, taps, dt_bias, a_log,
            key_heads) + (qkvz[..., width:],)
    g, beta = jax.checkpoint(_xla.gdn_gate)(ba, dt_bias, a_log)
    q, k, v, z = (a[:, :s] for a in _gdn(*_padded(tile, qkvz), taps,
                                          key_heads, tile, bool(interpret)))
    heads = lambda a: a.reshape(b, s, -1, HEAD_DIM)
    return heads(q), heads(k), heads(v), g, beta, z


@_common.kernel_trace("kda_edge")
def kda_post(o, gate, weight, epsilon, interpret=False, tile=ROW_TILE,
             activation="sigmoid"):
    """RMSNorm over each head's d channels times `weight` (d,) times
    `activation`(gate), "sigmoid" or "silu": o, gate (B, S, H * d) -> (B,
    S, H * d) in o's dtype (nn/functional/kda.py: `edge_post`).  d = 128
    on a TPU (or under `interpret`): `kda_post_fwd` / `kda_post_bwd`
    (`gdn_post_fwd` / `gdn_post_bwd` for SiLU); otherwise the XLA
    statement."""
    if activation not in _POST_NAME:
        raise ValueError(f"activation {activation!r}: sigmoid or silu")
    if not _fused(weight.shape[0], interpret):
        if activation != "sigmoid":
            return jax.checkpoint(_xla.edge_post, static_argnums=(3, 4))(
                o, gate, weight, epsilon, activation)
        return jax.checkpoint(_xla.edge_post, static_argnums=(3,))(
            o, gate, weight, epsilon)
    s = o.shape[1]
    lanes = jnp.tile(weight.astype(_F32), o.shape[-1] // HEAD_DIM)[None]
    return _post(*_padded(tile, o, gate), lanes, float(epsilon), tile,
                 bool(interpret), activation)[:, :s]
