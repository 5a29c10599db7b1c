"""Flash attention for TPU (Pallas/Mosaic).

Re-designs the reference's fused attention CUDA ops
(/root/reference/paddle/fluid/operators/fused/multihead_matmul_op.cu,
fused/fused_attention — BERT/transformer inference fusions) as a
blockwise online-softmax kernel tiled for the MXU, the standard
flash-attention recurrence:

    m_i = max(m_{i-1}, rowmax(S_i));  l_i = e^{m_{i-1}-m_i} l_{i-1} + rowsum(P_i)
    acc_i = e^{m_{i-1}-m_i} acc_{i-1} + P~_i V_i

The forward body (`_flash_fwd_kernel`) runs it a head at a time: a grid
step holds block_h heads' (block_q, block_k) score tiles, and each
head's tile goes matmul -> mask -> max -> exp -> sum -> cast -> matmul
before the next head's starts (`flash_fwd_pieces_total` counts the
pieces a step), whatever the operand layout.  m and l are kept
lane-replicated, (block_h, block_q, 128) float32 in scratch, so that
`s - m`, `acc * alpha` and `acc / l` are whole-vreg operations with no
lane broadcast of a (rows, 1) column and no one-lane store; scores,
max, exp, sums and the accumulator are float32, P is cast only as the
P V operand.  `lse` = m + log l leaves the kernel as the (B*H, Sq, 1)
float32 column the backward kernels read (with `delta`, of the same
shape), written once a q tile.

Round-2 upgrades (VERDICT.md "weak" #3, ADVICE #1):
  * key-padding masks run IN-kernel: any mask that is constant across
    query positions and heads becomes an additive key bias (B, Sk)
    streamed into the kernel, so real BERT inputs stay on the fast path;
  * attention dropout runs IN-kernel via a counter-based hash RNG over
    absolute (batch·head, q, k) coordinates — deterministic, identical
    bits in forward and backward regardless of block layout, and
    platform-independent (works in interpret mode on CPU, unlike the
    pltpu hardware PRNG);
  * arbitrary sequence lengths / head dims via a padding shim (pad to
    block multiples, bias out padded keys, slice the output);
  * the backward pass is two Pallas kernels (dkv and dq) instead of an
    O(S^2)-materializing XLA recompute.

Layout contract (paddle 2.x MultiHeadAttention): q/k/v are
(batch, seq, num_heads, head_dim).  The kernels take them as the
projections write them, (B, S, H*D) — a free reshape — wherever a grid
step's heads fill whole 128-lane blocks: head pairs at D = 64 (two
heads share a block and are told apart in-kernel by a lane mask),
single heads at D a multiple of 128.  No transpose of q, k, v, o or
their gradients is left around the calls then
(`flash_packed_layout_total` counts the instances).  Every other shape
(an odd head count at D = 64, D = 32/80/96) is merged to (B*H, S, D) by
an XLA transpose, as all shapes were before.  One set of kernel bodies
serves both: only the BlockSpecs and `_load_heads` / `_store_heads`
know the layout.

On non-TPU backends (CPU test meshes) the public entry point uses a
plain XLA implementation with identical semantics.  On a TPU, a shape
Mosaic refuses gives way to that XLA path too, with a warning and a
count (`flash_fallback_total`, `serving_ragged_fallback_total`) bumped
once per refused shape at trace time — chip_smoke.py and the TPU test
lane fail on a non-zero count.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import kernel_trace, on_tpu, probe_struct, round_up

DEFAULT_MASK_VALUE = -0.7 * float(jnp.finfo(jnp.float32).max)


# -- structured masks ---------------------------------------------------------

_NEVER_LE = 2 ** 30     # a column code no row threshold reaches


@dataclasses.dataclass(frozen=True)
class BlockDiffusionMask:
    """The block-diffusion training mask (BD3-LM, Arriola et al. 2025)
    over the 2 * seq_len rows `[x_t ‖ x_0]` of one sequence: a noisy
    copy followed by the clean one, both cut into blocks of
    `block_length`.  With blk(i) = (i mod seq_len) // block_length, row
    i sees column j iff

        i noisy, j noisy:  blk(j) == blk(i)   (bidirectional in-block)
        i noisy, j clean:  blk(j) <  blk(i)   (every earlier clean block)
        i clean, j clean:  blk(j) <= blk(i)   (block-causal)
        i clean, j noisy:  never

    The kernels take the family this belongs to — row i sees column j
    iff `c_le[j] <= r_le[i] or c_eq[j] == r_eq[i]` for four int32 code
    vectors — as those vectors plus a table of tile classes (dead,
    partial, full), both built here at trace time from the two
    integers; nothing of size (2 * seq_len)^2 reaches the device.
    Hashable: a static argument."""
    seq_len: int
    block_length: int

    @property
    def rows(self) -> int:
        return 2 * self.seq_len

    def codes(self, n_rows: int, n_cols: int):
        """`(r_le, r_eq, c_le, c_eq)` int32, padded to the kernels'
        sizes: a padding row sees nothing, a padding column is seen by
        nothing."""
        i = np.arange(self.rows)
        blk = (i % self.seq_len) // self.block_length
        noisy = i < self.seq_len
        pad = lambda x, n, fill: np.concatenate(
            [x, np.full(n - self.rows, fill)]).astype(np.int32)
        return (pad(np.where(noisy, blk - 1, blk), n_rows, -1),
                pad(np.where(noisy, blk, -2), n_rows, -2),
                pad(np.where(noisy, _NEVER_LE, blk), n_cols, _NEVER_LE),
                pad(np.where(noisy, blk, -1), n_cols, -1))

    def dense(self) -> np.ndarray:
        """(2 * seq_len, 2 * seq_len) bool, for the XLA path and tests."""
        r_le, r_eq, c_le, c_eq = self.codes(self.rows, self.rows)
        return ((c_le[None, :] <= r_le[:, None])
                | (c_eq[None, :] == r_eq[:, None]))

    @functools.lru_cache(maxsize=None)
    def tiles(self, n_rows: int, n_cols: int, block_q: int, block_k: int):
        """`(cls, k_fetch, q_fetch)`.  `cls[iq, ik]` is the class of
        the (q tile, k tile) pair over the padded rows and columns: 0
        dead (no pair live: the kernels skip it), 1 partial (live and
        dead pairs: the tile body runs with the code mask), 2 full
        (every pair live, so no padding either: the mask could change
        nothing, and the backward kernels run the body without it —
        `_by_class`).  The fetch tables give,
        for the two grid orders, the tile to have in VMEM at each step
        — the step's own where it is not dead, else the nearest such
        one before it (the first where none is), so that a dead step
        moves nothing."""
        r_le, r_eq, c_le, c_eq = self.codes(n_rows, n_cols)
        nq, nk = n_rows // block_q, n_cols // block_k
        cls = np.zeros((nq, nk), np.int32)
        for iq in range(nq):
            rows = slice(iq * block_q, (iq + 1) * block_q)
            m = ((c_le[None, :] <= r_le[rows, None])
                 | (c_eq[None, :] == r_eq[rows, None])).reshape(
                     block_q, nk, block_k)
            cls[iq] = m.any(axis=(0, 2)).astype(np.int32) \
                + m.all(axis=(0, 2))
        return _with_fetch_tables(cls)


def _with_fetch_tables(cls):
    """`(cls, k_fetch, q_fetch)` of a (nq, nk) tile-class table: what
    `BlockDiffusionMask.tiles` documents."""
    def fetch(lv):
        out = np.zeros(lv.shape, np.int32)
        for a, row in enumerate(lv):
            alive = np.flatnonzero(row)
            cur = alive[0] if len(alive) else 0
            for b, on in enumerate(row):
                cur = b if on else cur
                out[a, b] = cur
        return out

    return cls, fetch(cls != 0), fetch(cls.T != 0)


@dataclasses.dataclass(frozen=True)
class _CausalTiles:
    """The tile classes of a plain causal mask — row i sees column j
    iff j <= i + offset, offset = sk - sq before padding — in closed
    form: a tile above the diagonal band is dead, one below it full,
    one it crosses partial (the kernels mask those by their index
    compare; no code vector exists).  Key padding is the key bias's
    and no class's.  A q tile that holds a row which sees no key at
    all (offset < 0) has no dead tile: such a row's result is the mean
    over every key, as the XLA path's is."""
    offset: int

    @functools.lru_cache(maxsize=None)
    def tiles(self, n_rows: int, n_cols: int, block_q: int, block_k: int):
        r0 = np.arange(n_rows // block_q)[:, None] * block_q
        c0 = np.arange(n_cols // block_k)[None, :] * block_k
        some = c0 <= r0 + block_q - 1 + self.offset
        every = c0 + block_k - 1 <= r0 + self.offset
        cls = some.astype(np.int32) + every
        cls = np.where(r0 + self.offset < 0, np.maximum(cls, 1), cls)
        return _with_fetch_tables(cls)


@dataclasses.dataclass(frozen=True)
class _WindowTiles:
    """The tile classes of a causal sliding window over self-attention
    — row i sees column j iff i - window < j <= i: `window` keys, the
    row's own among them — in closed form, as `_CausalTiles`: a tile
    the band misses is dead, one inside it full, one that crosses
    either edge partial (masked by the index compare, which has the
    lower bound too).  The kernels do not walk this table: their grids
    are the band's own length along the inner axis (`_band_k`,
    `_band_q`); it is what the counters and the tests read."""
    window: int

    @functools.lru_cache(maxsize=None)
    def tiles(self, n_rows: int, n_cols: int, block_q: int, block_k: int):
        r0 = np.arange(n_rows // block_q)[:, None] * block_q
        c0 = np.arange(n_cols // block_k)[None, :] * block_k
        some = (c0 <= r0 + block_q - 1) & (c0 + block_k - 1 > r0 - self.window)
        every = (c0 + block_k - 1 <= r0) \
            & (c0 > r0 + block_q - 1 - self.window)
        return _with_fetch_tables(some.astype(np.int32) + every)


def _band_k(iq, block_q, block_k, window, nk, xp=jnp):
    """(first, last) k tile that q tile `iq` of a window's band sees:
    columns iq * block_q - window + 1 ... iq * block_q + block_q - 1.
    Host integers with `xp=np`, a grid step's scalars without."""
    lo = xp.maximum(iq * block_q - window + 1, 0) // block_k
    hi = xp.minimum((iq * block_q + block_q - 1) // block_k, nk - 1)
    return lo, hi


def _band_q(ik, block_q, block_k, window, nq, xp=jnp):
    """(first, last) q tile that sees k tile `ik`: rows ik * block_k ...
    ik * block_k + block_k + window - 2."""
    lo = (ik * block_k) // block_q
    hi = xp.minimum((ik * block_k + block_k + window - 2) // block_q, nq - 1)
    return lo, hi


def _band_lengths(window, nq, nk, block_q, block_k):
    """Grid steps along the inner axis of the (q tile, k tile) and the
    (k tile, q tile) grids of a window: the most tiles any outer tile's
    band holds (`window / block + 1`, or one more where the tiles'
    edges do not meet the band's)."""
    lo, hi = _band_k(np.arange(nq), block_q, block_k, window, nk, np)
    lo_q, hi_q = _band_q(np.arange(nk), block_q, block_k, window, nq, np)
    return int((hi - lo + 1).max()), int((hi_q - lo_q + 1).max())


def _band_step(outer, step, outer_is_q, block_q, block_k, window, tiles):
    """Where step `step` of the band of tile `outer` stands — `outer` a
    q tile and the band its k tiles (`outer_is_q`), or a k tile and the
    band its q tiles; `tiles` = (q tiles, k tiles): `(inner tile, (live,
    full))`.  A step past the band's last tile is dead (the first q
    tiles' bands and the last k tiles' are shorter than the grid); a
    tile wholly inside the band is full."""
    lo, hi = _band_k(outer, block_q, block_k, window, tiles[1]) \
        if outer_is_q else _band_q(outer, block_q, block_k, window, tiles[0])
    inner = lo + step
    iq, ik = (outer, inner) if outer_is_q else (inner, outer)
    full = (ik * block_k + block_k - 1 <= iq * block_q) \
        & (ik * block_k > iq * block_q + block_q - 1 - window)
    return inner, (inner <= hi, full)


# -- XLA reference path -------------------------------------------------------

def _xla_attention(q, k, v, mask=None, is_causal=False, scale=None,
                   dropout_p=0.0, dropout_key=None, window=None):
    """(B, S, H, D) attention in plain XLA; used off-TPU, for masks the
    kernel cannot express, and as the numerical oracle in tests.  Fewer
    key/value heads than query heads (grouped-query attention) are
    repeated here; a `BlockDiffusionMask` becomes its dense form, a
    `window` (with `is_causal`) a dense band."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if k.shape[2] != q.shape[2]:
        group = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    if isinstance(mask, BlockDiffusionMask):
        mask = jnp.asarray(mask.dense())[None, None]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, DEFAULT_MASK_VALUE) \
            if mask.dtype == jnp.bool_ else logits + mask
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), jnp.bool_), k=sk - sq)
        if window is not None:
            causal &= ~jnp.tril(jnp.ones((sq, sk), jnp.bool_),
                                k=sk - sq - window)
        logits = jnp.where(causal, logits, DEFAULT_MASK_VALUE)
    probs = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    if dropout_p > 0.0:
        key = dropout_key if dropout_key is not None \
            else jax.random.PRNGKey(0)
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# -- counter-based dropout RNG ------------------------------------------------

def _keep_mask(seed, bh, q0, k0, block_q, block_k, dropout_p):
    """Deterministic keep mask for the (block_q, block_k) tile whose
    top-left corner is at absolute coordinates (q0, k0) of batch-head bh.
    Delegates to _keep_mask3 so the hash (the dropout-bit contract
    between forward and backward kernels) is defined exactly once."""
    return _keep_mask3(seed, bh, q0, k0, 1, block_q, block_k,
                       dropout_p)[0]


def _keep_mask3(seed, bh0, q0, k0, block_h, block_q, block_k, dropout_p):
    """(block_h, block_q, block_k) keep mask for block_h consecutive
    batch-heads starting at bh0.

    A stateless 32-bit hash of (seed, bh, absolute q, absolute k) with a
    lowbias32 finalizer — bits depend only on absolute coordinates, so
    forward and backward kernels agree even with different grids or
    head-block sizes."""
    shp = (block_h, block_q, block_k)
    r = (q0 + lax.broadcasted_iota(jnp.int32, shp, 1)).astype(jnp.uint32)
    c = (k0 + lax.broadcasted_iota(jnp.int32, shp, 2)).astype(jnp.uint32)
    bh = (bh0 + lax.broadcasted_iota(jnp.int32, shp, 0)).astype(jnp.uint32)
    x = (r * jnp.uint32(0x9E3779B1)) ^ (c * jnp.uint32(0x85EBCA77))
    x = x ^ ((bh + jnp.uint32(1)) * jnp.uint32(0x27D4EB2F))
    x = x ^ (seed.astype(jnp.uint32) * jnp.uint32(0x165667B1))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(min(int(dropout_p * 2 ** 32), 2 ** 32 - 1))
    return x >= thresh


# -- operand layouts ----------------------------------------------------------

def _load_head(ref, h, block_h, mask=False):
    """Head `h` of a q/k/v-like block of block_h heads, (rows, lanes).

    A merged (block_h, rows, d) block holds it as entry h.  A packed
    (1, rows, block_h * d) block holds the heads side by side on the
    lane axis: where d is a multiple of 128 a head is an aligned static
    lane slice.  At d = 64 two heads share a 128-lane block and are
    separated without a relayout: each head takes its pair's whole
    block (lanes = 128) — with `mask`, the sibling's 64 lanes zeroed,
    so that a contraction over lanes (q k^T, g v^T) sees this head
    alone; without, as it is, for operands contracted over rows (p v,
    p^T g, ds^T q, ds k), whose result holds the head in its own 64
    lanes, which is what _store_heads keeps.  Either way the MXU does
    the passes it did at K = N = 64 (it is 128 deep and wide).

    At any other d = 64 mod 128 (192: latent attention's q/k heads) a
    head pair is 2 d lanes, whole 128-lane blocks, and the two heads
    share the middle one: the even head takes the pair's first d + 64
    lanes, the odd head its last d + 64 — aligned windows that hold the
    head and 64 lanes of its sibling, zeroed with `mask` as above (192:
    3 lane blocks a pair, windows of 256)."""
    if ref.shape[0] == block_h:
        return ref[h]
    d = ref.shape[2] // block_h
    if d % 128 == 0:
        return ref[0, :, h * d:(h + 1) * d]
    if d != 64:
        start = h // 2 * 2 * d + (h % 2) * (d - 64)
        x = ref[0, :, start:start + d + 64]
        lane = lax.broadcasted_iota(jnp.int32, (1, d + 64), 1)
        return jnp.where(lane >= 64 if h % 2 else lane < d, x, 0) \
            if mask else x
    x = ref[0, :, h // 2 * 128:(h // 2 + 1) * 128]
    lane = lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return jnp.where((lane >= 64) == bool(h % 2), x, 0) if mask else x


def _load_heads(ref, block_h, mask=False):
    """A q/k/v-like block as (block_h, rows, lanes), one entry a head
    (`_load_head`): what the backward kernels' head-batched products
    take."""
    if ref.shape[0] == block_h:
        return ref[...]
    return jnp.stack([_load_head(ref, h, block_h, mask)
                      for h in range(block_h)])


def _store_heads(ref, x):
    """Inverse of _load_heads: x is (block_h, rows, lanes), or the
    block_h heads in a list; of a head pair's two results each head's
    own lanes are kept (the 128-lane block the two windows share: its
    first 64 from the even head)."""
    if ref.shape[0] == len(x):
        for h in range(len(x)):
            ref[h] = x[h].astype(ref.dtype)
        return
    d = ref.shape[2] // len(x)
    if d % 128 == 0:
        for h in range(len(x)):
            ref[0, :, h * d:(h + 1) * d] = x[h].astype(ref.dtype)
        return
    lane = lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    if d != 64:
        for pair in range(len(x) // 2):
            base, even, odd = pair * 2 * d, x[2 * pair], x[2 * pair + 1]
            ref[0, :, base:base + d - 64] = even[:, :d - 64].astype(
                ref.dtype)
            ref[0, :, base + d - 64:base + d + 64] = jnp.where(
                lane < 64, even[:, d - 64:], odd[:, :128]).astype(ref.dtype)
            ref[0, :, base + d + 64:base + 2 * d] = odd[:, 128:].astype(
                ref.dtype)
        return
    for pair in range(len(x) // 2):
        ref[0, :, pair * 128:(pair + 1) * 128] = jnp.where(
            lane < 64, x[2 * pair], x[2 * pair + 1]).astype(ref.dtype)


# Grouped-query attention: the block_h query heads of a grid step share
# ONE key/value head, so they are laid on the ROW axis — (1, block_h *
# rows, d) against the (1, block_k, d) key tile — and every product of
# the step is one 2-D matmul; dK and dV sum over the group inside the
# contraction.  Packed layout, d a multiple of 128 only.

def _load_rows(ref, block_h):
    d = ref.shape[2] // block_h
    return jnp.concatenate([ref[0, :, h * d:(h + 1) * d]
                            for h in range(block_h)], axis=0)[None]


def _store_rows(ref, x):
    rows, d = ref.shape[1], x.shape[2]
    for h in range(ref.shape[2] // d):
        ref[0, :, h * d:(h + 1) * d] = x[
            0, h * rows:(h + 1) * rows].astype(ref.dtype)


def _load_row_vec(ref, grouped):
    """A (block_h, rows, 1) log-sum-exp / delta block; grouped: as
    (1, block_h * rows, 1)."""
    if not grouped:
        return ref[...]
    return jnp.concatenate([ref[h] for h in range(ref.shape[0])],
                           axis=0)[None]


def _tile_rows(x, reps):
    return x if reps == 1 else jnp.concatenate([x] * reps, axis=0)


def _code_mask(codes, reps):
    """(1, reps * block_q, block_k) bool of a tile from the four code
    blocks (BlockDiffusionMask): rows (1, block_q, 1), columns
    (1, 1, block_k)."""
    r_le, r_eq, c_le, c_eq = codes
    return ((c_le[0] <= _tile_rows(r_le[0], reps))
            | (c_eq[0] == _tile_rows(r_eq[0], reps)))[None]


def _causal_rows(iq, ik, block_q, block_k, reps, causal_offset,
                 window=None):
    """The causal mask of a grouped tile, (1, reps * block_q, block_k);
    with `window`, the band's: the lower bound too."""
    q_idx = _tile_rows(iq * block_q + lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0), reps)
    k_idx = ik * block_k + lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    seen = q_idx + causal_offset >= k_idx
    if window is not None:
        seen &= k_idx > q_idx + causal_offset - window
    return seen[None]


def _mask_scores(s, iq, ik, codes, full, *, block_h, block_q, block_k,
                 causal, causal_offset, grouped, window=None):
    """The score tile `s` with what the step's masks hide set to
    DEFAULT_MASK_VALUE: causal (query i attends keys <= i +
    causal_offset, offset = sk - sq, matching the XLA path's
    jnp.tril(..., k=sk - sq)) and the block mask's codes (`codes` None:
    no block mask).  `full`: the tile's table classes it full
    (`_by_class`) — the select of the mask the table is of (the block
    mask's where there is one, else the causal one's) would return `s`
    itself, and is left out."""
    reps = block_h if grouped else 1
    if full and codes is None:
        causal = False
    if full:
        codes = None
    if causal and (grouped or window is not None):
        s = jnp.where(_causal_rows(iq, ik, block_q, block_k, reps,
                                   causal_offset, window), s,
                      DEFAULT_MASK_VALUE)
    elif causal:
        q_idx = iq * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_h, block_q, block_k), 1)
        k_idx = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_h, block_q, block_k), 2)
        s = jnp.where(q_idx + causal_offset >= k_idx, s,
                      DEFAULT_MASK_VALUE)
    if codes is not None:
        s = jnp.where(_code_mask(codes, reps), s, DEFAULT_MASK_VALUE)
    return s


def _seen_pairs(iq, ik, codes, full, *, block_q, block_k, causal,
                causal_offset, window=None):
    """`_mask_scores`' masks as one (block_q, block_k) bool, the pairs
    of tile (iq, ik) a row sees — the same for every head of a step,
    which the forward kernel walks a head at a time — or None where
    they hide nothing."""
    if full and codes is None:
        causal = False
    seen = _causal_rows(iq, ik, block_q, block_k, 1, causal_offset,
                        window)[0] if causal else None
    if codes is not None and not full:
        code = _code_mask(codes, 1)[0]
        seen = code if seen is None else seen & code
    return seen


def _split_refs(refs, tabled, masked, n_in):
    """The kernels' operands: with a tile-class table (`tabled`: a
    block mask's or a causal mask's) it leads (scalar prefetch, beside
    the fetch table only the index maps read); with a block mask
    (`masked`) the four code blocks follow the `n_in` inputs."""
    cls = None
    if tabled:
        cls, _, *refs = refs
    ins, rest = refs[:n_in], refs[n_in:]
    codes = None
    if masked:
        codes, rest = rest[:4], rest[4:]
    return cls, ins, codes, rest


def _by_class(tile, cls_ref, iq, ik, nk, unmask_full=True, band=None):
    """Run the tile body `tile(full)` with the masking that can change
    the tile: by `band` = (live, full) where the grid walks a window's
    band (`_band_step`); all of it where there is no table (`cls_ref`
    None); else
    by the class of tile (iq, ik) in the (nq, nk) table of
    `BlockDiffusionMask.tiles` / `_CausalTiles.tiles` — a dead tile is
    skipped, not computed and masked; a partial one gets the table's
    mask (`full` False); a full one runs the same body without it
    (`unmask_full` False: with it, as a partial one)."""
    if band is not None:
        live, full = band
        pl.when(live & ~full if unmask_full else live)(
            functools.partial(tile, False))
        if unmask_full:
            pl.when(live & full)(functools.partial(tile, True))
        return
    if cls_ref is None:
        tile(False)
        return
    cls = cls_ref[iq * nk + ik]
    pl.when(cls == 1 if unmask_full else cls != 0)(
        functools.partial(tile, False))
    if unmask_full:
        pl.when(cls == 2)(functools.partial(tile, True))


# A packed step holds up to 4 heads' (512, 512) f32 score tiles and
# their temporaries: 17.5 MB in flash_bwd_dkv, over the compiler's
# default scoped-VMEM budget of 16 MiB, well inside the v5e's 128.
_PACKED_VMEM_LIMIT = 32 * 1024 * 1024
# tile edge of an instance with a window, unless the caller names one
_WINDOW_BLOCK = 256
_PACKED_MAX_SCORES = 4 * 512 * 512
# a grouped step's q, g and f32 accumulators span the whole group
_GROUPED_VMEM_LIMIT = 48 * 1024 * 1024


def _layout(q, k, kbias, heads):
    """(B*H, Sq, Sk, D, packed, lanes) of the kernels' q/k operands:
    merged (B*H, S, D), or packed (B, S, H*D) when the leading dim is
    kbias's B (one head: the two coincide).  `lanes` is the width of a
    head as _load_heads hands it out (and of its f32 accumulators)."""
    packed = heads > 1 and q.shape[0] == kbias.shape[0]
    bh, d = (q.shape[0] * heads, q.shape[2] // heads) if packed \
        else (q.shape[0], q.shape[2])
    lanes = round_up(d, 128) if packed else d
    return bh, q.shape[1], k.shape[1], d, packed, lanes


def _value_width(v, packed, kv_heads):
    """(Dv, lanes) of the value heads, whose width need not be the
    query/key heads' (latent attention: q/k heads of 192 over v heads
    of 128); o, g and dV are value-shaped."""
    dv = v.shape[2] // kv_heads if packed else v.shape[2]
    return dv, round_up(dv, 128) if packed else dv


def _heads_spec(packed, heads, block_h, rows, d, seq_axis, fetch=None):
    """BlockSpec of a q/k/v-like operand on the (batch-head block,
    i, j) grids: block_h heads x `rows` positions, the position block
    taken from grid axis `seq_axis` — through `fetch(i, j, tables)`
    where a block mask redirects dead steps.  Merged: (block_h, rows,
    d) of (B*H, S, D).  Packed: the same heads as (1, rows, block_h *
    d) of (B, S, H*D): batch n // groups, lane block n % groups."""
    def seq(n, i, j, tables):
        return fetch(i, j, tables) if fetch else (n, i, j)[seq_axis]

    if not packed:
        return pl.BlockSpec(
            (block_h, rows, d),
            lambda n, i, j, *t: (n, seq(n, i, j, t), 0))
    groups = heads // block_h
    return pl.BlockSpec(
        (1, rows, block_h * d),
        lambda n, i, j, *t: (n // groups, seq(n, i, j, t), n % groups))


def _pallas_call(kernel, grid, in_specs, out_specs, out_shape,
                 scratch_shapes, tables, **kw):
    """`pl.pallas_call`, with `tables` (the block mask's class and fetch
    tables) as scalar-prefetch operands where there are any."""
    if not tables:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, scratch_shapes=scratch_shapes, **kw)
    call = pl.pallas_call(
        kernel, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes), **kw)
    return functools.partial(call, *tables)


def _band_fetch_k(window, block_q, block_k, sk):
    """The k tile to have in VMEM at step j of q tile i's band, as an
    index-map function `(i, j, tables)`: the band's first plus j, held
    at the band's last on the steps past it (a dead step moves
    nothing)."""
    def fetch(i, j, _):
        lo, hi = _band_k(i, block_q, block_k, window, sk // block_k)
        return jnp.minimum(lo + j, hi)
    return fetch


def _band_fetch_q(window, block_q, block_k, sq, steps):
    """`_band_fetch_k`'s twin for the (k tile, q tile) grid, whose last
    axis runs over (head block of a group, band step): `steps` band
    steps a head block."""
    def fetch(i, j, _):
        lo, hi = _band_q(i, block_q, block_k, window, sq // block_q)
        return jnp.minimum(lo + j % steps, hi)
    return fetch


# -- Pallas forward kernel ----------------------------------------------------

def _lanes(x, width):
    """A lane-replicated (rows, 128) row statistic at `width` lanes:
    whole vregs side by side (the last one cut where a merged head's
    width is no multiple of 128), no broadcast."""
    if width > 128:
        x = jnp.concatenate([x] * -(-width // 128), axis=1)
    return x[:, :width]


def _flash_fwd_kernel(*refs, scale, block_h, block_q, block_k, causal,
                      causal_offset, dropout_p, grouped=False,
                      tabled=False, masked=False, biased=True,
                      window=None, tiles=None):
    """One grid step = block_h heads' (block_q, block_k) score tiles,
    walked a head at a time: a head's tile goes matmul -> mask -> max ->
    exp -> sum -> cast -> matmul before the next head's starts, so no
    float32 temporary spans the step's heads.  The row statistics m and
    l live lane-replicated, (block_h, block_q, 128): where they meet
    the scores (`_lanes`) `s - m`, `acc * alpha` and `acc / l` are
    plain vreg operations, with no one-lane store and no lane broadcast
    of a (rows, 1) column.  Only `lse` leaves as such a column, once a
    q tile.  `window` (with `tiles` = (q tiles, k tiles)): the last grid
    axis walks the band of the q tile, not the k tiles (`_band_k`)."""
    cls_ref, (seed_ref, q_ref, k_ref, v_ref, kbias_ref), codes, \
        (o_ref, lse_ref, m_scr, l_scr, acc_scr) = _split_refs(
            refs, tabled, masked, 5)
    b = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    kv_block_h = 1 if grouped else block_h  # heads in the k/v blocks
    step, band = ik, None
    if window is not None:
        ik, band = _band_step(iq, step, True, block_q, block_k, window,
                              tiles)

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _tile(full):
        # the masks are the same for every head of the step
        seen = _seen_pairs(iq, ik, codes, full, block_q=block_q,
                           block_k=block_k, causal=causal,
                           causal_offset=causal_offset, window=window)
        for h in range(block_h):
            kv = 0 if grouped else h
            s = jax.lax.dot_general(
                _load_head(q_ref, h, block_h, mask=True),
                _load_head(k_ref, kv, kv_block_h),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale  # (bq, bk)
            if biased:
                s = s + kbias_ref[0]     # additive key bias (1, block_k)
            if seen is not None:
                s = jnp.where(seen, s, DEFAULT_MASK_VALUE)
            m_prev = m_scr[h]                                # (bq, 128)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - _lanes(m_new, block_k))
            alpha = jnp.exp(m_prev - m_new)
            l_scr[h] = alpha * l_scr[h] + jnp.sum(p, axis=1, keepdims=True)
            m_scr[h] = m_new
            if dropout_p > 0.0:
                keep = _keep_mask3(seed_ref[0], b * block_h + h,
                                   iq * block_q, ik * block_k, 1, block_q,
                                   block_k, dropout_p)[0]
                p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
            pv = jax.lax.dot_general(
                p.astype(v_ref.dtype),
                _load_head(v_ref, kv, kv_block_h),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_scr[h] = acc_scr[h] * _lanes(alpha, pv.shape[1]) + pv

    # full tiles keep the select here: a second copy of this body costs
    # every forward instance 3 MB of the chip's memory (peak HBM 14.989
    # against 14.986 GiB in the SDAR cell, 15.085 against 15.080 in the
    # JoyAI cell, whose causal instances gain nothing by it; SDAR's run
    # 5.5% faster a call: PERF.md §6, PR 33)
    _by_class(_tile, cls_ref, iq, ik, nk, unmask_full=False, band=band)

    @pl.when(step == nk - 1)
    def _finalize():
        out = []
        for h in range(block_h):
            l = l_scr[h]
            out.append(acc_scr[h] / _lanes(l, acc_scr.shape[2]))
            lse_ref[h] = (m_scr[h] + jnp.log(l))[:, :1]
        _store_heads(o_ref, out)


def _tiler(block_mask, is_causal, causal_offset):
    """Whose tile-class table a call's kernels go by: the block mask's,
    else a causal mask's, else None (every tile runs)."""
    if block_mask is not None:
        return block_mask
    return _CausalTiles(causal_offset) if is_causal else None


def _mask_operands(tiler, block_mask, sq, sk, block_q, block_k, order):
    """What a tile-class table adds to a call on the (n, i, j) grid
    whose axes are (q, k) tiles in `order` "qk", (k, (head block, q))
    tiles in "kq": `(tables, code arrays, code specs, fetch of the
    operand of the inner axis)` — the code arrays and specs a block
    mask's alone."""
    if tiler is None:
        return (), [], [], None
    cls, k_fetch, q_fetch = tiler.tiles(sq, sk, block_q, block_k)
    nq, nk = cls.shape
    arrays = []
    if block_mask is not None:
        r_le, r_eq, c_le, c_eq = block_mask.codes(sq, sk)
        arrays = [jnp.asarray(r_le).reshape(1, sq, 1),
                  jnp.asarray(r_eq).reshape(1, sq, 1),
                  jnp.asarray(c_le).reshape(1, 1, sk),
                  jnp.asarray(c_eq).reshape(1, 1, sk)]
    if order == "qk":
        tables = (jnp.asarray(cls.reshape(-1)),
                  jnp.asarray(k_fetch.reshape(-1)))
        fetch = lambda i, j, t: t[1][i * nk + j]
        row = lambda n, i, j, *t: (0, i, 0)
        col = lambda n, i, j, *t: (0, 0, j)
    else:
        tables = (jnp.asarray(cls.reshape(-1)),
                  jnp.asarray(q_fetch.reshape(-1)))
        fetch = lambda i, j, t: t[1][i * nq + j % nq]
        row = lambda n, i, j, *t: (0, j % nq, 0)
        col = lambda n, i, j, *t: (0, 0, i)
    specs = [pl.BlockSpec((1, block_q, 1), row)] * 2 \
        + [pl.BlockSpec((1, 1, block_k), col)] * 2 if arrays else []
    return tables, arrays, specs, fetch


@functools.partial(jax.jit, static_argnames=(
    "heads", "is_causal", "scale", "dropout_p", "block_h", "block_q",
    "block_k", "interpret", "causal_offset", "kv_heads", "block_mask",
    "biased", "window"))
def _flash_forward(q, k, v, kbias, seed, heads, is_causal=False, scale=None,
                   dropout_p=0.0, block_h=1, block_q=128, block_k=128,
                   interpret=False, causal_offset=None, kv_heads=None,
                   block_mask=None, biased=True, window=None):
    """q,k,v: merged (BH, S, D) or packed (B, S, H*D) — told apart by
    the leading dim, kbias carrying B; kbias: (B, 1, Sk) f32; seed:
    (1,) i32 -> (out like q, lse (BH, Sq, 1) f32: a row's log-sum-exp
    of its scaled, biased, masked scores, before dropout).  Shapes must
    be pre-padded to block multiples (flash_attention() handles that).

    One body for every layout: the step's block_h heads are walked one
    at a time (`_load_head` reads a head's q/k/v from the block
    whatever the layout; grouped heads read the group's one k/v tile),
    the online softmax's m and l lane-replicated in (block_h, block_q,
    128) scratch beside the (block_h, block_q, Dv lanes) accumulator.

    block_h batches consecutive batch-heads into one grid step; it must
    divide heads so a head block never spans two batch elements (the
    kbias block is per batch element).  On the packed layout a step's
    block_h heads are block_h * D adjacent lanes of one batch element,
    a multiple of 128.

    kv_heads < heads (grouped-query attention; packed, D a multiple of
    128): k and v are (B, Sk, kv_heads * D), a step's block_h query
    heads lie inside one group and read its one key/value tile.
    block_mask (a BlockDiffusionMask over the padded rows): the mask is
    applied from code vectors in-kernel, on the tiles its table does
    not class dead; those are skipped, their k/v blocks not fetched.
    biased=False (the caller's promise that kbias is all zeros): the
    kernels are built without the `s + kbias` line.
    window (causal self-attention: row i sees the `window` keys i -
    window < j <= i): the k axis of the grid is as long as the band of
    a q tile (`_band_lengths`), a step's k tile the band's first plus
    the step's index (`_band_k`, in the index maps and in the kernel
    alike); no table.

    Row-vector operands are laid out with a unit SUBLANE dim ((B, 1, Sk)
    bias blocks (1, 1, block_k); (BH, Sq, 1) lse blocks (block_h,
    block_q, 1)) because Mosaic requires each block's last two dims to
    be divisible by (8, 128) or equal to the array dims — the round-2
    rank-2 row blocks (1, block_k) were illegal on real TPU (BENCH_r02
    failure)."""
    bh, sq, sk, d, packed, lanes = _layout(q, k, kbias, heads)
    dv, v_lanes = _value_width(v, packed, kv_heads or heads)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk)
    assert bh % block_h == 0 and heads % block_h == 0, (bh, heads, block_h)
    grid = (bh // block_h, sq // block_q, sk // block_k)
    grouped = kv_heads is not None and kv_heads != heads
    masked = block_mask is not None

    if causal_offset is None:
        causal_offset = sk - sq
    tiler = None if window is not None else _tiler(
        block_mask, is_causal, causal_offset)
    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, block_h=block_h, block_q=block_q,
        block_k=block_k, causal=is_causal, causal_offset=causal_offset,
        dropout_p=dropout_p, grouped=grouped, tabled=tiler is not None,
        masked=masked, biased=biased)
    tables, codes, code_specs, fetch = _mask_operands(
        tiler, block_mask, sq, sk, block_q, block_k, "qk")
    kb_tile = lambda i, j, t: j
    if window is not None:
        assert is_causal and not masked and causal_offset == 0, window
        kernel = functools.partial(kernel, window=window, tiles=grid[1:])
        grid = grid[:2] + (_band_lengths(window, *grid[1:], block_q,
                                         block_k)[0],)
        kb_tile = fetch = _band_fetch_k(window, block_q, block_k, sk)
    q_spec = _heads_spec(packed, heads, block_h, block_q, d, 1)
    o_spec = _heads_spec(packed, heads, block_h, block_q, dv, 1)
    if grouped:
        assert packed and d % 128 == 0 and dv == d and dropout_p == 0.0
        group = heads // kv_heads
        assert group % block_h == 0, (group, block_h)
        per_b = heads // block_h
        k_spec = v_spec = pl.BlockSpec(
            (1, block_k, d), lambda n, i, j, *t: (
                n // per_b, fetch(i, j, t) if fetch else j,
                (n % per_b) * block_h // group))
    else:
        k_spec = _heads_spec(packed, heads, block_h, block_k, d, 2, fetch)
        v_spec = _heads_spec(packed, heads, block_h, block_k, dv, 2, fetch)
    scratch = [pltpu.VMEM((block_h, block_q, 128), jnp.float32),
               pltpu.VMEM((block_h, block_q, 128), jnp.float32),
               pltpu.VMEM((block_h, block_q, v_lanes), jnp.float32)]

    out, lse = _pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            q_spec, k_spec, v_spec,
            pl.BlockSpec((1, 1, block_k),
                         lambda b, iq, ik, *t, h=heads, bh_=block_h:
                         ((b * bh_) // h, 0, kb_tile(iq, ik, t))),
        ] + code_specs,
        out_specs=[
            o_spec,
            pl.BlockSpec((block_h, block_q, 1),
                         lambda b, iq, ik, *t: (b, iq, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape[:2] + (q.shape[2] // d * dv,),
                                 q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        tables=tables,
        # bh/iq steps write disjoint outputs -> parallel lets Mosaic
        # double-buffer DMA across grid steps (the (bh, 1, 1) grid at
        # 512-blocks is otherwise serialized per-step overhead); ik
        # accumulates in scratch -> arbitrary
        compiler_params=_compiler_params(
            vmem_limit=_GROUPED_VMEM_LIMIT if grouped
            else _PACKED_VMEM_LIMIT if packed else None),
        interpret=interpret,
        name="flash_fwd",
    )(seed, q, k, v, kbias, *codes)
    return out, lse


# -- Pallas backward kernels --------------------------------------------------

def _flash_bwd_dkv_kernel(*refs, scale, block_h, block_q, block_k, causal,
                          causal_offset, dropout_p, grouped=False,
                          tabled=False, masked=False, biased=True,
                          q_tiles=None, window=None, tiles=None):
    cls_ref, (seed_ref, q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref,
               kbias_ref), codes, (dk_ref, dv_ref, dk_scr, dv_scr) = \
        _split_refs(refs, tabled, masked, 8)
    b = pl.program_id(0)
    ik = pl.program_id(1)
    iq = pl.program_id(2)
    nq = pl.num_programs(2)
    nk = pl.num_programs(1)
    # a grouped grid's last axis runs over (head block of the group,
    # q tile): all of them add into this key tile's one dK, dV
    last = iq == nq - 1
    first = iq == 0
    if grouped:
        iq = iq % q_tiles
    band = None
    if window is not None:      # the last axis walks the k tile's band
        iq, band = _band_step(ik, iq, False, block_q, block_k, window,
                              tiles)

    @pl.when(first)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def _tile(full):
        if grouped:
            q = _load_rows(q_ref, block_h)   # (1, block_h * block_q, d)
            g = _load_rows(g_ref, block_h)
            k = _load_heads(k_ref, 1)        # (1, block_k, d)
            v = _load_heads(v_ref, 1)
        else:
            q = _load_heads(q_ref, block_h, mask=True)
            g = _load_heads(g_ref, block_h, mask=True)
            k = _load_heads(k_ref, block_h)  # (block_h, block_k, d)
            v = _load_heads(v_ref, block_h)
        lse = _load_row_vec(lse_ref, grouped)      # (block_h, block_q, 1)
        delta = _load_row_vec(delta_ref, grouped)

        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if biased:
            s = s + kbias_ref[...]
        s = _mask_scores(s, iq, ik, codes, full, block_h=block_h,
                         block_q=block_q, block_k=block_k, causal=causal,
                         causal_offset=causal_offset, grouped=grouped,
                         window=window)
        p = jnp.exp(s - lse)      # softmax probs, (block_h, bq, bk)

        if dropout_p > 0.0:
            keep = _keep_mask3(seed_ref[0], b * block_h, iq * block_q,
                               ik * block_k, block_h, block_q, block_k,
                               dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            p_drop = jnp.where(keep, p * inv, 0.0)
        else:
            p_drop = p

        # dV += P~^T g
        dv_scr[:] += jax.lax.dot_general(
            p_drop.astype(g.dtype), g, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        # dP~ = g V^T ; dP = dP~ * keep/(1-r) ; dS = P (dP - delta) scale
        dp_drop = jax.lax.dot_general(
            g, v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            dp = jnp.where(keep, dp_drop * inv, 0.0)
        else:
            dp = dp_drop
        ds = p * (dp - delta) * scale
        # dK += dS^T q
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    _by_class(_tile, cls_ref, iq, ik, nk, band=band)

    @pl.when(last)
    def _finalize():
        _store_heads(dk_ref, dk_scr[:])
        _store_heads(dv_ref, dv_scr[:])


def _flash_bwd_dq_kernel(*refs, scale, block_h, block_q, block_k, causal,
                         causal_offset, dropout_p, grouped=False,
                         tabled=False, masked=False, biased=True,
                         window=None, tiles=None):
    cls_ref, (seed_ref, q_ref, g_ref, lse_ref, delta_ref, k_ref, v_ref,
               kbias_ref), codes, (dq_ref, dq_scr) = _split_refs(
        refs, tabled, masked, 8)
    b = pl.program_id(0)
    iq = pl.program_id(1)
    ik = pl.program_id(2)
    nk = pl.num_programs(2)
    step, band = ik, None
    if window is not None:
        ik, band = _band_step(iq, step, True, block_q, block_k, window,
                              tiles)

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def _tile(full):
        if grouped:
            q = _load_rows(q_ref, block_h)
            g = _load_rows(g_ref, block_h)
            k = _load_heads(k_ref, 1)
            v = _load_heads(v_ref, 1)
        else:
            q = _load_heads(q_ref, block_h, mask=True)
            g = _load_heads(g_ref, block_h, mask=True)
            k = _load_heads(k_ref, block_h)
            v = _load_heads(v_ref, block_h)
        lse = _load_row_vec(lse_ref, grouped)      # (block_h, block_q, 1)
        delta = _load_row_vec(delta_ref, grouped)

        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if biased:
            s = s + kbias_ref[...]
        s = _mask_scores(s, iq, ik, codes, full, block_h=block_h,
                         block_q=block_q, block_k=block_k, causal=causal,
                         causal_offset=causal_offset, grouped=grouped,
                         window=window)
        p = jnp.exp(s - lse)

        dp_drop = jax.lax.dot_general(
            g, v, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        if dropout_p > 0.0:
            keep = _keep_mask3(seed_ref[0], b * block_h, iq * block_q,
                               ik * block_k, block_h, block_q, block_k,
                               dropout_p)
            dp = jnp.where(keep, dp_drop / (1.0 - dropout_p), 0.0)
        else:
            dp = dp_drop
        ds = p * (dp - delta) * scale
        dq_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    _by_class(_tile, cls_ref, iq, ik, nk, band=band)

    @pl.when(step == nk - 1)
    def _finalize():
        if grouped:
            _store_rows(dq_ref, dq_scr[:])
        else:
            _store_heads(dq_ref, dq_scr[:])


@functools.partial(jax.jit, static_argnames=(
    "heads", "is_causal", "scale", "dropout_p", "block_h", "block_q",
    "block_k", "interpret", "causal_offset", "kv_heads", "block_mask",
    "biased", "window"))
def _flash_backward(q, k, v, kbias, seed, out, lse, g, heads,
                    is_causal=False, scale=None, dropout_p=0.0,
                    block_h=1, block_q=128, block_k=128, interpret=False,
                    causal_offset=None, kv_heads=None, block_mask=None,
                    biased=True, window=None):
    bh, sq, sk, d, packed, lanes = _layout(q, k, kbias, heads)
    dv, v_lanes = _value_width(v, packed, kv_heads or heads)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    assert bh % block_h == 0 and heads % block_h == 0, (bh, heads, block_h)
    grouped = kv_heads is not None and kv_heads != heads
    masked = block_mask is not None
    go = g.astype(jnp.float32) * out.astype(jnp.float32)
    if packed:
        # per-head sums of a (B, Sq, H*D) product: a reduce over a
        # reshape to (..., H, D) costs an f32 relayout of the product;
        # the MXU sums each head's D lanes in place instead
        heads_of = jnp.repeat(jnp.eye(heads, dtype=jnp.float32), dv, axis=0)
        delta = jnp.dot(go.reshape(-1, heads * dv), heads_of,
                        precision=lax.Precision.HIGH)
        delta = jnp.transpose(delta.reshape(-1, sq, heads),
                              (0, 2, 1)).reshape(bh, sq, 1)
    else:
        delta = jnp.sum(go, axis=-1, keepdims=True)  # (BH, Sq, 1)
    if causal_offset is None:
        causal_offset = sk - sq
    tiler = None if window is not None else _tiler(
        block_mask, is_causal, causal_offset)
    tabled = tiler is not None
    kw = dict(scale=scale, block_h=block_h, block_q=block_q,
              block_k=block_k, causal=is_causal,
              causal_offset=causal_offset, dropout_p=dropout_p,
              grouped=grouped, tabled=tabled, masked=masked, biased=biased)
    nq, nk = sq // block_q, sk // block_k
    t_qk, codes, specs_qk, fetch_k = _mask_operands(
        tiler, block_mask, sq, sk, block_q, block_k, "qk")
    t_kq, _, specs_kq, fetch_q = _mask_operands(
        tiler, block_mask, sq, sk, block_q, block_k, "kq")
    # steps of the inner axis: every tile, or a window's band
    k_steps, q_steps = nk, nq
    kb_tile = lambda i, j, t: j
    if window is not None:
        assert is_causal and not masked and causal_offset == 0, window
        kw.update(window=window, tiles=(nq, nk))
        k_steps, q_steps = _band_lengths(window, nq, nk, block_q, block_k)
        kb_tile = fetch_k = _band_fetch_k(window, block_q, block_k, sk)
        fetch_q = _band_fetch_q(window, block_q, block_k, sq, q_steps)

    q_spec = _heads_spec(packed, heads, block_h, block_q, d, 1)
    g_spec = _heads_spec(packed, heads, block_h, block_q, dv, 1)
    row_spec = pl.BlockSpec((block_h, block_q, 1),
                            lambda b, i, j, *t: (b, i, 0))
    kb_spec = pl.BlockSpec((1, 1, block_k),
                           lambda b, i, j, *t, h=heads, bh_=block_h:
                           ((b * bh_) // h, 0, kb_tile(i, j, t)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    # whether the inner axis's tile goes through fetch_k / fetch_q
    redirect = fetch_k is not None
    if grouped:
        assert packed and d % 128 == 0 and dv == d and dropout_p == 0.0
        group = heads // kv_heads
        assert group % block_h == 0, (group, block_h)
        per_b, per_g = heads // block_h, group // block_h
        rows = block_h * block_q
        k_spec = v_spec = pl.BlockSpec(
            (1, block_k, d), lambda n, i, j, *t: (
                n // per_b, fetch_k(i, j, t) if redirect else j,
                (n % per_b) * block_h // group))
        # the dkv grid: (batch x kv head, k tile, head block x q tile)
        dkv_grid = (bh // group, nk, per_g * q_steps)
        q_tile = (lambda i, j, t: fetch_q(i, j, t)) if redirect \
            else (lambda i, j, t: j % nq)
        q_spec_t = g_spec_t = pl.BlockSpec(
            (1, block_q, block_h * d), lambda n, i, j, *t: (
                n // kv_heads, q_tile(i, j, t),
                (n % kv_heads) * per_g + j // q_steps))
        row_spec_t = pl.BlockSpec(
            (block_h, block_q, 1), lambda n, i, j, *t: (
                (n // kv_heads) * per_b + (n % kv_heads) * per_g
                + j // q_steps, q_tile(i, j, t), 0))
        k_spec_t = v_spec_t = pl.BlockSpec(
            (1, block_k, d),
            lambda n, i, j, *t: (n // kv_heads, i, n % kv_heads))
        kb_spec_t = pl.BlockSpec(
            (1, 1, block_k), lambda n, i, j, *t: (n // kv_heads, 0, i))
        dkv_scratch = [pltpu.VMEM((1, block_k, d), jnp.float32)] * 2
        dq_scratch = [pltpu.VMEM((1, rows, d), jnp.float32)]
        vmem = _GROUPED_VMEM_LIMIT
    else:
        k_spec = _heads_spec(packed, heads, block_h, block_k, d, 2,
                             fetch_k)
        v_spec = _heads_spec(packed, heads, block_h, block_k, dv, 2,
                             fetch_k)
        # dkv grid iterates (bh, ik, iq): swap index maps for q-side
        # inputs
        dkv_grid = (bh // block_h, nk, q_steps)
        q_spec_t = _heads_spec(packed, heads, block_h, block_q, d, 2,
                               fetch_q)
        g_spec_t = _heads_spec(packed, heads, block_h, block_q, dv, 2,
                               fetch_q)
        row_spec_t = pl.BlockSpec(
            (block_h, block_q, 1), lambda b, i, j, *t: (
                b, fetch_q(i, j, t) if redirect else j, 0))
        k_spec_t = _heads_spec(packed, heads, block_h, block_k, d, 1)
        v_spec_t = _heads_spec(packed, heads, block_h, block_k, dv, 1)
        kb_spec_t = pl.BlockSpec((1, 1, block_k),
                                 lambda b, i, j, *t, h=heads, bh_=block_h:
                                 ((b * bh_) // h, 0, i))
        dkv_scratch = [
            pltpu.VMEM((block_h, block_k, lanes), jnp.float32),
            pltpu.VMEM((block_h, block_k, v_lanes), jnp.float32)]
        dq_scratch = [pltpu.VMEM((block_h, block_q, lanes), jnp.float32)]
        vmem = _PACKED_VMEM_LIMIT if packed else None
    params = _compiler_params(vmem_limit=vmem)

    dk, dv_out = _pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, q_tiles=q_steps, **kw),
        grid=dkv_grid,
        in_specs=[smem, q_spec_t, g_spec_t, row_spec_t, row_spec_t,
                  k_spec_t, v_spec_t, kb_spec_t] + specs_kq,
        out_specs=[k_spec_t, v_spec_t],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=dkv_scratch,
        tables=t_kq,
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(seed, q, g, lse, delta, k, v, kbias, *codes)

    dq = _pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **kw),
        grid=(bh // block_h, nq, k_steps),
        in_specs=[smem, q_spec, g_spec, row_spec, row_spec,
                  k_spec, v_spec, kb_spec] + specs_qk,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=dq_scratch,
        tables=t_qk,
        compiler_params=params,
        interpret=interpret,
        name="flash_bwd_dq",
    )(seed, q, g, lse, delta, k, v, kbias, *codes)
    return dq, dk, dv_out


# -- custom VJP over the kernels ----------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=tuple(range(5, 18)))
def _flash_attention(q, k, v, kbias, seed_f, heads, is_causal, scale,
                     dropout_p, interpret, causal_offset, block_h,
                     block_q, block_k, kv_heads, block_mask, biased,
                     window=None):
    """seed_f: (1,) float32 — a bitcast int32 dropout seed (float so the
    custom_vjp machinery sees only inexact primals).  causal_offset is
    the ORIGINAL sk - sq (pre-padding): the shim pads seq lengths, so it
    cannot be recovered from the padded shapes."""
    seed = lax.bitcast_convert_type(seed_f, jnp.int32)
    out, _ = _flash_forward(q, k, v, kbias, seed, heads,
                            is_causal=is_causal, scale=scale,
                            dropout_p=dropout_p, interpret=interpret,
                            causal_offset=causal_offset, block_h=block_h,
                            block_q=block_q, block_k=block_k,
                            kv_heads=kv_heads, block_mask=block_mask,
                            biased=biased, window=window)
    return out


@kernel_trace("flash_attention")
def _flash_fwd_rule(q, k, v, kbias, seed_f, heads, is_causal, scale,
                    dropout_p, interpret, causal_offset, block_h,
                    block_q, block_k, kv_heads, block_mask, biased,
                    window=None):
    seed = lax.bitcast_convert_type(seed_f, jnp.int32)
    out, lse = _flash_forward(q, k, v, kbias, seed, heads,
                              is_causal=is_causal, scale=scale,
                              dropout_p=dropout_p, interpret=interpret,
                              causal_offset=causal_offset,
                              block_h=block_h, block_q=block_q,
                              block_k=block_k, kv_heads=kv_heads,
                              block_mask=block_mask, biased=biased,
                              window=window)
    return out, (q, k, v, kbias, seed, out, lse)


@kernel_trace("flash_attention")
def _flash_bwd_rule(heads, is_causal, scale, dropout_p, interpret,
                    causal_offset, block_h, block_q, block_k, kv_heads,
                    block_mask, biased, window, res, g):
    q, k, v, kbias, seed, out, lse = res
    dq, dk, dv = _flash_backward(
        q, k, v, kbias, seed, out, lse, g, heads, is_causal=is_causal,
        scale=scale, dropout_p=dropout_p, interpret=interpret,
        causal_offset=causal_offset, block_h=block_h, block_q=block_q,
        block_k=block_k, kv_heads=kv_heads, block_mask=block_mask,
        biased=biased, window=window)
    # key-bias grads are not needed (masks are constants); seed is rng
    return dq, dk, dv, jnp.zeros_like(kbias), jnp.zeros_like(
        lse, shape=(1,))


_flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# -- public API ---------------------------------------------------------------

def _pick_blocks(sq, sk, d, block_q=None, block_k=None,
                 vmem_budget=8 * 1024 * 1024):
    """Choose MXU-friendly block sizes.  Bigger tiles amortize the
    per-grid-step overhead (measured on v5e: (512,512) blocks run the
    S=512 BERT forward ~4x faster than (128,128)), capped so the
    working set (q/k/v blocks + f32 scores + accumulators) stays well
    inside VMEM."""
    if block_q is None:
        block_q = min(512, round_up(sq, 128))
    if block_k is None:
        block_k = min(512, round_up(sk, 128))
    # working set ~= f32 scores + probs + q/k/v/acc tiles; shrink in
    # 128-steps (Mosaic wants lane-dim blocks divisible by 128)
    while block_q > 128 and (
            block_q * block_k * 8 + (block_q + 2 * block_k) * d * 4
            > vmem_budget):
        block_q -= 128
    while block_k > 128 and (
            block_q * block_k * 8 + (block_q + 2 * block_k) * d * 4
            > vmem_budget):
        block_k -= 128
    return block_q, block_k


def _packs(heads, d):
    """Whether the kernels can take q/k/v as the projections write
    them, (B, S, H*D): a grid step's heads must be whole 128-lane
    blocks, a head pair at D = 64 mod 128 (64; 192, three blocks a
    pair), single heads at D a multiple of 128.  Every other shape
    keeps the merged (B*H, S, D) operands."""
    return d % 128 == 0 or (d % 128 == 64 and heads % 2 == 0)


def _block_h_ladder(heads, lane_d=None, max_h=8):
    """Candidate head-block sizes, largest first, ending in the
    smallest valid one.  Batching block_h (q, k) panels per grid step
    amortizes the fixed per-grid-step cost of the (BH, 1, 1) grid at
    512-blocks.  Each candidate must divide `heads` (a head block must
    not span batch elements — the kbias block is per batch element)
    and, on the packed layout (`lane_d` = its D), fill whole 128-lane
    blocks: head pairs at D = 64.  Whether a rung fits VMEM is Mosaic's
    call: the caller compile-probes each rung and takes the first one
    accepted (`max_h` spares it the probes of rungs known too large)."""
    return [B for B in (8, 6, 4, 3, 2, 1) if heads % B == 0
            and (lane_d is None or B * lane_d % 128 == 0)
            and B <= max_h]


@kernel_trace("flash_attention")
def flash_attention(q, k, v, key_bias=None, is_causal=False, scale=None,
                    dropout_p=0.0, dropout_seed=None, block_q=None,
                    block_k=None, interpret=False, block_mask=None,
                    window=None):
    """(B, S, H, D) flash attention via the Pallas kernels.

    key_bias: optional (B, Sk) float32 additive bias applied to every
    query row (the in-kernel form of a key-padding mask).  Without one,
    and with no key to pad, the bias would be all zeros: the kernels
    are then built without the line that adds it.  It is
    treated as a CONSTANT (stop_gradient): masks are the use case; a
    *learned* bias would silently get zero gradient here, so pass those
    through `scaled_dot_product_attention`'s XLA path instead.
    Arbitrary per-query masks are not expressible here either — use
    `scaled_dot_product_attention`, which falls back to XLA for those.

    Any seq length / head dim is accepted: inputs are padded to block
    multiples, padded keys are masked via the bias, and the output is
    sliced back (ADVICE round-1 #1: the unpadded kernel read garbage
    K/V columns for non-block-multiple lengths).

    Operand layout, chosen from the shape (`_packs`): where a grid
    step's heads fill whole 128-lane blocks the kernels read q/k/v and
    write the output (and dq/dk/dv) as (B, S, H*D), a free reshape of
    what the projections produce, so no transpose surrounds the calls
    (`flash_packed_layout_total` counts these instances); otherwise
    heads are merged into (B*H, S, D) by an XLA transpose, D padded to
    a multiple of 64.

    Grouped-query attention: k and v may hold fewer heads, (B, Sk, Hkv,
    D) with Hkv dividing H; query head j reads key/value head j //
    (H / Hkv).  On the packed layout at D a multiple of 128 the kernels
    read the Hkv heads as they are, one key/value tile a group and grid
    step; any other shape, and attention dropout, repeats them in HBM
    first.

    block_mask: a `BlockDiffusionMask` whose rows are q's and k's (self
    attention over `[x_t ‖ x_0]`).  Its tile table gives every (q
    tile, k tile) pair a class: dead tiles are skipped, partial ones
    masked from index codes, and full ones (every pair live) run
    without the mask in the two backward kernels — the forward kernel
    keeps it, a second copy of its body costing memory the cells at
    8k rows do not have
    (`flash_tiles_full_total` of `flash_tiles_live_total` of
    `flash_tiles_total`, per head, counted here at trace time with
    `flash_block_mask_total` instances); no dense mask exists.

    window (with `is_causal`, self-attention): row i sees the `window`
    keys i - window < j <= i.  The kernels' grids walk the band: the k
    axis of the forward and dq kernels, the q axis of dkv, is as long
    as the band of one tile (`window / block + 1` tiles, or + 2), the
    tile of a step the band's first plus the step's index, so that the
    grid grows with rows x window and not rows^2; (`_WINDOW_BLOCK`,
    `_WINDOW_BLOCK`) tiles unless the caller names others.  Counted at
    trace time: `flash_window_total` instances,
    `flash_window_grid_steps_total` forward grid steps a head and
    `flash_window_tiles_live_total` of them on a live tile (the first
    q tiles' bands are shorter than the grid) beside the `flash_tiles_*`
    of the whole rectangle.  `window >= rows` is the plain causal
    instance.
    """
    b, sq, h, d = q.shape
    sk, dv = k.shape[1], v.shape[3]
    if window is not None:
        if not is_causal or sq != sk or block_mask is not None \
                or window < 1:
            raise ValueError(
                "a window is for causal self-attention without a block "
                f"mask: is_causal {is_causal}, q {sq} and k {sk} rows, "
                f"window {window}")
        if window >= sq:
            window = None
        else:
            block_q = block_q or min(_WINDOW_BLOCK, round_up(sq, 128))
            block_k = block_k or min(_WINDOW_BLOCK, round_up(sk, 128))
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    kv_heads = k.shape[2]
    packed = _packs(h, d) and _packs(h, dv)
    grouped = kv_heads != h
    if grouped and not (packed and d % 128 == 0 and dv == d
                        and dropout_p == 0.0):
        k = jnp.repeat(k, h // kv_heads, axis=2)
        v = jnp.repeat(v, h // kv_heads, axis=2)
        kv_heads, grouped = h, False
    if block_mask is not None and not (sq == sk == block_mask.rows):
        raise ValueError(
            f"block_mask covers {block_mask.rows} rows, q has {sq} and "
            f"k {sk}")

    block_q, block_k = _pick_blocks(sq, sk, d, block_q, block_k)
    if grouped:
        # the whole group in one step where its score tiles fit: its
        # key/value tile is then read once a group
        while (h // kv_heads) * block_q * block_k > _PACKED_MAX_SCORES \
                and block_q > 128:
            block_q //= 2
    sq_p = round_up(sq, block_q)
    sk_p = round_up(sk, block_k)
    d_p, dv_p = (d, dv) if packed else (round_up(d, 64), round_up(dv, 64))

    if packed:
        merge = lambda x, s: x.reshape(b, s, -1)
    else:
        merge = lambda x, s: jnp.transpose(x, (0, 2, 1, 3)).reshape(
            b * h, s, -1)
    qm = merge(q, sq)
    if grouped:
        km, vm = (x.reshape(b, sk, kv_heads * d) for x in (k, v))
    else:
        km, vm = merge(k, sk), merge(v, sk)
    if sq_p != sq or d_p != d:
        qm = jnp.pad(qm, ((0, 0), (0, sq_p - sq), (0, d_p - d)))
    if sk_p != sk or d_p != d:
        km = jnp.pad(km, ((0, 0), (0, sk_p - sk), (0, d_p - d)))
    if sk_p != sk or dv_p != dv:
        vm = jnp.pad(vm, ((0, 0), (0, sk_p - sk), (0, dv_p - dv)))

    bias = jnp.zeros((b, sk_p), jnp.float32) if key_bias is None \
        else jnp.pad(lax.stop_gradient(key_bias).astype(jnp.float32),
                     ((0, 0), (0, sk_p - sk)))
    if sk_p != sk:  # mask out padded keys
        valid = jnp.arange(sk_p) < sk
        bias = jnp.where(valid[None, :], bias, DEFAULT_MASK_VALUE)
    bias = bias[:, None, :]  # (B, 1, Sk_p): unit sublane dim for Mosaic
    biased = key_bias is not None or sk_p != sk     # else: all zeros

    if dropout_p > 0.0:
        seed = (jnp.zeros((1,), jnp.int32) if dropout_seed is None
                else jnp.asarray(dropout_seed, jnp.int32).reshape((1,)))
    else:
        seed = jnp.zeros((1,), jnp.int32)
    seed_f = lax.bitcast_convert_type(seed, jnp.float32)

    # packed steps are sized to _PACKED_VMEM_LIMIT: at most 4 heads'
    # (512, 512) score tiles; the merged ladder is left to the probes
    ladder = _block_h_ladder(
        h // kv_heads if grouped else h, d,
        _PACKED_MAX_SCORES // (block_q * block_k)) if packed \
        else _block_h_ladder(h)
    if interpret:
        # exercise the head-blocked (3D-batched) kernel path in CPU
        # interpret tests too — same grid validity rules, no probing
        block_h = ladder[0]
    else:
        # Compile the EXACT fwd+bwd instances standalone before
        # committing the traced graph to them: a Mosaic refusal would
        # otherwise surface at the caller's jit compile, where nothing
        # can catch it.  Walk the head-block ladder: the first rung
        # Mosaic accepts wins; exhaustion gives way to XLA, counted.
        block_h = None
        if on_tpu():
            for cand in ladder:
                if _probe_exact((b * h, sq_p, d_p), (b * h, sk_p, d_p), h,
                                is_causal, float(dropout_p), qm.dtype,
                                cand, block_q, block_k, sk - sq,
                                final_rung=(cand == ladder[-1]),
                                packed=packed, kv_heads=kv_heads,
                                block_mask=block_mask, biased=biased,
                                v_dim=dv_p, window=window):
                    block_h = cand
                    break
        if block_h is None:
            mask = None if key_bias is None \
                else lax.stop_gradient(key_bias)[:, None, None, :]
            if block_mask is not None:
                dense = jnp.asarray(block_mask.dense())[None, None]
                mask = dense if mask is None else jnp.where(
                    dense, mask, DEFAULT_MASK_VALUE)
            # carry the caller's per-step seed into the XLA path, else
            # its default PRNGKey(0) would reuse one dropout mask every
            # step
            dk = jax.random.fold_in(jax.random.PRNGKey(0), seed[0]) \
                if dropout_p > 0.0 else None
            return _xla_attention(q, k, v, mask=mask,
                                  is_causal=is_causal, scale=scale,
                                  dropout_p=dropout_p, dropout_key=dk,
                                  window=window)

    from ...profiler import stat_add

    tiler = _WindowTiles(window) if window is not None else _tiler(
        block_mask, is_causal, sk - sq)
    if tiler is not None:
        cls = tiler.tiles(sq_p, sk_p, block_q, block_k)[0]
        if block_mask is not None:
            stat_add("flash_block_mask_total")
        stat_add("flash_tiles_full_total", int((cls == 2).sum()))
        stat_add("flash_tiles_live_total", int((cls != 0).sum()))
        stat_add("flash_tiles_total", cls.size)
        if window is not None:
            stat_add("flash_window_total")
            stat_add("flash_window_grid_steps_total", cls.shape[0]
                     * _band_lengths(window, *cls.shape, block_q,
                                     block_k)[0])
            stat_add("flash_window_tiles_live_total", int((cls != 0).sum()))
    if dv != d:
        stat_add("flash_split_value_total")
    stat_add("flash_fwd_pieces_total", block_h)
    out = _flash_attention(qm, km, vm, bias, seed_f, h, is_causal, scale,
                           float(dropout_p), interpret, sk - sq,
                           block_h, block_q, block_k, kv_heads, block_mask,
                           biased, window)
    if packed:
        stat_add("flash_packed_layout_total")
        return out[:, :sq].reshape(b, sq, h, dv)
    out = out[:, :sq, :dv]
    return jnp.transpose(out.reshape(b, h, sq, dv), (0, 2, 1, 3))


_EXACT_PROBE_CACHE = {}


def _probe_exact(q_shape, k_shape, heads, is_causal, dropout_p, dtype,
                 block_h, block_q, block_k, causal_offset,
                 final_rung=True, packed=False, kv_heads=None,
                 block_mask=None, biased=True, v_dim=None, window=None):
    """Compile (never run) the exact kernel instances flash_attention is
    about to stage, once per configuration.  q_shape / k_shape are the
    padded (B*H, S, D) whichever the operand layout; `packed` probes
    the (B, S, H*D) instance of them.  Returns False if Mosaic
    refuses them, so the caller can take a smaller head-block rung (or
    XLA) instead of poisoning the surrounding jit compile.
    final_rung=False marks a speculative head-block ladder rung: its
    refusal is routine and stays silent and uncounted."""
    key = (q_shape, k_shape, heads, is_causal, dropout_p,
           jnp.dtype(dtype).name, block_h, block_q, block_k,
           causal_offset, packed, kv_heads, block_mask, biased, v_dim,
           window)
    if key not in _EXACT_PROBE_CACHE:
        def compile_probe():
            bh, sq, d = q_shape
            sk = k_shape[1]
            dv = v_dim or d
            fold = (lambda s, w, n=heads: (bh // heads, s, n * w)) \
                if packed else (lambda s, w, n=heads: (bh, s, w))
            x = probe_struct(fold(sq, d), dtype)
            o = probe_struct(fold(sq, dv), dtype)
            kk = probe_struct(fold(sk, d, kv_heads or heads), dtype)
            vv = probe_struct(fold(sk, dv, kv_heads or heads), dtype)
            kb = probe_struct((bh // heads, 1, sk), jnp.float32)
            seed = probe_struct((1,), jnp.int32)
            kw = dict(is_causal=is_causal, dropout_p=dropout_p,
                      block_h=block_h, block_q=block_q, block_k=block_k,
                      causal_offset=causal_offset, kv_heads=kv_heads,
                      block_mask=block_mask, biased=biased, window=window)
            _flash_forward.lower(x, kk, vv, kb, seed, heads,
                                 **kw).compile()
            lse = probe_struct((bh, sq, 1), jnp.float32)
            _flash_backward.lower(x, kk, vv, kb, seed, o, lse, o, heads,
                                  **kw).compile()

        _try_compile(
            compile_probe, _EXACT_PROBE_CACHE, key,
            "paddle_tpu: flash-attention instance "
            f"q{q_shape} k{k_shape} blocks=({block_h},{block_q},"
            f"{block_k}) failed to compile ({{err}}); the head-block "
            "ladder is exhausted, this shape takes the XLA attention "
            "path.", "flash_fallback_total" if final_rung else None)
    return _EXACT_PROBE_CACHE[key]


def _mask_as_key_bias(mask, batch, sk):
    """Reduce a mask to (B, Sk) additive key bias if it is constant over
    query and head dims; return None when it is not expressible."""
    if mask is None:
        return None
    m = mask
    if m.ndim == 4:
        if m.shape[1] != 1 or m.shape[2] != 1:
            return None
        m = m[:, 0, 0, :]
    elif m.ndim == 3:
        if m.shape[1] != 1:
            return None
        m = m[:, 0, :]
    elif m.ndim != 2:
        return None
    if m.shape[-1] != sk:
        return None
    if m.dtype == jnp.bool_:
        m = jnp.where(m, 0.0, DEFAULT_MASK_VALUE)
    m = jnp.broadcast_to(m.astype(jnp.float32), (batch, sk))
    return m


_PROBE_CACHE = {}


def _try_compile(compile_fn, cache, key, fail_msg, count):
    """Shared probe body: compile once per key and cache the verdict.
    A refusal warns once with `fail_msg` and bumps the `count` stat —
    here, at trace time, never per step.  `count=None` marks a
    speculative probe whose refusal is routine: silent, uncounted."""
    try:
        compile_fn()
        cache[key] = True
    except Exception as err:  # noqa: BLE001 - Pallas lowering and Mosaic raise several types; the verdict is recorded, warned and counted
        cache[key] = False
        if count is not None:
            from ...profiler import stat_add

            stat_add(count)
            warnings.warn(
                fail_msg.format(err=f"{type(err).__name__}: {err}"),
                RuntimeWarning, stacklevel=4)
    return cache[key]


def _compiler_params(semantics=("parallel", "parallel", "arbitrary"),
                     vmem_limit=None):
    """Grid dimension semantics: parallel over independent output
    blocks, arbitrary over accumulation axes.  vmem_limit: the kernel's
    scoped-VMEM budget in bytes where the compiler's default (16 MiB of
    the v5e's 128) is not to decide."""
    return pltpu.CompilerParams(dimension_semantics=tuple(semantics),
                                vmem_limit_bytes=vmem_limit)


def _probe_flash_kernel(block_q=128, block_k=128, d=128,
                        dtype=jnp.bfloat16):
    """Compile (never run) a tiny fwd+bwd kernel instance for the
    device, once per block config.  If Mosaic refuses it, attention
    takes the plain XLA path with a warning and a count.

    `.lower().compile()` happens at the Python level, so this is safe to
    call while tracing an outer jit: nothing is staged into the caller's
    graph."""
    key = (block_q, block_k, d, jnp.dtype(dtype).name)
    if key not in _PROBE_CACHE:
        def compile_probe():
            s = 2 * max(block_q, block_k)
            x = probe_struct((2, s, d), dtype)
            kb = probe_struct((1, 1, s), jnp.float32)
            seed = probe_struct((1,), jnp.int32)
            _flash_forward.lower(
                x, x, x, kb, seed, 2, is_causal=True, dropout_p=0.1,
                block_q=block_q, block_k=block_k,
                causal_offset=0).compile()
            lse = probe_struct((2, s, 1), jnp.float32)
            _flash_backward.lower(
                x, x, x, kb, seed, x, lse, x, 2, is_causal=True,
                dropout_p=0.1, block_q=block_q, block_k=block_k,
                causal_offset=0).compile()

        _try_compile(
            compile_probe, _PROBE_CACHE, key,
            "paddle_tpu: Pallas flash-attention kernel failed to "
            "compile for this TPU ({err}); attention takes the XLA "
            "path.", "flash_fallback_total")
    return _PROBE_CACHE[key]


def _flash_ok(q, k):
    """Kernel-dispatch heuristic: on TPU, the sequences long enough
    that blockwise tiling wins over plain XLA (the padding shim makes
    any shape *correct*; this is about perf), and the kernel actually
    compiles for this chip (probe above)."""
    if not on_tpu():
        return False
    if not (q.shape[1] >= 128 and k.shape[1] >= 128):
        return False
    bq, bk = _pick_blocks(q.shape[1], k.shape[1], q.shape[-1])
    return _probe_flash_kernel(bq, bk, round_up(q.shape[-1], 64),
                               q.dtype)


import contextlib
import threading

_RING_CTX = threading.local()  # per-thread, like the tracer's rng scope


@contextlib.contextmanager
def ring_attention_scope(mesh, axis="sp"):
    """Route subsequent attention calls through ring attention
    (sequence-parallel over `axis`; paddle_tpu/parallel/ring_attention.py).
    Model code stays unchanged — MultiHeadAttention picks it up via the
    dispatcher below."""
    old = (getattr(_RING_CTX, "mesh", None), getattr(_RING_CTX, "axis", None))
    _RING_CTX.mesh, _RING_CTX.axis = mesh, axis
    try:
        yield
    finally:
        _RING_CTX.mesh, _RING_CTX.axis = old


_ULYSSES_CTX = threading.local()


@contextlib.contextmanager
def ulysses_attention_scope(mesh, axis="sp"):
    """Route subsequent attention calls through Ulysses all-to-all
    sequence parallelism (parallel/ulysses.py).  Unlike the ring scope,
    key-padding masks ARE supported (each device sees the full key axis
    for its head group); attention dropout is not."""
    old = (getattr(_ULYSSES_CTX, "mesh", None),
           getattr(_ULYSSES_CTX, "axis", None))
    _ULYSSES_CTX.mesh, _ULYSSES_CTX.axis = mesh, axis
    try:
        yield
    finally:
        _ULYSSES_CTX.mesh, _ULYSSES_CTX.axis = old


_MESH_CTX = threading.local()


@contextlib.contextmanager
def sharded_attention_scope(mesh, batch_axis="dp", head_axis=None):
    """Run the flash kernels per shard under a jit whose operands are
    sharded over `mesh`: batch over `batch_axis`, heads over
    `head_axis`.  GSPMD cannot partition a Mosaic kernel ("wrap the
    call in a shard_map") and refuses to lower the step otherwise;
    attention is independent per batch element and head, so the split
    is exact.  Only the kernel path is wrapped — the XLA attention
    path partitions by itself."""
    old = getattr(_MESH_CTX, "spec", None)
    _MESH_CTX.spec = (mesh, batch_axis, head_axis)
    try:
        yield
    finally:
        _MESH_CTX.spec = old


def _flash_per_shard(spec, q, k, v, key_bias, is_causal, scale,
                     dropout_p, seed, interpret=False, block_mask=None,
                     window=None):
    """flash_attention under shard_map over `spec` = (mesh, batch_axis,
    head_axis); q/k/v (B, S, H, D) global, key_bias (B, Sk) or None."""
    from jax.sharding import PartitionSpec as P

    mesh, batch_axis, head_axis = spec
    qkv = P(batch_axis, None, head_axis, None)
    if key_bias is None:
        key_bias = jnp.zeros((q.shape[0], k.shape[1]), jnp.float32)
    if seed is None:
        seed = jnp.zeros((1,), jnp.int32)

    def local(q, k, v, kb, seed):
        # the in-kernel dropout hash runs over LOCAL (batch, head)
        # coordinates: fold the shard's position into the seed so two
        # shards never draw the same mask
        for ax in (batch_axis, head_axis):
            if ax is not None:
                seed = seed * jnp.int32(1000003) + lax.axis_index(ax)
        return flash_attention(q, k, v, key_bias=kb, is_causal=is_causal,
                               scale=scale, dropout_p=dropout_p,
                               dropout_seed=seed, interpret=interpret,
                               block_mask=block_mask, window=window)

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(qkv, qkv, qkv, P(batch_axis, None), P()),
        out_specs=qkv, check_vma=False)(q, k, v, key_bias, seed)


def _seed_from_key(key):
    """Fold a jax PRNG key into a (1,) int32 kernel seed."""
    if key is None:
        return None
    data = jax.random.key_data(key) if jnp.issubdtype(
        key.dtype, jax.dtypes.prng_key) else key
    data = data.reshape(-1).astype(jnp.uint32)
    folded = data[0] * jnp.uint32(0x9E3779B9) + data[-1]
    return lax.bitcast_convert_type(folded, jnp.int32).reshape((1,))


# -- ragged paged attention (serving decode path) -----------------------------

def _ragged_paged_kernel(rows_ref, len_ref, q_ref, k_ref, v_ref,
                         qp_ref, o_ref, m_scr, l_scr, acc_scr,
                         *, page_size, scale):
    """One grid step = one (sequence, page) pair.

    The page table rides in as SCALAR-PREFETCH operands (rows_ref,
    len_ref live in SMEM before the body runs), so the k/v BlockSpec
    index_maps below dereference `rows[b, i]` to DMA page i of
    sequence b straight out of the pool — the dense (B, Lmax, H, D)
    gather the XLA path materializes never exists here (*Ragged Paged
    Attention*, arxiv 2604.15464).  Online softmax accumulates across
    the page axis exactly like the flash kernel's key-block axis."""
    b = pl.program_id(0)
    i = pl.program_id(1)
    npg = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    # pages wholly beyond the sequence are skipped (their row entries
    # point at scratch page 0); page 0 of the grid always runs so a
    # length-0 lane still produces the finite uniform-softmax output
    # the dense reference yields for an all-masked row
    @pl.when(jnp.logical_or(i == 0, i * page_size < length))
    def _accumulate():
        q = q_ref[0]                      # (T, H, D)
        k = k_ref[0]                      # (S, H, D)
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((1,), (1,))),
            preferred_element_type=jnp.float32) * scale  # (H, T, S)
        kpos = i * page_size + lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        qpos = qp_ref[...]                # (1, T, 1)
        s = jnp.where(kpos <= qpos, s, DEFAULT_MASK_VALUE)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_cur = jnp.max(s, axis=2, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            (((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32)          # (H, T, D)
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(i == npg - 1)
    def _finalize():
        o_ref[0] = jnp.transpose(acc_scr[:] / l_scr[:],
                                 (1, 0, 2)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("page_size", "scale",
                                             "interpret"))
def _ragged_paged_forward(page_rows, lengths, q, k_pages, v_pages,
                          qpos, *, page_size, scale, interpret=False):
    """page_rows: (B, W) i32; lengths: (B,) i32; q: (B, T, H, D);
    k/v_pages: (P, S, H, D); qpos: (B, T, 1) i32 -> (B, T, H, D).

    Head and head_dim stay whole per block ((1, S, H, D) k/v blocks,
    last two dims equal to the array dims — the Mosaic divisibility
    escape hatch), so one grid step feeds the MXU all heads of one
    page and the grid is just (sequences, pages).  qpos rides with a
    unit LANE dim like the flash kernels' lse ((1, T, 1) blocks): a
    rank-2 (1, T) block of a (B, T) array has a second-minor dim of 1
    that is neither a multiple of 8 nor the array dim, which Mosaic
    refuses for every B > 1."""
    b, t, h, d = q.shape
    w = page_rows.shape[1]
    kernel = functools.partial(_ragged_paged_kernel,
                               page_size=page_size, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, w),
        in_specs=[
            pl.BlockSpec((1, t, h, d),
                         lambda b_, i, rows, lens: (b_, 0, 0, 0)),
            pl.BlockSpec((1, page_size, h, d),
                         lambda b_, i, rows, lens:
                         (rows[b_, i], 0, 0, 0)),
            pl.BlockSpec((1, page_size, h, d),
                         lambda b_, i, rows, lens:
                         (rows[b_, i], 0, 0, 0)),
            pl.BlockSpec((1, t, 1),
                         lambda b_, i, rows, lens: (b_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, t, h, d),
                               lambda b_, i, rows, lens:
                               (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, t, 1), jnp.float32),
            pltpu.VMEM((h, t, 1), jnp.float32),
            pltpu.VMEM((h, t, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h, d), q.dtype),
        # sequences write disjoint outputs -> parallel; the page axis
        # accumulates in scratch -> arbitrary
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(page_rows, lengths, q, k_pages, v_pages, qpos)


_RAGGED_PROBE_CACHE = {}


def _probe_ragged(q_shape, pool_shape, rows_shape, dtype, page_size,
                  scale):
    """Compile (never run) the exact ragged-kernel instance once per
    configuration; False means Mosaic refused it and the caller takes
    the dense-gather XLA path — counted via
    serving_ragged_fallback_total so a fleet silently running the slow
    path shows up in the stats, not just in a scrolled-away warning."""
    key = (q_shape, pool_shape, rows_shape, jnp.dtype(dtype).name,
           page_size)
    if key not in _RAGGED_PROBE_CACHE:
        def compile_probe():
            b, t = q_shape[0], q_shape[1]
            _ragged_paged_forward.lower(
                probe_struct(rows_shape, jnp.int32),
                probe_struct((b,), jnp.int32),
                probe_struct(q_shape, dtype),
                probe_struct(pool_shape, dtype),
                probe_struct(pool_shape, dtype),
                probe_struct((b, t, 1), jnp.int32),
                page_size=page_size, scale=scale).compile()

        _try_compile(
            compile_probe, _RAGGED_PROBE_CACHE, key,
            "paddle_tpu: ragged paged-attention kernel "
            f"q{q_shape} pool{pool_shape} failed to compile ({{err}}); "
            "serving decode falls back to the dense-gather XLA path "
            "for this shape (correct but slower).",
            "serving_ragged_fallback_total")
    return _RAGGED_PROBE_CACHE[key]


def _dense_paged_attention(q, k_pages, v_pages, page_rows, lengths,
                           qpos, scale):
    """XLA reference/fallback: gather the pages into a contiguous
    (B, Lmax, H, D) view (Lmax = max_pages * S, static) and dispatch
    through `scaled_dot_product_attention` with an additive bias.  For
    T == 1 the bias is constant over queries, so on TPU it rides the
    flash kernel's key-bias fast path."""
    b, t, h, d = q.shape
    p, s = k_pages.shape[0], k_pages.shape[1]
    max_pages = page_rows.shape[1]
    lmax = max_pages * s
    pos = jnp.arange(lmax, dtype=jnp.int32)
    # flat pool index of logical position `pos` of each sequence
    gidx = page_rows[:, pos // s] * s + pos % s          # (B, Lmax)
    kflat = k_pages.reshape(p * s, h, d)
    vflat = v_pages.reshape(p * s, h, d)
    k = kflat[gidx]                                      # (B, Lmax, H, D)
    v = vflat[gidx]
    bias = jnp.where(pos[None, None, :] <= qpos[:, :, None], 0.0,
                     DEFAULT_MASK_VALUE).astype(jnp.float32)
    return scaled_dot_product_attention(
        q, k, v, mask=bias[:, None, :, :], scale=scale)


def paged_attention(q, k_pages, v_pages, page_rows, lengths, scale=None,
                    q_positions=None, interpret=False):
    """Attention over PAGED keys/values (serving decode path).

    q: (B, T, H, D) — the T newest query positions per sequence
    (decode: T == 1; chunked prefill: T == chunk bucket);
    k_pages/v_pages: (P, S, H, D) device-resident page pools
    (serving/kv_cache.py); page_rows: (B, max_pages) int32 page ids
    per sequence (unused entries -> scratch page 0); lengths: (B,)
    int32 — valid key count per sequence.

    Masking: query j of sequence b attends keys at positions
    <= q_positions[b, j].  The default q_positions places the T
    queries at the newest T positions (lengths - T .. lengths - 1),
    i.e. plain length masking for T == 1 and causal-tail masking for
    a multi-token tail; chunked prefill passes its chunk's absolute
    positions explicitly.  Query lanes whose position is >= lengths
    (chunk padding) produce finite but unspecified output — callers
    slice them away.

    Dispatch: the ragged Pallas kernel above consumes `page_rows`
    directly via scalar prefetch — no dense (B, Lmax) gather is ever
    materialized — on TPU when the per-shape Mosaic probe accepts it,
    or anywhere under `interpret=True` (CPU tier-1 parity tests);
    otherwise the dense-gather XLA path, with the fallback counted in
    serving_ragged_fallback_total."""
    b, t, h, d = q.shape
    s = k_pages.shape[1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    if q_positions is None:
        q_positions = lengths[:, None] - t \
            + jnp.arange(t, dtype=jnp.int32)[None, :]
    qpos = q_positions.astype(jnp.int32)
    use_kernel = bool(interpret)
    if not use_kernel and on_tpu():
        use_kernel = _probe_ragged(
            q.shape, k_pages.shape, page_rows.shape, q.dtype, s,
            float(scale))
    if use_kernel:
        return _ragged_paged_forward(
            page_rows.astype(jnp.int32), lengths.astype(jnp.int32),
            q, k_pages, v_pages, qpos[:, :, None], page_size=s,
            scale=float(scale), interpret=bool(interpret))
    return _dense_paged_attention(q, k_pages, v_pages, page_rows,
                                  lengths, qpos, scale)


def scaled_dot_product_attention(q, k, v, mask=None, is_causal=False,
                                 scale=None, dropout_p=0.0,
                                 dropout_key=None, window=None):
    """Dispatcher: ring attention inside ring_attention_scope (sequence
    parallel), Pallas flash kernel on TPU (key-padding masks, a
    `BlockDiffusionMask`, grouped key/value heads and attention dropout
    run in-kernel), XLA path otherwise (arbitrary dense masks, tiny
    shapes, non-TPU backends).
    q/k/v: (batch, seq, heads, head_dim); k/v may hold fewer heads.
    `window` (with `is_causal`): a sliding window of that many keys,
    in-kernel too (`flash_attention`)."""
    block_mask = mask if isinstance(mask, BlockDiffusionMask) else None
    if (block_mask is not None or window is not None) and (
            getattr(_ULYSSES_CTX, "mesh", None) is not None
            or getattr(_RING_CTX, "mesh", None) is not None):
        raise ValueError("a BlockDiffusionMask or a window cannot be "
                         "routed through the ring / all-to-all "
                         "sequence-parallel paths")
    uly_mesh = getattr(_ULYSSES_CTX, "mesh", None)
    if uly_mesh is not None:
        if dropout_p != 0.0:
            raise ValueError(
                "ulysses_attention_scope is active but attention "
                "dropout is not supported by the all-to-all path; set "
                "attention dropout to 0 or exit the scope.")
        # same normalization as the flash path: any key-padding form
        # (ndim 2/3/4, bool or additive float) -> (B, S) additive bias;
        # query/head-varying masks are not expressible over all-to-all
        key_mask = _mask_as_key_bias(mask, q.shape[0], k.shape[1])
        if mask is not None and key_mask is None:
            raise ValueError(
                "ulysses_attention_scope supports key-padding masks "
                "(constant over query/head dims); got mask shape "
                f"{mask.shape}")
        from ...parallel.ulysses import ulysses_attention

        return ulysses_attention(uly_mesh, _ULYSSES_CTX.axis)(
            q, k, v, mask=key_mask, is_causal=is_causal, scale=scale)
    ring_mesh = getattr(_RING_CTX, "mesh", None)
    if ring_mesh is not None:
        if mask is not None or dropout_p != 0.0:
            # loud failure beats silently dropping sequence parallelism
            # (the whole point of the scope is bounded per-chip memory)
            raise ValueError(
                "ring_attention_scope is active but this attention call "
                "cannot be ring-routed: attention masks and attention "
                "dropout are not supported by the ring path yet. Set "
                "attention dropout to 0 (and drop the mask) or exit the "
                "scope.")
        from ...parallel.ring_attention import ring_attention

        return ring_attention(ring_mesh, _RING_CTX.axis)(
            q, k, v, is_causal=is_causal, scale=scale)
    if _flash_ok(q, k):
        dense = None if block_mask is not None else mask
        key_bias = _mask_as_key_bias(dense, q.shape[0], k.shape[1])
        if dense is None or key_bias is not None:
            seed = _seed_from_key(dropout_key)
            spec = getattr(_MESH_CTX, "spec", None)
            if spec is not None:
                return _flash_per_shard(spec, q, k, v, key_bias,
                                        is_causal, scale, dropout_p,
                                        seed, block_mask=block_mask,
                                        window=window)
            return flash_attention(
                q, k, v, key_bias=key_bias, is_causal=is_causal,
                scale=scale, dropout_p=dropout_p, dropout_seed=seed,
                block_mask=block_mask, window=window)
    return _xla_attention(q, k, v, mask=mask, is_causal=is_causal,
                          scale=scale, dropout_p=dropout_p,
                          dropout_key=dropout_key, window=window)
