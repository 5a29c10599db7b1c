"""Fused transformer FFN — Pallas TPU kernel.

Motivation (the builders' 2026-07-31 chip trace, ROADMAP Queue 3;
to be reproduced): the BERT bench step is
HBM-bound, and after attention the largest traffic group is the FFN —
the (tokens, d_ff) intermediates (gelu input/output, dropout mask and
select) each round-trip HBM as separate fusion results.  This kernel
computes

    out = dropout(act(x @ w1 + b1), p) @ w2 + b2

with the (block_t, block_f) intermediates living ONLY in VMEM: the
grid walks d_ff blocks ("arbitrary" axis) accumulating the second
matmul into a VMEM accumulator, so the d_ff dimension never
materializes in HBM.  Backward recomputes the intermediates in-kernel
(flash-style) from x, in two passes: a dW kernel (parallel over d_ff
blocks, accumulating over token blocks) and a dx kernel (parallel over
token blocks, accumulating over d_ff blocks).  Dropout uses the same
stateless coordinate-hash mask as the attention kernel
(attention.py:_keep_mask), so forward and both backward passes agree
bit-for-bit without storing the mask.

The reference hand-fuses the same structure in CUDA
(/root/reference/paddle/fluid/operators/fused/fused_feedforward_op.cu:1,
fused_dropout_helper.h) — this is its TPU-native counterpart.

Like the attention kernel, everything works in interpret mode on CPU
(tests) and the dispatcher probes Mosaic compilation with an XLA
fallback, so a kernel regression degrades to slower-but-correct.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import round_up


def _erf(x):
    """erf via Abramowitz-Stegun 7.1.26 (max abs err 1.5e-7): Mosaic
    has no erf/erfc primitive, so the exact-gelu path composes it from
    supported ops (abs/exp/mul). Accuracy is far inside bf16/f32
    training noise, and the XLA fallback uses the SAME formula so both
    dispatcher paths agree bit-for-bit in f32."""
    a1, a2, a3 = 0.254829592, -0.284496736, 1.421413741
    a4, a5, p = -1.453152027, 1.061405429, 0.3275911
    s = jnp.sign(x)
    ax = jnp.abs(x)
    t = 1.0 / (1.0 + p * ax)
    poly = t * (a1 + t * (a2 + t * (a3 + t * (a4 + t * a5))))
    return s * (1.0 - poly * jnp.exp(-ax * ax))


def _act(h, activation):
    if activation == "gelu":
        # exact-erf gelu (the repo's GELU()/F.gelu default), with _erf
        # composed from Mosaic-supported primitives
        return h * 0.5 * (1.0 + _erf(h * 0.7071067811865476))
    if activation == "gelu_tanh":
        return jax.nn.gelu(h, approximate=True)
    if activation == "relu":
        return jax.nn.relu(h)
    raise NotImplementedError(activation)


def _act_grad(pre, activation):
    """d act(pre) / d pre, computed in f32."""
    if activation == "relu":
        return (pre > 0).astype(pre.dtype)
    if activation == "gelu":
        # exact: d[x Phi(x)] = Phi(x) + x phi(x)
        inv_sqrt2 = 0.7071067811865476
        inv_sqrt2pi = 0.3989422804014327
        cdf = 0.5 * (1.0 + _erf(pre * inv_sqrt2))
        pdf = inv_sqrt2pi * jnp.exp(-0.5 * pre * pre)
        return cdf + pre * pdf
    # gelu_tanh (jax.nn.gelu approximate=True)
    c = 0.7978845608028654  # sqrt(2/pi)
    t = jnp.tanh(c * (pre + 0.044715 * pre ** 3))
    return 0.5 * (1 + t) + 0.5 * pre * (1 - t ** 2) * c * (
        1 + 3 * 0.044715 * pre ** 2)


def _ffn_keep(seed, t0, f0, block_t, block_f, dropout_p):
    """Stateless keep mask for the (block_t, block_f) tile at absolute
    (t0, f0) — the attention kernel's lowbias32 hash on coordinates."""
    r = (t0 + lax.broadcasted_iota(jnp.int32, (block_t, block_f), 0)
         ).astype(jnp.uint32)
    c = (f0 + lax.broadcasted_iota(jnp.int32, (block_t, block_f), 1)
         ).astype(jnp.uint32)
    x = (r * jnp.uint32(0x9E3779B1)) ^ (c * jnp.uint32(0x85EBCA77))
    x = x ^ (seed.astype(jnp.uint32) * jnp.uint32(0x165667B1))
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    thresh = jnp.uint32(min(int(dropout_p * 2 ** 32), 2 ** 32 - 1))
    return x >= thresh


def _h_block(x, w1, b1, seed, t0, f0, block_t, block_f, activation,
             dropout_p, want_h=True):
    """One recomputable (block_t, block_f) hidden tile in f32.

    Returns (pre, h_dropped_or_None, keep_or_None): the hash mask is
    computed ONCE here and shared by callers that also drop their dh
    (the backward kernels); want_h=False skips materializing h when the
    caller only needs pre/keep (the dx kernel)."""
    pre = jnp.dot(x, w1, preferred_element_type=jnp.float32) \
        + b1.astype(jnp.float32)
    keep = (_ffn_keep(seed, t0, f0, block_t, block_f, dropout_p)
            if dropout_p > 0.0 else None)
    h = None
    if want_h:
        h = _act(pre, activation)
        if keep is not None:
            h = jnp.where(keep, h / (1.0 - dropout_p), 0.0)
    return pre, h, keep


# -- forward ------------------------------------------------------------------

def _fwd_kernel(seed_ref, x_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                out_ref, acc_ref, *, block_t, block_f, n_f, activation,
                dropout_p):
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t0 = pl.program_id(0) * block_t
    f0 = f * block_f
    _, h, _ = _h_block(x_ref[...], w1_ref[...], b1_ref[...],
                       seed_ref[0], t0, f0, block_t, block_f,
                       activation, dropout_p)
    acc_ref[...] += jnp.dot(h.astype(x_ref.dtype), w2_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _finalize():
        out_ref[...] = (acc_ref[...]
                        + b2_ref[...].astype(jnp.float32)
                        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "activation", "dropout_p", "block_t", "block_f", "interpret"))
def _ffn_forward(x, w1, b1, w2, b2, seed, activation="gelu",
                 dropout_p=0.0, block_t=512, block_f=512,
                 interpret=False):
    T, H = x.shape
    F = w1.shape[1]
    n_t, n_f = T // block_t, F // block_f
    grid = (n_t, n_f)
    kernel = functools.partial(
        _fwd_kernel, block_t=block_t, block_f=block_f, n_f=n_f,
        activation=activation, dropout_p=dropout_p)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_t, H), lambda t, f: (t, 0)),
            pl.BlockSpec((H, block_f), lambda t, f: (0, f)),
            pl.BlockSpec((1, block_f), lambda t, f: (0, f)),
            pl.BlockSpec((block_f, H), lambda t, f: (f, 0)),
            pl.BlockSpec((1, H), lambda t, f: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, H), lambda t, f: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, H), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(seed, x, w1, b1.reshape(1, F), w2, b2.reshape(1, H))


# -- backward: dW pass (parallel over d_ff, accumulate over tokens) ----------

def _bwd_dw_kernel(seed_ref, x_ref, g_ref, w1_ref, b1_ref, w2_ref,
                   dw1_ref, db1_ref, dw2_ref,
                   dw1_acc, db1_acc, dw2_acc, *, block_t, block_f, n_t,
                   activation, dropout_p):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        dw1_acc[...] = jnp.zeros_like(dw1_acc)
        db1_acc[...] = jnp.zeros_like(db1_acc)
        dw2_acc[...] = jnp.zeros_like(dw2_acc)

    t0 = t * block_t
    f0 = pl.program_id(0) * block_f
    x = x_ref[...]
    g = g_ref[...]
    pre, h, keep = _h_block(x, w1_ref[...], b1_ref[...], seed_ref[0],
                            t0, f0, block_t, block_f, activation,
                            dropout_p)
    # dh = g @ w2^T ; dpre = drop'(dh) * act'(pre)
    dh = jnp.dot(g, w2_ref[...].T, preferred_element_type=jnp.float32)
    if keep is not None:
        dh = jnp.where(keep, dh / (1.0 - dropout_p), 0.0)
    dpre = dh * _act_grad(pre, activation)
    dw2_acc[...] += jnp.dot(h.astype(g.dtype).T, g,
                            preferred_element_type=jnp.float32)
    dw1_acc[...] += jnp.dot(x.T, dpre.astype(x.dtype),
                            preferred_element_type=jnp.float32)
    db1_acc[...] += jnp.sum(dpre, axis=0, keepdims=True)

    @pl.when(t == n_t - 1)
    def _finalize():
        dw1_ref[...] = dw1_acc[...].astype(dw1_ref.dtype)
        db1_ref[...] = db1_acc[...].astype(db1_ref.dtype)
        dw2_ref[...] = dw2_acc[...].astype(dw2_ref.dtype)


# -- backward: dx pass (parallel over tokens, accumulate over d_ff) ----------

def _bwd_dx_kernel(seed_ref, x_ref, g_ref, w1_ref, b1_ref, w2_ref,
                   dx_ref, acc_ref, *, block_t, block_f, n_f,
                   activation, dropout_p):
    f = pl.program_id(1)

    @pl.when(f == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    t0 = pl.program_id(0) * block_t
    f0 = f * block_f
    pre, _, keep = _h_block(x_ref[...], w1_ref[...], b1_ref[...],
                            seed_ref[0], t0, f0, block_t, block_f,
                            activation, dropout_p, want_h=False)
    dh = jnp.dot(g_ref[...], w2_ref[...].T,
                 preferred_element_type=jnp.float32)
    if keep is not None:
        dh = jnp.where(keep, dh / (1.0 - dropout_p), 0.0)
    dpre = dh * _act_grad(pre, activation)
    acc_ref[...] += jnp.dot(dpre.astype(x_ref.dtype), w1_ref[...].T,
                            preferred_element_type=jnp.float32)

    @pl.when(f == n_f - 1)
    def _finalize():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "activation", "dropout_p", "block_t", "block_f", "interpret"))
def _ffn_backward(x, w1, b1, w2, b2, seed, g, activation="gelu",
                  dropout_p=0.0, block_t=512, block_f=512,
                  interpret=False):
    T, H = x.shape
    F = w1.shape[1]
    n_t, n_f = T // block_t, F // block_f
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    b1r = b1.reshape(1, F)

    dw_kernel = functools.partial(
        _bwd_dw_kernel, block_t=block_t, block_f=block_f, n_t=n_t,
        activation=activation, dropout_p=dropout_p)
    dw1, db1, dw2 = pl.pallas_call(
        dw_kernel,
        grid=(n_f, n_t),
        in_specs=[
            smem,
            pl.BlockSpec((block_t, H), lambda f, t: (t, 0)),
            pl.BlockSpec((block_t, H), lambda f, t: (t, 0)),
            pl.BlockSpec((H, block_f), lambda f, t: (0, f)),
            pl.BlockSpec((1, block_f), lambda f, t: (0, f)),
            pl.BlockSpec((block_f, H), lambda f, t: (f, 0)),
        ],
        out_specs=[
            pl.BlockSpec((H, block_f), lambda f, t: (0, f)),
            pl.BlockSpec((1, block_f), lambda f, t: (0, f)),
            pl.BlockSpec((block_f, H), lambda f, t: (f, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, F), w1.dtype),
            jax.ShapeDtypeStruct((1, F), b1.dtype),
            jax.ShapeDtypeStruct((F, H), w2.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, block_f), jnp.float32),
            pltpu.VMEM((1, block_f), jnp.float32),
            pltpu.VMEM((block_f, H), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(seed, x, g, w1, b1r, w2)

    dx_kernel = functools.partial(
        _bwd_dx_kernel, block_t=block_t, block_f=block_f, n_f=n_f,
        activation=activation, dropout_p=dropout_p)
    dx = pl.pallas_call(
        dx_kernel,
        grid=(n_t, n_f),
        in_specs=[
            smem,
            pl.BlockSpec((block_t, H), lambda t, f: (t, 0)),
            pl.BlockSpec((block_t, H), lambda t, f: (t, 0)),
            pl.BlockSpec((H, block_f), lambda t, f: (0, f)),
            pl.BlockSpec((1, block_f), lambda t, f: (0, f)),
            pl.BlockSpec((block_f, H), lambda t, f: (f, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, H), lambda t, f: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((T, H), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(seed, x, g, w1, b1r, w2)

    db2 = jnp.sum(g.astype(jnp.float32), axis=0).astype(b2.dtype)
    return dx, dw1, db1.reshape(F), dw2, db2


# -- custom_vjp shim ----------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(6, 7, 8, 9, 10))
def _fused_ffn(x, w1, b1, w2, b2, seed_f, activation, dropout_p,
               block_t, block_f, interpret):
    seed = lax.bitcast_convert_type(seed_f, jnp.int32)
    return _ffn_forward(x, w1, b1, w2, b2, seed, activation=activation,
                        dropout_p=dropout_p, block_t=block_t,
                        block_f=block_f, interpret=interpret)


def _fused_ffn_fwd(x, w1, b1, w2, b2, seed_f, activation, dropout_p,
                   block_t, block_f, interpret):
    seed = lax.bitcast_convert_type(seed_f, jnp.int32)
    out = _ffn_forward(x, w1, b1, w2, b2, seed, activation=activation,
                       dropout_p=dropout_p, block_t=block_t,
                       block_f=block_f, interpret=interpret)
    return out, (x, w1, b1, w2, b2, seed)


def _fused_ffn_bwd(activation, dropout_p, block_t, block_f, interpret,
                   res, g):
    x, w1, b1, w2, b2, seed = res
    dx, dw1, db1, dw2, db2 = _ffn_backward(
        x, w1, b1, w2, b2, seed, g, activation=activation,
        dropout_p=dropout_p, block_t=block_t, block_f=block_f,
        interpret=interpret)
    return dx, dw1, db1, dw2, db2, jnp.zeros((1,), jnp.float32)


_fused_ffn.defvjp(_fused_ffn_fwd, _fused_ffn_bwd)


# -- public API + dispatcher --------------------------------------------------

_PROBE_CACHE = {}
# OPT-IN since the 2026-07-31 on-chip A/B (artifacts/FFN_AB_r19.md;
# before PR 1, not measured since):
# the AOT byte model said the kernel saves 15.5 GB/step, but measured
# v5e steps are 120.9 ms on the XLA FFN path vs 136.6 ms with the
# kernel — the in-kernel backward recompute costs more wall time than
# the HBM traffic it saves (profile: ~2 ms x 12 layers in
# ffn_backward pallas calls).  Enable via PADDLE_TPU_FUSED_FFN=1 or
# enable_fused_ffn() for memory-limited configs where VMEM-resident
# d_ff intermediates matter more than step time.
_FFN_DISABLED = (
    None if os.environ.get("PADDLE_TPU_FUSED_FFN") == "1"
    else "opt-in (on-chip A/B 2026-07-31: XLA FFN path faster)")


def disable_fused_ffn(reason):
    global _FFN_DISABLED
    _FFN_DISABLED = reason


def enable_fused_ffn():
    global _FFN_DISABLED
    _FFN_DISABLED = None


def _ffn_ok(T, H, F, dtype, activation, dropout_p, block_t, block_f):
    """Compile-probe the kernels once per configuration (the attention
    kernel's discipline: a Mosaic rejection must degrade to the XLA
    path, never kill the surrounding jit)."""
    if _FFN_DISABLED is not None:
        return False
    key = (T, H, F, jnp.dtype(dtype).name, activation, dropout_p,
           block_t, block_f)
    if key in _PROBE_CACHE:
        return _PROBE_CACHE[key]

    def compile_probe():
        sds = jax.ShapeDtypeStruct
        x = sds((T, H), dtype)
        w1, b1 = sds((H, F), dtype), sds((F,), dtype)
        w2, b2 = sds((F, H), dtype), sds((H,), dtype)
        seed = sds((1,), jnp.int32)
        g = sds((T, H), dtype)
        jax.jit(functools.partial(
            _ffn_forward, activation=activation, dropout_p=dropout_p,
            block_t=block_t, block_f=block_f)) \
            .lower(x, w1, b1, w2, b2, seed).compile()
        jax.jit(functools.partial(
            _ffn_backward, activation=activation, dropout_p=dropout_p,
            block_t=block_t, block_f=block_f)) \
            .lower(x, w1, b1, w2, b2, seed, g).compile()
        return True

    # silent per rung — the CALLER warns once if the whole ladder
    # exhausts, so a successful smaller rung never logs a misleading
    # "falling back" message
    try:
        _PROBE_CACHE[key] = bool(compile_probe())
        _PROBE_CACHE[(key, "err")] = None
    except Exception as e:  # noqa: BLE001 - degrade to XLA
        _PROBE_CACHE[key] = False
        _PROBE_CACHE[(key, "err")] = f"{type(e).__name__}: {e}"
    return _PROBE_CACHE[key]


def fused_ffn(x, w1, b1, w2, b2, activation="gelu", dropout_p=0.0,
              dropout_seed=None, interpret=False):
    """dropout(act(x @ w1 + b1), p) @ w2 + b2 with d_ff kept in VMEM.

    x: (..., H); w1 (H, F); w2 (F, H).  Returns (..., H).  Falls back
    to plain XLA ops when the kernel is unavailable for the shape/
    backend (tokens or d_ff not tileable, non-TPU without interpret).
    """
    lead = x.shape[:-1]
    H = x.shape[-1]
    F = w1.shape[1]
    T = 1
    for d in lead:
        T *= d
    xt = x.reshape(T, H)

    # block ladder: prefer big tiles (fewer grid steps, better MXU
    # shapes); if Mosaic rejects a rung (VMEM pressure at large
    # d_model), probe the next before giving up the kernel.  Three
    # rungs bound the worst-case probe cost for shapes that can never
    # compile.
    bt0 = min(512, round_up(T, 128))
    bf0 = min(512, round_up(F, 128))
    ladder = list(dict.fromkeys(
        (bt, bf) for bt, bf in
        [(bt0, bf0), (min(bt0, 256), bf0), (min(bt0, 256),
                                            min(bf0, 256))]
        if T % bt == 0 and F % bf == 0))
    # tuned kernel choice (docs/autotune.md): the thread-local tune
    # scope pins this dispatch to one arm of the re-armed FFN A/B —
    # "xla" forces the fallback path, "pallas" overrides the opt-in
    # default (the 2026-07-31 on-chip verdict) but still requires a
    # TPU backend plus a passing Mosaic probe, or interpret mode.
    # None = untuned: the existing dispatch, byte-identical.
    try:
        from ... import tune as _tune

        _choice = _tune.kernel_choice("ffn")
    except Exception:  # noqa: BLE001 - tune unavailable (minimal env)
        _choice = None
    block_t = block_f = None
    if _choice != "xla" and H % 128 == 0 and ladder:
        if interpret:
            block_t, block_f = ladder[0]
        elif (_FFN_DISABLED is None or _choice == "pallas") \
                and jax.default_backend() == "tpu":
            for bt, bf in ladder:
                if _ffn_ok(T, H, F, x.dtype, activation, dropout_p,
                           bt, bf):
                    block_t, block_f = bt, bf
                    break
            if block_t is None:
                import warnings

                last_key = (T, H, F, jnp.dtype(x.dtype).name,
                            activation, dropout_p) + ladder[-1]
                warnings.warn(
                    "fused FFN kernel unavailable for this shape "
                    f"(last rung: {_PROBE_CACHE.get((last_key, 'err'))})"
                    "; falling back to XLA ops", RuntimeWarning,
                    stacklevel=2)
    usable = block_t is not None
    try:
        from ...profiler import stat_add

        # trace-time only (inside a jit trace, never per step): the
        # A/B arm that actually dispatched, assertable from counters
        stat_add("ffn_dispatch_kernel" if usable else "ffn_dispatch_xla")
    except Exception:  # noqa: BLE001 - profiler unavailable (minimal env)
        pass
    if not usable:
        h = _act(jnp.dot(xt, w1, preferred_element_type=jnp.float32)
                 .astype(x.dtype) + b1, activation)
        if dropout_p > 0.0:
            seed = (dropout_seed if dropout_seed is not None
                    else jnp.zeros((1,), jnp.int32))
            keep = _ffn_keep(seed.reshape(()), 0, 0, T, F, dropout_p)
            h = jnp.where(keep, h / (1.0 - dropout_p),
                          jnp.zeros_like(h))
        out = jnp.dot(h, w2, preferred_element_type=jnp.float32) \
            .astype(x.dtype) + b2
        return out.reshape(lead + (H,))

    seed = (dropout_seed if dropout_seed is not None
            else jnp.zeros((1,), jnp.int32))
    seed_f = lax.bitcast_convert_type(seed.astype(jnp.int32)
                                      .reshape(1), jnp.float32)
    out = _fused_ffn(xt, w1, b1, w2, b2, seed_f, activation, dropout_p,
                     block_t, block_f, interpret)
    return out.reshape(lead + (H,))
