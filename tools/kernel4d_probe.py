#!/usr/bin/env python
"""Feasibility probe for a 4D-input (no-transpose) flash attention.

The bench step pays ~11.6 ms/step in (B,S,H,D)->(BH,S,D) layout copies
feeding the flash kernels (ROADMAP Speed 6; builders' chip trace of
2026-07-31, to be reproduced).  A kernel whose
BlockSpec reads the projection output layout directly — block
(1, block_q, H, D) with FULL trailing (H, D) dims (legal: equal to the
array dims) — would eliminate them, at the price of per-head slicing
(sublane relayouts) inside the kernel.

This probe answers, cheaply, in order:
  1. does Mosaic COMPILE a kernel that slices q_ref[0, :, h, :] per
     (static) head and matmuls per head?   [compile probe on TPU]
  2. what does it cost vs the same math on pre-merged (BH,S,D) input?
     [timed A/B on TPU, amortized via in-jit unroll]
On CPU it runs step 0: interpret-mode numeric validation.

Usage: python tools/kernel4d_probe.py          # auto: CPU->validate,
                                               # TPU->compile+time
"""

import json
import sys
import time

import numpy as np


def build(B, S, H, D, block_q, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = 1.0 / (D ** 0.5)

    def kernel(q_ref, k_ref, v_ref, o_ref):
        # q_ref: (1, block_q, H, D); k/v_ref: (1, S, H, D) full-seq
        # blocks; o_ref: (1, block_q, H, D).  Per-head flash-free
        # attention (one k block = whole S, softmax in one shot) —
        # enough to price the per-head slicing; the real kernel would
        # keep the online-softmax recurrence.
        for h in range(H):
            q = q_ref[0, :, h, :]            # (block_q, D) sublane slice
            k = k_ref[0, :, h, :]            # (S, D)
            v = v_ref[0, :, h, :]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            o = jax.lax.dot_general(
                (p / l).astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[0, :, h, :] = o.astype(o_ref.dtype)

    def run(q4, k4, v4):
        return pl.pallas_call(
            kernel,
            grid=(B, S // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, H, D), lambda b, i: (b, i, 0, 0)),
                pl.BlockSpec((1, S, H, D), lambda b, i: (b, 0, 0, 0)),
                pl.BlockSpec((1, S, H, D), lambda b, i: (b, 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, H, D),
                                   lambda b, i: (b, i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, S, H, D), q4.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(q4, k4, v4)

    return run


def build_fold3d(B, S, H, D, block_q, interpret):
    """Variant: operands in the NATURAL projection layout (B, S, H*D)
    — no sublane/lane padding inflation (H*D=768 is lane-aligned),
    per-head slices taken on the lane dim at h*D offsets (D=64 is a
    half-tile offset; whether Mosaic relayouts cheaply is exactly what
    this probe prices)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = 1.0 / (D ** 0.5)

    def kernel(q_ref, k_ref, v_ref, o_ref):
        for h in range(H):
            sl = slice(h * D, (h + 1) * D)
            q = q_ref[0, :, sl]              # (block_q, D) lane slice
            k = k_ref[0, :, sl]              # (S, D)
            v = v_ref[0, :, sl]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            m = jnp.max(s, axis=1, keepdims=True)
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            o = jax.lax.dot_general(
                (p / l).astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            o_ref[0, :, sl] = o.astype(o_ref.dtype)

    def run(q3, k3, v3):
        return pl.pallas_call(
            kernel,
            grid=(B, S // block_q),
            in_specs=[
                pl.BlockSpec((1, block_q, H * D),
                             lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, S, H * D), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, S, H * D), lambda b, i: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_q, H * D),
                                   lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct((B, S, H * D), q3.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=interpret,
        )(q3, k3, v3)

    return run


def reference(q4, k4, v4):
    import jax
    import jax.numpy as jnp

    scale = 1.0 / (q4.shape[-1] ** 0.5)
    s = jnp.einsum("bqhd,bkhd->bhqk", q4, k4,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v4.dtype), v4)


def main():
    import jax
    import jax.numpy as jnp

    B, S, H, D = 8, 512, 12, 64
    r = np.random.RandomState(0)
    mk = lambda: jnp.asarray(r.randn(B, S, H, D) * 0.3, jnp.bfloat16)
    q4, k4, v4 = mk(), mk(), mk()
    on_tpu = jax.default_backend() == "tpu"

    if not on_tpu:
        run = build(B, S, H, D, 512, interpret=True)
        out = run(q4, k4, v4)
        ref = reference(q4, k4, v4)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - ref.astype(jnp.float32))))
        fold = build_fold3d(B, S, H, D, 512, interpret=True)
        to3 = lambda x: x.reshape(B, S, H * D)
        out3 = fold(to3(q4), to3(k4), to3(v4)) \
            .reshape(B, S, H, D)
        err3 = float(jnp.max(jnp.abs(out3.astype(jnp.float32)
                                     - ref.astype(jnp.float32))))
        print(json.dumps({"mode": "cpu-interpret", "max_err_4d": err,
                          "max_err_fold3d": err3,
                          "ok": err < 0.05 and err3 < 0.05}))
        return 0 if (err < 0.05 and err3 < 0.05) else 1

    # compile/run status and numeric error are SEPARATE answers: a
    # kernel that compiles but is wrong is a different diagnosis from
    # a Mosaic rejection, and the error magnitude matters either way
    run = build(B, S, H, D, 512, interpret=False)
    fold = build_fold3d(B, S, H, D, 512, interpret=False)
    to3 = lambda x: x.reshape(B, S, H * D)
    try:
        ref = reference(q4, k4, v4).astype(jnp.float32)
        ref.block_until_ready()
    except Exception as e:  # noqa: BLE001 - keep the JSON contract
        print(json.dumps({"mode": "tpu", "reference_failed":
                          f"{type(e).__name__}: {str(e)[:300]}"}))
        return 1
    compiles, errs = {}, {}

    def attempt(key, f, reshape=None):
        # compile/run status FIRST, numeric check in its own try: a
        # post-run comparison failure must not masquerade as Mosaic
        # rejecting the kernel
        try:
            o = f()
            o.block_until_ready()
        except Exception as e:  # noqa: BLE001
            compiles[key] = f"{type(e).__name__}: {str(e)[:200]}"
            return
        compiles[key] = True
        try:
            o = o.reshape(B, S, H, D) if reshape else o
            errs[key] = float(jnp.max(jnp.abs(
                o.astype(jnp.float32) - ref)))
        except Exception as e:  # noqa: BLE001
            errs[key] = f"check failed: {type(e).__name__}: " \
                f"{str(e)[:160]}"

    attempt("4d", lambda: run(q4, k4, v4))
    attempt("fold3d", lambda: fold(to3(q4), to3(k4), to3(v4)),
            reshape=True)
    usable = {k for k, v in compiles.items()
              if v is True and isinstance(errs.get(k), float)
              and errs[k] < 0.05}
    if not usable:
        print(json.dumps({"mode": "tpu", "compiles": compiles,
                          "max_err": errs}))
        return 1

    # A/B: same math on pre-merged (BH, S, D) input, 2D per-bh grid —
    # prices ONLY the 4D slicing overhead, both sides unrolled N deep
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    scale = 1.0 / (D ** 0.5)

    def kernel3(q_ref, k_ref, v_ref, o_ref):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=1, keepdims=True)
        o_ref[0] = jax.lax.dot_general(
            (p / l).astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    def run3(qm, km, vm):
        BH = B * H
        return pl.pallas_call(
            kernel3,
            grid=(BH, 1),
            in_specs=[pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0))] * 3,
            out_specs=pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, S, D), qm.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
        )(qm, km, vm)

    N = 8

    def chain4(q4, k4, v4):
        # same per-iteration k/v perturbation as chain3 so both arms
        # carry identical non-kernel work
        acc = q4
        eps = jnp.bfloat16(1e-8)
        for _ in range(N):
            acc = run(acc, k4 + acc * eps, v4 + acc * eps)
        return acc

    def chain3(q4, k4, v4):
        # INCLUDES the merge transposes PER CALL — the real bench pays
        # them per layer (q, k, v in; out back), so each iteration
        # re-merges from the 4D layout.  k/v are perturbed by the
        # running value so XLA cannot hoist their merges out of the
        # unrolled loop as loop-invariant.
        merge = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, D)
        unmerge = lambda x: x.reshape(B, H, S, D).transpose(0, 2, 1, 3)
        acc = q4
        eps = jnp.bfloat16(1e-8)
        for _ in range(N):
            out = run3(merge(acc), merge(k4 + acc * eps),
                       merge(v4 + acc * eps))
            acc = unmerge(out)
        return acc

    def timed(f):
        g = jax.jit(f)
        v = g(q4, k4, v4)
        float(jnp.sum(v.astype(jnp.float32)[0, 0]))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            v = g(q4, k4, v4)
            float(jnp.sum(v.astype(jnp.float32)[0, 0]))
            best = min(best, time.perf_counter() - t0)
        return best * 1e3 / N

    def chain_fold(q4, k4, v4):
        # the natural-layout kernel: no reshapes at all between calls
        q3, k3, v3 = to3(q4), to3(k4), to3(v4)
        acc = q3
        eps = jnp.bfloat16(1e-8)
        for _ in range(N):
            acc = fold(acc, k3 + acc * eps, v3 + acc * eps)
        return acc

    out = {"mode": "tpu", "compiles": compiles, "max_err": errs,
           "per_call_ms_merged_incl_transpose": timed(chain3),
           "B": B, "S": S, "H": H, "D": D, "unroll": N}
    if "4d" in usable:
        out["per_call_ms_4d"] = timed(chain4)
    if "fold3d" in usable:
        out["per_call_ms_fold3d"] = timed(chain_fold)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
