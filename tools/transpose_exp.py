#!/usr/bin/env python
"""On-chip experiments for the attention layout-transpose cost.

The v5e-compiled bench step materializes 36 copies/step of
bf16[32,512,768] into a {1,2,0} layout (the per-layer (B,S,H,D) ->
(BH,S,D) head-split transposes feeding the flash kernels); the trace
bills ~9 ms/step of `copy` + 2.5 ms `copy-done` — ~200 GB/s effective,
a quarter of HBM bandwidth.  Experiments:

  1. baseline: time jnp.transpose((0,2,1,3)) + reshape at bench shape
  2. two-step: (B,S,HD) -> swap(1,2) -> (B,H,D,S) -> swap(-1,-2), i.e.
     two clean minor-dim 2D transposes (MXU/fast path candidates)
  3. fused chain: transpose inside a dot-consuming jit (does XLA sink
     it into the consumer?)

Each timed over an unrolled in-jit loop (one dispatch carries N
transposes) and synced by materializing a scalar with float().

Usage: python tools/transpose_exp.py   (needs the chip; one process)
"""

import json
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    assert jax.default_backend() == "tpu", "needs the TPU"
    B, S, H, D = 32, 512, 12, 64
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(B, S, H * D) * 0.1, jnp.bfloat16)
    N = 24  # transposes per dispatch: ~12 layers x 2 (fwd+out)

    def timed(f, *args):
        g = jax.jit(f)
        val = g(*args)
        float(jnp.sum(val.astype(jnp.float32)[0]))
        best = 1e9
        for _ in range(3):
            t0 = time.perf_counter()
            v = g(*args)
            float(jnp.sum(v.astype(jnp.float32)[0]))
            best = min(best, time.perf_counter() - t0)
        return best * 1e3

    # 1. the merge flash_attention does today, chained N times with a
    # +1 to defeat CSE; result folded back so shapes close the loop
    def direct(x):
        acc = x
        for _ in range(N):
            t = acc.reshape(B, S, H, D).transpose(0, 2, 1, 3) \
                .reshape(B * H, S, D)
            acc = t.reshape(B, H, S, D).transpose(0, 2, 1, 3) \
                .reshape(B, S, H * D) + jnp.bfloat16(1)
        return acc

    # 2. two clean 2D transposes per direction
    def twostep(x):
        acc = x
        for _ in range(N):
            t = jnp.swapaxes(acc, 1, 2)          # (B, HD, S)
            t = t.reshape(B, H, D, S)
            t = jnp.swapaxes(t, 2, 3)            # (B, H, S, D)
            t = t.reshape(B * H, S, D)
            u = t.reshape(B, H, S, D)
            u = jnp.swapaxes(u, 2, 3).reshape(B, H * D, S)
            acc = jnp.swapaxes(u, 1, 2) + jnp.bfloat16(1)
        return acc

    res = {"direct_ms": timed(direct, x), "twostep_ms": timed(twostep, x),
           "n_roundtrips": N,
           "bytes_per_roundtrip_GB": 2 * x.size * 2 / 1e9}
    res["direct_us_per_transpose"] = res["direct_ms"] * 1e3 / (2 * N)
    res["twostep_us_per_transpose"] = res["twostep_ms"] * 1e3 / (2 * N)
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
