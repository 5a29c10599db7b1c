"""Python side of the inference C ABI (core_native/c_api.cc).

The C layer hands raw pointers + shapes across the ABI; this module
turns them into arrays, drives the Predictor, and hands back contiguous
bytes.  It deliberately knows nothing about the C structs — the whole
contract is (address, shape) in, (bytes, shape) out."""

from __future__ import annotations

import ctypes

import numpy as np

from . import Config, Predictor


def new_predictor(prefix: str) -> Predictor:
    return Predictor(Config(prefix))


def run_f32(pred: Predictor, addr: int, shape) -> tuple:
    """One f32 tensor in, one f32 tensor out, zero avoidable copies.

    The C buffer is viewed (not copied — `device_put` inside the
    predictor's bucketed dispatch is the one host read, and it happens
    before this function returns, while the caller's buffer is alive).
    The output rides a LazyFetch handle end to end and materializes
    exactly once, here at the ABI boundary — the same sanctioned-sync
    contract as the training hot path (docs/async_hot_path.md)."""
    n = int(np.prod(shape))
    buf = (ctypes.c_float * n).from_address(int(addr))
    x = np.ctypeslib.as_array(buf).reshape([int(s) for s in shape])
    handle = pred.run_handles([x])[0]
    out = np.ascontiguousarray(
        handle.numpy(), dtype=np.float32)  # sync-ok: ABI boundary
    return out.tobytes(), [int(s) for s in out.shape]
