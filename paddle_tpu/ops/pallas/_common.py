"""Shared helpers for pallas kernels."""

from __future__ import annotations

import contextlib
import functools

import jax


@functools.cache
def default_backend() -> str:
    return jax.default_backend()


# A Sharding over devices of a TPU *topology* (no chip attached), or
# None; set only inside `compile_target`.
_COMPILE_TARGET = None


@contextlib.contextmanager
def compile_target(sharding):
    """Inside the block, kernel dispatch behaves as on a TPU and the
    compile probes lower for `sharding`'s devices — those of a TPU
    topology — so Mosaic accepts or refuses every kernel on a host
    without a chip (tools/aot_analysis.py,
    tests/test_pallas_attention.py).  Tracing and lowering must happen
    inside the block; nothing compiled there can run here."""
    global _COMPILE_TARGET
    prev, _COMPILE_TARGET = _COMPILE_TARGET, sharding
    try:
        yield
    finally:
        _COMPILE_TARGET = prev


def on_tpu() -> bool:
    if _COMPILE_TARGET is not None:
        return True
    return default_backend() == "tpu"


def probe_struct(shape, dtype) -> jax.ShapeDtypeStruct:
    """Abstract operand for a compile probe, placed on the compile
    target when one is set (else the default device)."""
    return jax.ShapeDtypeStruct(shape, dtype, sharding=_COMPILE_TARGET)


def kernel_trace(kernel: str):
    """Decorator for a kernel's entry point (and the rules of its
    `custom_vjp`, which a differentiated program calls later): the
    time inside it while a program is being traced — some operand is
    a tracer — is the start-up phase `setup.kernel_trace` (timer
    `kernel_trace_ms`, attribute `kernel`): building specs and tables,
    the compile probes, the kernel body's trace.  Called on concrete
    arrays it adds one scan of the operands."""
    def wrap(fn):
        @functools.wraps(fn)
        def entry(*args, **kwargs):
            if not any(isinstance(a, jax.core.Tracer)
                       for a in jax.tree_util.tree_leaves((args, kwargs))):
                return fn(*args, **kwargs)
            from ...profiler import stage

            with stage("setup.kernel_trace", "kernel_trace_ms",
                       {"kernel": kernel}):
                return fn(*args, **kwargs)
        return entry
    return wrap


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b
