"""Bucketed compile cache: a fixed set of padded batch shapes.

The serving hot path must never trace/compile inline: XLA compilation
takes seconds while a request deadline is milliseconds.  So the batch
dimension is snapped onto a small ladder of buckets (powers of two by
default), every request batch is padded up to its bucket (edge
replication — numerically inert for inference), and each (bucket,
input-signature) pair is compiled EXACTLY once into an ahead-of-time
executable held in the shared `CompileCache`
(paddle_tpu/fluid/compile_cache.py — the same LRU class behind
`Executor._cache` and `CompiledProgram._cache`).

A new signature therefore costs one compile, performed OFF the dispatch
loop (serving/engine.py parks the batch with the compiler thread); a
seen signature is a dictionary hit + one padded dispatch.  Batches
larger than the top bucket are served by chunking through it, so the
compiled-entry count stays <= len(buckets) per signature no matter the
offered load.
"""

from __future__ import annotations

import threading
import warnings
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..fluid.compile_cache import CompileCache

TRACE_STAT = "serving_trace_count"


def bucket_ladder(max_batch: int, min_bucket: int = 8) -> List[int]:
    """Power-of-two ladder covering [1, max_batch]: [8, 16, ..].

    The smallest bucket is `min_bucket` so single-request traffic maps
    onto ONE entry (batch 1..8 all pad to 8) instead of eight."""
    max_batch = max(1, int(max_batch))
    b = max(1, int(min_bucket))
    ladder = [min(b, max_batch)]
    while ladder[-1] < max_batch:
        b *= 2
        ladder.append(min(b, max_batch))
    return ladder


def bucket_for(n: int, buckets: Sequence[int]) -> Optional[int]:
    """Smallest bucket >= n, or None (caller chunks through max)."""
    for b in buckets:
        if b >= n:
            return b
    return None


def _is_jax_array(a) -> bool:
    return isinstance(a, jax.Array)


def pad_batch(a, n: int):
    """Pad the leading dim of `a` up to `n` rows by edge replication.

    Edge replication (repeat the last real row) keeps padded rows
    inside the model's numeric envelope — zeros can hit log(0)/div-0
    branches in real models.  Works on host numpy and on device arrays
    (jnp path, async, no transfer)."""
    rows = a.shape[0]
    if rows == n:
        return a
    if rows > n:
        raise ValueError(f"pad_batch: {rows} rows > bucket {n}")
    if _is_jax_array(a):
        import jax.numpy as jnp

        fill = jnp.broadcast_to(a[-1:], (n - rows,) + a.shape[1:])
        return jnp.concatenate([a, fill], axis=0)
    fill = np.broadcast_to(a[-1:], (n - rows,) + a.shape[1:])
    return np.concatenate([a, fill], axis=0)


def input_signature(inputs: Sequence[Any]) -> Tuple:
    """Per-request shape identity: trailing dims + dtype of each input
    (the batch dim is the bucket's business, not the signature's)."""
    return tuple((tuple(a.shape[1:]), str(np.dtype(a.dtype)))
                 for a in inputs)


class BucketedRunner:
    """Pads/buckets the leading batch dim of a traceable fn into a
    fixed set of AOT-compiled entries.

    fn(*inputs) -> output array / list of output arrays, traceable by
    jax (a jitted model step, `Exported.call`, a functionalized
    nn.Layer forward).  Outputs whose leading dim equals the padded
    batch are sliced back to the real row count (device-side, lazy).

    `donate=True` donates the input buffers to XLA (the inference
    `enable_memory_optim` mapping): activations may reuse the feed
    buffers in HBM.  `bucketed=False` disables padding (exact-shape
    compiles — the inference `switch_ir_optim(False)` mapping)."""

    CACHE_CAPACITY = 32

    def __init__(self, fn: Callable, buckets: Sequence[int],
                 donate: bool = False, bucketed: bool = True,
                 cache: Optional[CompileCache] = None,
                 max_rows_per_call: Optional[int] = None,
                 aot_token: Optional[str] = None):
        if not buckets:
            raise ValueError("BucketedRunner needs >= 1 bucket")
        self._fn = fn
        self.buckets = sorted(set(int(b) for b in buckets))
        self.donate = bool(donate)
        self.bucketed = bool(bucketed)
        self._cache = cache if cache is not None else CompileCache(
            self.CACHE_CAPACITY, stat_prefix="serving")
        self._compile_lock = threading.Lock()
        # persistent AOT cache opt-in (fluid/aot_cache.py): a stable
        # token naming this model's computation + weights version lets
        # a fresh process load the serialized bucket executables
        # instead of recompiling (ModelRegistry derives it; raw
        # callables must supply their own — a reused token would load
        # another model's executable)
        self.aot_token = aot_token
        # bucket key -> obs ProgramCost gauge (flops from the AOT
        # entry's cost_analysis; run() feeds it dispatch intervals)
        self._costs: dict = {}

    # -- compile management ------------------------------------------------
    def _key(self, bucket: int, sig: Tuple) -> Tuple:
        return (bucket, sig, self.donate)

    def _bucket_of(self, rows: int) -> int:
        if not self.bucketed:
            return rows
        b = bucket_for(rows, self.buckets)
        return b if b is not None else self.buckets[-1]

    def plan(self, inputs: Sequence[Any]) -> Tuple[int, Tuple]:
        """(bucket, signature) the given inputs will run under."""
        return (self._bucket_of(inputs[0].shape[0]),
                input_signature(inputs))

    def is_compiled(self, inputs: Sequence[Any]) -> bool:
        bucket, sig = self.plan(inputs)
        return self._key(bucket, sig) in self._cache

    def ensure_compiled(self, inputs: Sequence[Any]):
        """Compile (AOT) the entry for these inputs if missing — the
        off-path half of the contract: the engine's compiler thread
        calls this with the request parked, the dispatch loop never
        does."""
        bucket, sig = self.plan(inputs)
        return self._entry(bucket, sig, inputs)

    def _entry(self, bucket: int, sig: Tuple, inputs: Sequence[Any]):
        key = self._key(bucket, sig)
        entry = self._cache.get(key)
        if entry is not None:
            return entry
        # one compile at a time: racing threads would compile the same
        # entry twice (correct but wasteful — compiles are seconds)
        with self._compile_lock:
            entry = self._cache.get(key)
            if entry is not None:
                return entry
            from ..fluid import aot_cache
            from ..profiler import stat_add, timed

            stable = aot_cache.runner_stable_key(
                self.aot_token, bucket, sig, self.donate)
            loaded, _meta = aot_cache.try_load(
                stable, label=f"serving.bucket{bucket}")
            if loaded is not None:
                from ..obs import cost as obs_cost

                self._costs[key] = obs_cost.register_program(
                    f"serving.bucket{bucket}",
                    obs_cost.cost_of_compiled(loaded))
                self._cache.put(key, loaded)
                return loaded
            with timed("serving_compile_ms"):
                specs = [
                    jax.ShapeDtypeStruct((bucket,) + tuple(a.shape[1:]),
                                         np.dtype(a.dtype))
                    for a in inputs
                ]
                donate = tuple(range(len(specs))) if self.donate else ()
                jitted = jax.jit(self._list_fn, donate_argnums=donate)
                with warnings.catch_warnings():
                    # see _call: unusable donations are expected for
                    # inference graphs, at compile time too
                    warnings.filterwarnings(
                        "ignore", message=".*donated buffer.*")
                    entry = jitted.lower(*specs).compile()
            aot_cache.try_store(stable, entry,
                                label=f"serving.bucket{bucket}")
            # the entry is already AOT: reading its XLA cost_analysis
            # into the obs gauge registry is free (no extra compile) —
            # serving MFU reports per bucket (docs/observability.md)
            from ..obs import cost as obs_cost

            self._costs[key] = obs_cost.register_program(
                f"serving.bucket{bucket}",
                obs_cost.cost_of_compiled(entry))
            stat_add(TRACE_STAT)
            self._cache.put(key, entry)
            return entry

    def _list_fn(self, *xs):
        out = self._fn(*xs)
        if isinstance(out, (list, tuple)):
            return list(out)
        return [out]

    # -- execution ---------------------------------------------------------
    def run(self, inputs: Sequence[Any]) -> List[Any]:
        """Dispatch `inputs` (shared leading batch dim) through the
        bucketed entry; returns DEVICE arrays sliced to the real row
        count — no device->host transfer (the caller materializes at
        its own sanctioned boundary)."""
        rows = inputs[0].shape[0]
        top = self.buckets[-1]
        if self.bucketed and rows > top:
            return self._run_chunked(inputs, rows, top)
        bucket, sig = self.plan(inputs)
        entry = self._entry(bucket, sig, inputs)
        pc = self._costs.get(self._key(bucket, sig))
        if pc is not None:
            pc.observe_dispatch()
        padded = [pad_batch(a, bucket) for a in inputs]
        outs = self._call(entry, padded)
        return [o[:rows] if hasattr(o, "shape") and o.shape
                and o.shape[0] == bucket else o
                for o in outs]

    def _run_chunked(self, inputs: Sequence[Any], rows: int,
                     top: int) -> List[Any]:
        """rows > max bucket: stream through the top bucket and
        concatenate on device — entry count stays <= len(buckets)."""
        import jax.numpy as jnp

        parts, rows_per = [], []
        for lo in range(0, rows, top):
            hi = min(lo + top, rows)
            rows_per.append(hi - lo)
            parts.append(self.run([a[lo:hi] for a in inputs]))
        outs = []
        for vals in zip(*parts):
            batched = all(
                hasattr(v, "shape") and v.shape and v.shape[0] == r
                for v, r in zip(vals, rows_per))
            outs.append(jnp.concatenate(list(vals), axis=0)
                        if batched else vals[0])
        return outs

    def _call(self, entry, padded):
        if not self.donate:
            return entry(*padded)
        with warnings.catch_warnings():
            # inference outputs rarely alias inputs shape-for-shape;
            # XLA then reports the donation as unusable every call —
            # that is expected here, not a bug to surface per-request
            warnings.filterwarnings(
                "ignore", message=".*donated buffer.*")
            return entry(*padded)

    @property
    def trace_count(self) -> int:
        return len(self._cache)
