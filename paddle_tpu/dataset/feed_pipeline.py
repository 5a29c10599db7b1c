"""Pod-scale input pipeline: per-host sharded feed prefetch with a
device-resident double-buffer ring (docs/async_hot_path.md, "Multi-host
feed").

The single-host async hot path (ISSUE 1) overlaps feed `device_put`
with compute through `_FeedPrefetcher`'s one background thread and a
bounded host queue.  On a multi-process pod slice that design has two
gaps the TensorFlow paper (1605.08695) calls out for input pipelines at
scale: every host re-parses the FULL dataset (the parser pool is not
sharded), and the staged-batch queue holds host arrays, so the
host->device upload of batch N+1 only starts when the consumer asks
for it.

This module closes both:

* **Per-host sharding** (`shard_plan` / `epoch_order`): each jax
  process receives a disjoint, exhaustive shard of the dataset's
  files (records when there are fewer files than hosts), keyed off
  `jax.process_index()` / `process_count()`.  The shard is a strided
  slice of a seeded permutation, so it stays disjoint+exhaustive for
  ANY (n, count) — including counts that do not divide the dataset —
  and the permutation is re-drawn deterministically per epoch
  (same seed+epoch on every host), so hosts cycle through different
  parts of the data across epochs without ever overlapping within one.

* **Device-resident double-buffer ring** (`DeviceRing`): a depth-K
  ring of staged batches per host (`PADDLE_PREFETCH_DEPTH`, default 2
  = classic double buffering).  The producer thread parses, normalizes
  and `jax.device_put`s batch N+1..N+K while steps N-k..N are in
  flight, then BLOCKS when the ring is full — backpressure bounds host
  and device memory at K staged batches instead of growing an
  unbounded host queue.  Consumed slots drop their reference so XLA
  frees the buffer once the consuming step retires (feeds are program
  inputs, never donated, so a slot cannot alias live state).

* **Overlap accounting**: `ring_occupancy`/`ring_occupancy_max`
  gauges, `parser_wait_ms` (producer waiting on the parser pool),
  `ring_full_wait_ms` (producer backpressured = device is the
  bottleneck), `ring_empty_wait_ms` (consumer starved = feed is the
  bottleneck) and the per-epoch `shard_skew_ms` gauge make a stall
  attributable from the counters alone — `attribute_stall()` is the
  canonical classification.

Everything here is on the executor hot path and therefore on the
`hot-path-sync` lint watchlist: no `np.asarray`/`.numpy()`/
`block_until_ready` outside sanctioned boundaries.
"""

from __future__ import annotations

import collections
import os
import random
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

DEFAULT_PREFETCH_DEPTH = int(os.environ.get("PADDLE_PREFETCH_DEPTH", "2"))


# ---------------------------------------------------------------------------
# host topology + shard math (pure functions — the disjoint/exhaustive
# contract is tested over mocked (index, count) combos)
# ---------------------------------------------------------------------------

def host_topology(process_index: Optional[int] = None,
                  process_count: Optional[int] = None) -> Tuple[int, int]:
    """(index, count) for this host.  Explicit args win (mocked pods in
    tests); otherwise the live jax runtime; otherwise the PADDLE_* env
    contract; otherwise a single host."""
    if process_index is not None and process_count is not None:
        return int(process_index), max(1, int(process_count))
    from ..distributed.parallel import (_safe_process_count,
                                        _safe_process_index)

    index = int(process_index) if process_index is not None \
        else _safe_process_index()
    count = int(process_count) if process_count is not None \
        else _safe_process_count()
    return index, max(1, count)


def epoch_order(n: int, seed: int, epoch: int) -> List[int]:
    """Deterministic permutation of range(n) for one epoch — identical
    on every host (the seed and epoch counter are shared), so strided
    shard slices stay disjoint pod-wide."""
    order = list(range(n))
    random.Random(f"feed-shard:{int(seed)}:{int(epoch)}").shuffle(order)
    return order


def shard_plan(n_items: int, index: int, count: int, epoch: int = 0,
               seed: int = 0) -> List[int]:
    """Item indices host `index` of `count` owns this epoch.

    Disjoint and exhaustive for ANY (n_items, count): the union over
    all hosts is exactly range(n_items) and no item appears on two
    hosts, including when count does not divide n_items (strided slice
    of one shared permutation) and when count > n_items (some hosts
    own nothing).  With a single host the plan is the identity, so
    single-process behavior is bit-identical to the unsharded path.
    """
    if count <= 1:
        return list(range(n_items))
    if index < 0 or index >= count:
        raise ValueError(f"shard index {index} outside [0, {count})")
    return epoch_order(n_items, seed, epoch)[index::count]


def compute_shard_skew(host_feed_ms: Iterable[float]) -> float:
    """Pod-wide shard skew: max - min of the per-host epoch feed wall
    times.  A large skew means the file shards are imbalanced and the
    slowest host gates every collective step."""
    times = [float(t) for t in host_feed_ms]
    if len(times) < 2:
        return 0.0
    return max(times) - min(times)


def gather_host_feed_ms(local_ms: float,
                        process_count: Optional[int] = None) -> List[float]:
    """All-gather the per-host epoch feed time (one scalar per host, at
    an epoch boundary — off the hot path).  Single-process: [local]."""
    _, count = host_topology(None, process_count)
    if count <= 1:
        return [float(local_ms)]
    try:
        import numpy as np
        from jax.experimental import multihost_utils

        arr = multihost_utils.process_allgather(
            np.float32(local_ms))
        return [float(v) for v in np.asarray(arr).ravel()]  # sync-ok: epoch boundary
    except Exception:  # noqa: BLE001 - skew is observability, not control
        return [float(local_ms)]


def attribute_stall(times: Optional[Dict[str, float]] = None) -> str:
    """Classify where the pipeline's wall time went, from the profiler
    counters alone (the BENCH JSON embeds them, so the attribution is
    reproducible from the artifact):

    - ``compute-bound``  — the producer spent its wait backpressured on
      a full ring: the device is the bottleneck (the healthy state).
    - ``parser-bound``   — the consumer starved on an empty ring and
      the producer's time went to waiting on the parser pool.
    - ``transfer-bound`` — the consumer starved and the producer's time
      went to normalize + `device_put` staging.
    - ``balanced``       — nobody waited measurably.
    """
    if times is None:
        from .. import profiler

        times = profiler.get_time_stats()
    full = float(times.get("ring_full_wait_ms", 0.0))
    empty = float(times.get("ring_empty_wait_ms", 0.0))
    parser = float(times.get("parser_wait_ms", 0.0))
    stage = float(times.get("host_feed_ms", 0.0))
    if full < 1e-6 and empty < 1e-6:
        return "balanced"
    if full >= empty:
        return "compute-bound"
    return "parser-bound" if parser >= stage else "transfer-bound"


# ---------------------------------------------------------------------------
# the device-resident double-buffer ring
# ---------------------------------------------------------------------------

def _staged_nbytes(item) -> int:
    """Device bytes one ring slot pins: the staged feed dict's arrays.
    Sentinels and forwarded exceptions weigh nothing."""
    if not isinstance(item, tuple) or len(item) != 2:
        return 0
    staged = item[0]
    if not isinstance(staged, dict):
        return 0
    total = 0
    for v in staged.values():
        total += int(getattr(v, "nbytes", 0) or 0)
    return total


def _ring_account(delta: int) -> None:
    """Maintain the `feed_ring_bytes` memory-ledger entry
    (obs/memprof.py) incrementally at stage/consume/close."""
    if not delta:
        return
    try:
        from ..obs import memprof

        memprof.add_entry("feed_ring_bytes", delta)
    except Exception:  # noqa: BLE001 - observability, not control
        pass


class DeviceRing:
    """Depth-K ring of staged device batches.

    The producer stages (device_put) into free slots and BLOCKS when
    all K slots hold unconsumed batches — backpressure instead of
    unbounded host queueing; the queue length can never exceed the
    depth.  The consumer pops the oldest staged batch.  Upstream
    exceptions re-raise in the consumer; closing the ring (consumer
    abandoned the epoch) releases a blocked producer.
    """

    _END = object()

    def __init__(self, depth: int):
        self.depth = max(1, int(depth))
        self._slots: collections.deque = collections.deque()
        self._cond = threading.Condition()
        self._closed = False
        self.max_occupancy = 0
        self.total_put = 0

    def __len__(self) -> int:
        with self._cond:
            return len(self._slots)

    def put(self, staged) -> bool:
        """Stage one batch; blocks while the ring is full (the
        backpressure boundary — accounted as `ring_full_wait_ms`).
        Returns False when the ring was closed under us."""
        from .. import profiler

        with self._cond:
            if len(self._slots) >= self.depth and not self._closed:
                t0 = time.perf_counter()
                while len(self._slots) >= self.depth and not self._closed:
                    self._cond.wait(timeout=0.1)
                profiler.time_add("ring_full_wait_ms",
                                  (time.perf_counter() - t0) * 1e3)
            if self._closed:
                return False
            self._slots.append(staged)
            _ring_account(_staged_nbytes(staged))
            occ = len(self._slots)
            self.total_put += staged is not self._END
            if occ > self.max_occupancy:
                self.max_occupancy = occ
            profiler.stat_set("ring_occupancy", occ)
            profiler.stat_max("ring_occupancy_max", occ)
            self._cond.notify_all()
            return True

    def put_end(self):
        self.put(self._END)

    def get(self):
        """Pop the oldest staged batch; blocks while the ring is empty
        (consumer starved — accounted as `ring_empty_wait_ms`).
        Returns the _END sentinel at end of epoch."""
        from .. import profiler

        with self._cond:
            if not self._slots and not self._closed:
                t0 = time.perf_counter()
                while not self._slots and not self._closed:
                    self._cond.wait(timeout=0.1)
                profiler.time_add("ring_empty_wait_ms",
                                  (time.perf_counter() - t0) * 1e3)
            if not self._slots:
                return self._END  # closed and drained
            item = self._slots.popleft()
            _ring_account(-_staged_nbytes(item))
            profiler.stat_set("ring_occupancy", len(self._slots))
            self._cond.notify_all()
            return item

    def close(self):
        """Consumer is done (normally or abandoning mid-epoch): unblock
        and drain.  Dropped slots release their device buffers to XLA."""
        with self._cond:
            self._closed = True
            for item in self._slots:
                _ring_account(-_staged_nbytes(item))
            self._slots.clear()
            self._cond.notify_all()


# ---------------------------------------------------------------------------
# the pipeline
# ---------------------------------------------------------------------------

class FeedPipeline:
    """Iterable of device-staged feed dicts: parser pool -> normalize +
    `device_put` (producer thread) -> `DeviceRing` -> consumer.

    `source` is either a `fluid.DatasetBase` — in which case this host
    iterates only its own shard (see `shard_plan`) through the
    dataset's parser worker pool, re-sharded deterministically each
    epoch — or any iterable of host feed dicts (the `_FeedPrefetcher`
    compatibility path; no sharding).

    `stage_fn(feed) -> staged feed` runs on the producer thread; the
    Executor passes its `_normalize_feed`, so staging hits the same
    content-hash device cache and `host_feed_ms` accounting as the
    single-host path.
    """

    def __init__(self, stage_fn: Callable[[Any], Any], source,
                 depth: Optional[int] = None,
                 process_index: Optional[int] = None,
                 process_count: Optional[int] = None,
                 epoch: Optional[int] = None,
                 skip_batches: int = 0,
                 mesh=None):
        from .. import profiler

        self._stage = stage_fn
        # SPMD mesh (docs/spmd.md): when the program compiles under a
        # named-axis mesh, staged batches are placed under
        # NamedSharding(P("data"[, "fsdp"])) on the producer thread so
        # dispatch never reshards.  None (plain Executor path) keeps
        # staging byte-identical to before.
        self._mesh = mesh
        self._depth = DEFAULT_PREFETCH_DEPTH if depth is None \
            else max(1, int(depth))
        # deterministic mid-epoch resume (paddle_tpu.ckpt,
        # docs/fault_tolerance.md): the first `skip_batches` batches of
        # the epoch were consumed before the checkpoint — discard them
        # on the producer thread BEFORE staging, so a resumed run
        # replays exactly the remaining data order (the order itself is
        # already deterministic via shard_plan/epoch_order)
        self._skip = max(0, int(skip_batches))
        self._index, self._count = host_topology(process_index,
                                                 process_count)
        self._ring = DeviceRing(self._depth)
        self._batch_iter = self._open_source(source, epoch)
        self.epoch_feed_ms = 0.0
        profiler.stat_set("prefetch_depth", self._depth)
        self._thread = threading.Thread(target=self._produce, daemon=True,
                                        name="feed-producer")
        self._thread.start()

    # -- source handling ---------------------------------------------------
    def _open_source(self, source, epoch: Optional[int]):
        batch_iter = getattr(source, "batch_iter", None)
        if batch_iter is None:
            return iter(source)
        if self._count <= 1 or getattr(source, "_host_sharded", False):
            # single host, or the dataset was already shard-loaded
            # (load_into_memory(shard_by_host=True)) — re-sharding
            # would drop data.  The epoch counter still advances: the
            # checkpoint subsystem keys mid-epoch resume off it
            # (docs/fault_tolerance.md), single- and multi-host alike.
            if epoch is None:
                epoch = getattr(source, "_feed_epoch", -1) + 1
            source._feed_epoch = epoch
            return batch_iter()
        if epoch is None:
            # one pipeline = one pass: auto-advance the dataset's epoch
            # counter so successive train_from_dataset calls re-deal
            # the file shards (call counts match across hosts, so the
            # permutation stays pod-consistent).  An explicit epoch
            # (mocked multi-host tests drain several host views of the
            # SAME epoch in one process) only records itself.
            epoch = getattr(source, "_feed_epoch", -1) + 1
        source._feed_epoch = epoch
        return batch_iter(shard=(self._index, self._count), epoch=epoch)

    def _place_sharded(self, staged):
        """Seat a staged feed dict under the mesh's batch sharding
        (mesh_lib.batch_spec: P("data") composed with "fsdp" when
        present).  device_put under a NamedSharding is an async device
        placement — no host transfer, hot-path safe.  No-op without a
        mesh."""
        mesh = self._mesh
        if mesh is None or not isinstance(staged, dict):
            return staged
        import jax
        from jax.sharding import NamedSharding

        from ..parallel import mesh as mesh_lib

        out = {}
        for n, a in staged.items():
            if getattr(a, "ndim", 0) >= 1:
                spec = mesh_lib.batch_spec(mesh, a.shape[0])
                out[n] = jax.device_put(a, NamedSharding(mesh, spec))
            else:
                out[n] = a
        return out

    # -- producer (background thread; hot path — lint-watched) -------------
    def _produce(self):
        from .. import obs, profiler

        ring = self._ring
        tracer = obs.TRACER
        t_start = time.perf_counter()
        try:
            it = self._batch_iter
            skipped = 0
            while skipped < self._skip:
                try:
                    next(it)  # resume: already-consumed batch, not staged
                except StopIteration:
                    break
                skipped += 1
            if skipped:
                profiler.stat_add("feed_skipped_batches", skipped)
            while True:
                t0 = time.perf_counter()
                try:
                    feed = next(it)
                except StopIteration:
                    break
                profiler.time_add("parser_wait_ms",
                                  (time.perf_counter() - t0) * 1e3)
                # one flow id per batch links the producer's stage span
                # to the consumer's ring_get span across threads
                flow = tracer.new_flow() if tracer.enabled else 0
                with obs.span("feed.stage", flow=flow):
                    staged = self._place_sharded(self._stage(feed))
                if not ring.put((staged, flow)):
                    return  # consumer abandoned the epoch
            self.epoch_feed_ms = (time.perf_counter() - t_start) * 1e3
            ring.put_end()
        except BaseException as e:  # noqa: BLE001 - forward to consumer
            ring.put(e)
        finally:
            close = getattr(self._batch_iter, "close", None)
            if close is not None:
                close()  # release the dataset's parser pool

    # -- consumer ----------------------------------------------------------
    def __iter__(self):
        from .. import obs

        ring = self._ring
        tracer = obs.TRACER
        try:
            while True:
                t0 = time.perf_counter()
                item = ring.get()
                if item is DeviceRing._END:
                    break
                if isinstance(item, BaseException):
                    raise item
                staged, flow = item
                # span covers the ring wait: a long feed.ring_get IS the
                # consumer-starved stall, flow-linked to its producer
                tracer.add_span("feed.ring_get", t0,
                                time.perf_counter() - t0, flow=flow)
                yield staged
        finally:
            ring.close()
            self._finish_epoch()

    def _finish_epoch(self):
        """Epoch boundary (off the hot path): publish the pod-wide
        shard-skew gauge.  Single process: skew 0.  Skipped when the
        consumer abandoned mid-epoch — the gather is a collective and
        abandonment is not synchronized across hosts."""
        from .. import profiler

        if self.epoch_feed_ms <= 0.0:
            return
        skew = compute_shard_skew(
            gather_host_feed_ms(self.epoch_feed_ms, self._count))
        profiler.time_set("shard_skew_ms", skew)
        try:
            # ride the collective boundary every host already reaches:
            # refresh the telemetry endpoint's pod-merged /snapshot view
            from .. import obs

            obs.telemetry_epoch_refresh()
        except Exception:  # noqa: BLE001 - observability, not control
            pass

    # -- observability -----------------------------------------------------
    def feed_report(self) -> Dict[str, Any]:
        """Per-host feed summary for bench/debug output: the pipeline
        counters plus the stall attribution, keyed so a pod run can
        merge one report per host."""
        from .. import profiler

        times = profiler.get_time_stats()
        stats = profiler.get_int_stats()
        return {
            "host": self._index,
            "hosts": self._count,
            "prefetch_depth": self._depth,
            "epoch_feed_ms": round(self.epoch_feed_ms, 3),
            "host_feed_ms": round(times.get("host_feed_ms", 0.0), 3),
            "parser_wait_ms": round(times.get("parser_wait_ms", 0.0), 3),
            "ring_full_wait_ms": round(
                times.get("ring_full_wait_ms", 0.0), 3),
            "ring_empty_wait_ms": round(
                times.get("ring_empty_wait_ms", 0.0), 3),
            "shard_skew_ms": round(times.get("shard_skew_ms", 0.0), 3),
            "ring_occupancy_max": stats.get("ring_occupancy_max", 0),
            "stall_attribution": attribute_stall(times),
        }
