"""The train-steps loop: what a training script does around a step.

A traffic file names its loop module (`"loop": "loop"`); this is the
one for training cells.  A loop module has `warm_up(system, traffic)`
and `run(system, traffic, spans, seconds=None, chunks=None)`;
an open-loop serving generator would be a sibling of this file.

The loop cycles a pool of host batches that the configuration's
builder drew from `--seed` during set-up (no step sees its
predecessor's batch, and the host-to-device copy is real), dispatches
steps ahead without waiting, and every `fetch_every`-th step fetches
that step's loss to the host, as a training script logs.  The fetch
closes a *chunk*.

The window starts at a sync (the device idle, the clock read) and ends
when the last chunk's fetch returns, so every step counted has
finished inside it.  It lasts at least `seconds`, and its own length,
not the request, is the denominator.  With `chunks` the loop runs that
many chunks, for the short traced window.
"""

from __future__ import annotations

import dataclasses
import math
import time


@dataclasses.dataclass
class Window:
    steps: int
    start_ns: int
    end_ns: int
    chunk_s: list          # seconds of each chunk of `fetch_every` steps
    losses: list           # the fetched loss closing each chunk
    fetch_every: int

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def failed_steps(self) -> int:
        return sum(not math.isfinite(v) for v in self.losses)


def warm_up(system, traffic: dict) -> list:
    """`warmup_steps` steps, each fetched: the first returns the loss
    of the untrained weights, the rest settle allocator and caches.
    Part of set-up."""
    losses = []
    for i in range(traffic["warmup_steps"]):
        losses.append(system.fetch(system.step(
            system.pool[i % len(system.pool)])))
    return losses


def run(system, traffic: dict, spans, seconds=None, chunks=None) -> Window:
    if seconds is None and chunks is None:
        raise ValueError("loop.run needs seconds or chunks to end on")
    fetch_every = traffic["fetch_every"]
    pool = system.pool
    chunk_s, losses = [], []
    steps = 0
    system.sync()
    start_ns = chunk_start = time.perf_counter_ns()
    while True:
        with spans.span("bench.next_batch"):
            batch = pool[steps % len(pool)]
        handle = system.step(batch)
        steps += 1
        if steps % fetch_every:
            continue
        with spans.span("bench.fetch"):
            losses.append(system.fetch(handle))
        now = time.perf_counter_ns()
        chunk_s.append((now - chunk_start) / 1e9)
        chunk_start = now
        if chunks is not None and len(chunk_s) >= chunks:
            break
        if seconds is not None and now - start_ns >= seconds * 1e9:
            break
    return Window(steps, start_ns, chunk_start, chunk_s, losses,
                  fetch_every)
