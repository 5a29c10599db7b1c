"""From a profiler trace to numbers: the one reduction every PR uses.

`jax.profiler` writes `<dir>/plugins/profile/<time>/<host>.xplane.pb`;
`jax.profiler.ProfileData` reads it with nothing but JAX.  A TPU shows
as one plane a chip, `/device:TPU:<n>`, whose line `XLA Ops` holds one
event per executed HLO instruction, named by the instruction's text
(`%fusion.8 = bf16[...] fusion(...)`: the name is what stands before
` = `); the benchmark's spans (`benchmark/lib/spans.py`) are events
called `bench.*` on the lines of `/host:CPU`, on the same clock.

`load` pulls those two kinds of event out of the file, `reduce` turns
them into what the per-layer readers and the `breakdown` need.  Both
are checked against a recorded v5e trace in benchmark/tests.

Definitions:

* window — the `bench.window` host span the harness opens around the
  traced chunks (it starts at a sync, so the device is idle then, and
  ends when the last loss has reached the host).  Without one, the
  extent of the device events.
* busy — per chip, the union of its op intervals clipped to the
  window; `busy_s` is the mean over chips.  Nested events (a `while`
  around its body) count once.
* device ops — op time summed by family: the instruction's name
  without its number (`fusion.1400` -> `fusion.*`), with the calls a
  step makes (`x461`), seconds in the window, mean over chips.
* kernel time — sum of the durations of the events whose instruction
  the caller's map assigns to a kernel, mean over chips.
* collective time — a synchronous collective's event; an asynchronous
  one from the beginning of its `-start` to the end of its `-done`.
  Exposed is the part of that during which no other op ran on that
  chip.  Means over chips.
* idle gaps — the window minus busy, per chip; each gap goes to the
  `bench.*` span (other than the window) that overlaps it most, or to
  "(between spans)"; summed by name, mean over chips.
"""

from __future__ import annotations

import collections
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE = "XLA Ops"
_HOST_PLANE = "/host:CPU"
_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all|collective-broadcast)(-start|-done)?(\.|$)")
_FAMILY = re.compile(r"\.\d+$")     # fusion.1400 -> fusion
_GAP_FLOOR_NS = 1_000      # shorter gaps are the sequencer, not the host


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def instruction_name(event_name: str) -> str:
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(xplane_path: str) -> dict:
    """`{"devices": {plane: [(name, start_ns, end_ns)]}, "spans":
    [(name, start_ns, end_ns)]}` — device op events and the
    benchmark's host spans."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    devices, spans = {}, []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((instruction_name(e.name), e.start_ns,
                                e.start_ns + e.duration_ns))
        elif plane.name == _HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(_SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


# -- interval arithmetic ------------------------------------------------------

def _union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _length(intervals):
    return sum(e - s for s, e in intervals)


def _minus(a, b):
    """Points of `a` not in `b`; both sorted and disjoint."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _collective_intervals(ops):
    """`(collective intervals, the events they are made of)`: start/done
    pairs matched first-in first-out per kind, on one chip."""
    intervals, members, pending = [], set(), collections.defaultdict(list)
    for i, (name, s, e) in sorted(enumerate(ops), key=lambda x: x[1][1]):
        m = _COLLECTIVE.match(name)
        if not m:
            continue
        members.add(i)
        kind, phase = m.group(1), m.group(2)
        if phase == "-start":
            pending[kind].append(s)
        elif phase == "-done" and pending[kind]:
            intervals.append((pending[kind].pop(0), e))
        else:
            intervals.append((s, e))
    return intervals, members


def reduce(events: dict, steps: int, kernel_ops: dict | None = None,
           top: int = 10) -> dict | None:
    """The reduced trace, or None when no operation ran on a device
    (a CPU run: no device metric comes of it)."""
    kernel_ops = kernel_ops or {}
    devices = {k: v for k, v in events["devices"].items() if v}
    if not devices:
        return None
    spans = events["spans"]
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if windows:
        lo, hi = windows[-1][1], windows[-1][2]
    else:
        lo = min(s for ops in devices.values() for _, s, _ in ops)
        hi = max(e for ops in devices.values() for _, _, e in ops)
    inner = [s for s in spans if s[0] != WINDOW_SPAN]
    n = len(devices)

    busy_ns = coll_ns = exposed_ns = 0
    by_op, calls = collections.Counter(), collections.Counter()
    kernel_ns = collections.Counter()
    gaps = collections.Counter()
    for ops in devices.values():
        ops = [(name, max(s, lo), min(e, hi)) for name, s, e in ops
               if e > lo and s < hi]
        busy = _union((s, e) for _, s, e in ops)
        busy_ns += _length(busy)
        for name, s, e in ops:
            by_op[_FAMILY.sub("", name)] += e - s
            calls[_FAMILY.sub("", name)] += 1
            if name in kernel_ops:
                kernel_ns[kernel_ops[name]] += e - s
        coll, members = _collective_intervals(ops)
        coll = _union(coll)
        others = _union((s, e) for i, (_, s, e) in enumerate(ops)
                        if i not in members)
        coll_ns += _length(coll)
        exposed_ns += _length(_minus(coll, others))
        for s, e in _minus([(lo, hi)], busy):
            if e - s >= _GAP_FLOOR_NS:
                gaps[_span_over(inner, s, e)] += e - s

    return {
        "chips": n,
        "steps": steps,
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "kernel_s": {k: v / n / 1e9 for k, v in kernel_ns.items()},
        "collective_s": coll_ns / n / 1e9,
        "collective_exposed_s": exposed_ns / n / 1e9,
        "device_ops": [
            [f"{name}.* x{round(calls[name] / n / steps)}", ns / n / 1e9]
            for name, ns in by_op.most_common(top)],
        "idle_gaps": [[name, ns / n / 1e9]
                      for name, ns in gaps.most_common(top)],
    }


def _span_over(spans, s, e) -> str:
    best, best_ns = "(between spans)", 0
    for name, a, b in spans:
        if a >= e:
            break
        overlap = min(b, e) - max(a, s)
        if overlap > best_ns:
            best, best_ns = name, overlap
    return best
