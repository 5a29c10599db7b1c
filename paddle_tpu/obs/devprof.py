"""Measured device time under the program's own names.

`obs.opprof` says what an executable *should* cost; this module says
what it *did* cost on the device, from the profiler's trace.

`device_time` takes a `.xplane.pb` that `jax.profiler` wrote (whoever
opened the trace) and the executables the process holds, and returns
seconds by `(phase, path)`.  A TPU shows as one plane a chip,
`/device:TPU:<n>`; its `XLA Ops` line holds one event per executed HLO
instruction, named by the instruction's text (the name stands before
` = `), its `XLA Modules` line names the running program.  The join to
`opprof`'s `instr_name` map is exact by instruction name, per
executable; the rest lands in the explicit `unattributed` bin, by
instruction family.  The benchmark (`benchmark/lib/scopes.py`) is its
reader; the Executor's stages are in the same trace as `pt.*`
annotations (`profiler.stage`).

Nothing here imports jax at module level.
"""

from __future__ import annotations

import bisect
import collections
import glob as _glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE_RE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
_FAMILY_RE = re.compile(r"\.\d+$")      # fusion.1400 -> fusion


def instruction_of(event_name: str) -> str:
    """The HLO instruction an `XLA Ops` event ran: its text up to ` = `."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest `.xplane.pb` of a `jax.profiler` trace directory."""
    files = sorted(_glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def read_trace(xplane_path: str) -> dict:
    """`{"devices": {plane: {"ops": [(instruction, start_ns, end_ns)],
    "modules": [(program, start_ns, end_ns)]}}, "host": [(name,
    start_ns, end_ns)]}`, all on one clock; `host` holds every event
    of the `/host:CPU` plane."""
    from jax.profiler import ProfileData

    devices: Dict[str, dict] = {}
    host: List[tuple] = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if DEVICE_PLANE_RE.match(plane.name):
            dev = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] += [
                        (instruction_of(e.name), e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] += [
                        (e.name.split("(", 1)[0], e.start_ns,
                         e.start_ns + e.duration_ns) for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    return {"devices": devices, "host": sorted(host, key=lambda s: s[1])}


def _union(intervals) -> List[Tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_ns(ops: List[tuple]) -> List[float]:
    """Each event's own time: its duration less the events nested in it
    (a `while` around its body), so that a sum counts no time twice.
    `ops` sorted by start, the enclosing event first."""
    own = [e - s for _, s, e in ops]
    stack: List[int] = []
    for i, (_, s, e) in enumerate(ops):
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(e, ops[stack[-1]][2]) - s
        stack.append(i)
    return own


def _name_maps(executables) -> List[dict]:
    """`[{"label", "module", "instr_name"}]` from opprof profiles
    (`opprof.profiles()`) and/or objects with `.as_text()`."""
    from . import opprof

    items = (executables.items() if isinstance(executables, dict)
             else enumerate(executables or ()))
    out = []
    for label, exe in items:
        if not isinstance(exe, dict):
            exe = opprof.profile_hlo_text(exe.as_text(), label=str(label))
        if exe.get("instr_name"):
            out.append({"label": label, "module": exe.get("module", ""),
                        "instr_name": exe["instr_name"]})
    return out


def device_time(xplane_path: str, executables,
                window_ns=None) -> Optional[dict]:
    """Device seconds of a chip trace under the program's names.

    `executables`: what the process holds — `opprof.profiles()`
    (`{label: profile}`), and for a bare jitted step any object with
    `.as_text()`, in a dict or a list.  `window_ns`: `(start, end)` on
    the trace's clock, or the name of a host span whose last occurrence
    bounds the window; None takes the extent of the device's events.

    Returns None when no operation ran on a TPU plane (a CPU trace),
    else `{"chips", "window_ns", "op_s", "by_name": {(phase, path):
    seconds}, "unattributed": {instruction family: seconds},
    "programs": {module: label}, "busy": {plane: [(start_ns, end_ns)]},
    "host_spans": {name: [(start_ns, end_ns)]}}`.  Seconds are each
    event's own time clipped to the window, means over chips; `op_s` is
    their sum, so `sum(by_name) + sum(unattributed) == op_s`.
    `host_spans` holds the `pt.*` annotations of the host plane
    (`profiler.stage`)."""
    from ..profiler import ANNOTATION_PREFIX

    trace = read_trace(xplane_path)
    devices = {k: v for k, v in trace["devices"].items() if v["ops"]}
    if not devices:
        return None
    if isinstance(window_ns, str):
        named = [s for s in trace["host"] if s[0] == window_ns]
        window_ns = named[-1][1:] if named else None
    if window_ns is None:
        window_ns = (min(o[1] for d in devices.values() for o in d["ops"]),
                     max(o[2] for d in devices.values() for o in d["ops"]))
    lo, hi = window_ns

    # which program ran each event: the module event that holds it
    per_plane, seen = {}, collections.defaultdict(set)
    for plane, dev in devices.items():
        modules = sorted(dev["modules"], key=lambda m: m[1])
        starts = [m[1] for m in modules]
        ops = sorted((o for o in dev["ops"] if o[2] > lo and o[1] < hi),
                     key=lambda o: (o[1], -o[2]))
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in ops]
        owners = []
        for name, s, _e in ops:
            k = bisect.bisect_right(starts, s) - 1
            module = modules[k][0] if k >= 0 and s < modules[k][2] else ""
            owners.append(module)
            seen[module].add(name)
        per_plane[plane] = (ops, owners)

    maps = _name_maps(executables)
    chosen: Dict[str, Optional[dict]] = {}
    for module, names in seen.items():
        best, best_hits = None, 0
        for m in [m for m in maps if m["module"] == module] or maps:
            hits = sum(1 for n in names if n in m["instr_name"])
            if hits > best_hits:
                best, best_hits = m, hits
        chosen[module] = best

    n = len(devices)
    by_name: Dict[tuple, float] = collections.defaultdict(float)
    unattributed: Dict[str, float] = collections.defaultdict(float)
    busy = {}
    for plane, (ops, owners) in per_plane.items():
        busy[plane] = _union((s, e) for _, s, e in ops)
        for (name, _s, _e), module, ns in zip(ops, owners, _self_ns(ops)):
            m = chosen[module]
            key = m["instr_name"].get(name) if m else None
            if isinstance(key, tuple):
                by_name[key] += ns / n / 1e9
            else:
                unattributed[_FAMILY_RE.sub("", name)] += ns / n / 1e9
    host_spans: Dict[str, list] = collections.defaultdict(list)
    for name, s, e in trace["host"]:
        if name.startswith(ANNOTATION_PREFIX):
            host_spans[name].append((s, e))
    return {
        "chips": n,
        "window_ns": (lo, hi),
        "op_s": sum(by_name.values()) + sum(unattributed.values()),
        "by_name": dict(by_name),
        "unattributed": dict(unattributed),
        "programs": {mod: (m["label"] if m else None)
                     for mod, m in chosen.items()},
        "busy": busy,
        "host_spans": dict(host_spans),
    }
