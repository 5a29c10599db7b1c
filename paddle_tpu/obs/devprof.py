"""Measured device time under the program's own names.

`obs.opprof` says what an executable *should* cost; this module says
what it *did* cost on the device, from the profiler's trace.

* `device_time`: a `.xplane.pb` that `jax.profiler` wrote (whoever
  opened the trace) and the executables the process holds -> seconds by
  `(phase, path)`.  A TPU shows as one plane a chip, `/device:TPU:<n>`;
  its `XLA Ops` line holds one event per executed HLO instruction,
  named by the instruction's text (the name stands before ` = `), its
  `XLA Modules` line names the running program.  The join to `opprof`'s
  `instr_name` map is exact by instruction name, per executable; the
  rest lands in the explicit `unattributed` bin, by instruction family.
* `profile_window(steps=N)`: a bounded capture around real dispatches
  (`jax.profiler.start_trace` / `stop_trace`), then `device_time` on
  what it wrote.  The device timeline itself is the profiler's own
  trace; the Executor's stages are in it as `pt.*` annotations
  (`profiler.stage`).
* The CPU backend has no device plane: its thunks run on host threads,
  named by their instruction.  `join_events` folds those onto
  `instr_prov`, because tier-1 exercises the window on the CPU;
  scheduler containers overlap the leaf thunks they run and stay out of
  the measured denominator.  `compute_roofline` sets measured per-op
  time against opprof's FLOPs/bytes.

Hot-path contract: the dispatch path only ever calls `note_dispatch`;
capture start/stop/read are pinned to the hot-path-sync WATCHLIST.
Nothing here imports jax at module level: `tools/tracetool.py` loads
this file by path.
"""

from __future__ import annotations

import bisect
import collections
import glob as _glob
import os
import re
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

UNATTRIBUTED = "unattributed"

# scheduler/executable wrappers overlap the leaf thunks they run:
# timeline furniture, excluded from the measured denominator
CONTAINER_PREFIXES = (
    "TfrtCpuExecutable::",
    "ThunkExecutor::",
    "ThreadpoolListener",
    "XlaModule:",
    "Thunk::",
)
# host-side stack-frame lines (python frames): host time, not device
HOST_LINE_NAMES = {"python"}

# a non-host line under this share of profile-matchable event names is
# another subsystem's: binned under skipped_lines, outside the denominator
_LINE_MATCH_MIN = 0.30


# ---------------------------------------------------------------------------
# a chip's trace -> seconds by name
# ---------------------------------------------------------------------------

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


def _cpu_space(xplane_path: str) -> dict:
    """The host plane of a CPU-backend trace in the shape `join_events`
    walks: `{"planes": [{"name", "lines": [{"name", "events": [{"name",
    "offset_ps", "duration_ps", "stats": {"program_id"}}]}]}]}`."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(xplane_path).planes:
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                stats = {}
                if line.name not in HOST_LINE_NAMES:
                    stats = {k: v for k, v in e.stats
                             if k == "program_id"}
                events.append({"name": e.name,
                               "offset_ps": e.start_ns * 1e3,
                               "duration_ps": e.duration_ns * 1e3,
                               "stats": stats})
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------------------
# join: measured event time -> source Program ops
# ---------------------------------------------------------------------------

def _pick_profile(distinct: Iterable[str], profiles: Dict[str, dict]
                  ) -> Tuple[Optional[str], float]:
    """The profile whose instruction map knows most of a set of event
    names, and the share it knows (later-registered profiles win ties —
    the most recent compile is the likely run)."""
    distinct = set(distinct)
    best_lab, best_score = None, 0.0
    for lab, prof in (profiles or {}).items():
        ip = prof.get("instr_prov") or {}
        score = sum(1 for nm in distinct if nm in ip) / max(1, len(distinct))
        if score >= best_score and score > 0.0:
            best_lab, best_score = lab, score
    return best_lab, best_score


def join_events(space: dict, profiles: Dict[str, dict],
                runs: int = 1) -> dict:
    """Fold the thunk events of a CPU-backend trace (`_cpu_space`) onto
    source Program ops: an event is named by its HLO instruction, and
    the join to `instr_prov` is exact by that name.

    `profiles` is the opprof registry ({label: profile}); `runs` the
    number of dispatches the window covered.  Pure function of its
    inputs (selftest-able on synthetic planes)."""
    measured_ns = 0.0
    nevents = 0
    ops: Dict[str, dict] = {}
    used_labels: set = set()
    skipped_lines: List[dict] = []

    for plane in space.get("planes", []):
        pname = plane.get("name", "")
        for line in plane.get("lines", []):
            lname = line.get("name", "")
            events = line.get("events", [])
            if not events or lname in HOST_LINE_NAMES:
                continue
            track = f"{pname}/{lname}" if pname else lname
            leaves = [ev for ev in events
                      if not ev["name"].startswith(CONTAINER_PREFIXES)]
            _lab, score = _pick_profile(
                (ev["name"] for ev in leaves), profiles)
            if score < _LINE_MATCH_MIN:
                skipped_lines.append({
                    "line": track,
                    "events": len(leaves),
                    "time_ns": int(sum(ev["duration_ps"]
                                       for ev in leaves) / 1e3),
                })
                continue

            # events of different executables interleave on one thread
            # line; the program_id stat keeps their joins separate
            groups: Dict[Any, List[dict]] = {}
            for ev in leaves:
                groups.setdefault(
                    ev["stats"].get("program_id"), []).append(ev)
            for group in groups.values():
                lab, score = _pick_profile(
                    {ev["name"] for ev in group}, profiles)
                instr_prov = {}
                if lab is not None and score >= _LINE_MATCH_MIN:
                    used_labels.add(lab)
                    instr_prov = profiles[lab]["instr_prov"]
                for ev in group:
                    dur_ns = ev["duration_ps"] / 1e3
                    measured_ns += dur_ns
                    nevents += 1
                    key = instr_prov.get(ev["name"], UNATTRIBUTED)
                    rec = ops.setdefault(key, {"time_ns": 0.0, "events": 0})
                    rec["time_ns"] += dur_ns
                    rec["events"] += 1

    unattr_ns = ops.get(UNATTRIBUTED, {}).get("time_ns", 0.0)
    attributed_ns = measured_ns - unattr_ns
    prog_ids: set = set()
    for lab in used_labels:
        for row in profiles[lab].get("rows", []):
            src = row.get("source")
            if src and "prog" in src:
                prog_ids.add(src["prog"])

    return {
        "events": nevents,
        "runs": max(1, int(runs)),
        "measured_ns": measured_ns,
        "attributed_ns": attributed_ns,
        "attributed_pct": (attributed_ns / measured_ns * 100.0
                           if measured_ns > 0.0 else 0.0),
        "ops": ops,
        "labels": sorted(used_labels),
        "prog_ids": sorted(prog_ids),
        "skipped_lines": skipped_lines,
    }


# ---------------------------------------------------------------------------
# roofline: measured time vs opprof FLOPs/bytes
# ---------------------------------------------------------------------------

def compute_roofline(join: dict, profiles: Dict[str, dict],
                     device_cls: str = "cpu-fallback",
                     pf: float = 0.0, pb: float = 0.0) -> dict:
    """Per-op achieved FLOPs/BW and bound verdict from a join result.
    Uses the *raw* (per-run) opprof estimates; relayout-bound means
    the op's HBM traffic is dominated by transpose/copy bytes."""
    rows: Dict[str, dict] = {}
    for lab in join.get("labels", []):
        prof = profiles.get(lab)
        if not prof:
            continue
        for r in prof.get("rows", []):
            rows.setdefault(r["op"], r)
    runs = max(1, int(join.get("runs", 1)))
    total_ns = float(join.get("measured_ns", 0.0))
    out = []
    items = sorted(join.get("ops", {}).items(),
                   key=lambda kv: -kv[1]["time_ns"])
    for op, rec in items:
        t_s = rec["time_ns"] / runs / 1e9
        row = rows.get(op)
        flops = float(row.get("flops_raw", 0.0)) if row else 0.0
        nbytes = float(row.get("bytes_raw", 0.0)) if row else 0.0
        mfu = (flops / t_s / pf * 100.0
               if t_s > 0.0 and flops > 0.0 and pf > 0.0 else 0.0)
        hbm = (nbytes / t_s / pb * 100.0
               if t_s > 0.0 and nbytes > 0.0 and pb > 0.0 else 0.0)
        if op == UNATTRIBUTED:
            bound = UNATTRIBUTED
        elif row is None:
            bound = "unknown"
        elif row.get("transposes", 0) > 0 and \
                row.get("transpose_bytes", 0.0) >= \
                0.5 * max(1.0, row.get("bytes_raw", 0.0)):
            bound = "relayout-bound"
        elif flops <= 0.0 and nbytes > 0.0:
            bound = "memory-bound"
        elif mfu >= hbm:
            bound = "compute-bound"
        else:
            bound = "memory-bound"
        passes = list((row or {}).get("source", {}).get("passes", []))
        out.append({
            "op": op,
            "time_ms": round(rec["time_ns"] / 1e6, 6),
            "per_run_ms": round(t_s * 1e3, 6),
            "share_pct": round(rec["time_ns"] / total_ns * 100.0, 3)
            if total_ns > 0.0 else 0.0,
            "events": rec["events"],
            "flops": flops,
            "bytes": nbytes,
            "mfu_pct": round(mfu, 8),
            "hbm_bw_pct": round(hbm, 8),
            "bound": bound,
            "passes": passes,
        })
    return {
        "device_class": device_cls,
        "peak_flops": pf,
        "peak_hbm_bps": pb,
        "runs": runs,
        "measured_ms": round(total_ns / 1e6, 6),
        "attributed_pct": round(float(join.get("attributed_pct", 0.0)), 3),
        "ops": out,
    }


# ---------------------------------------------------------------------------
# capture windows
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_ACTIVE: Optional["DevprofWindow"] = None
_RESULTS: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
_RESULTS_CAP = 16


def note_dispatch(label: str) -> None:
    """The ONE devprof touch on the dispatch hot path: while a window
    is armed, count the dispatch (what `steps=N` stops on).  A single
    attribute check when no window is active; never syncs, never
    transfers."""
    w = _ACTIVE
    if w is not None:
        w.dispatches.append(label)


class DevprofWindow:
    """One bounded capture window: start_trace -> N dispatches ->
    stop_trace -> parse -> join -> roofline.  Context-manager friendly;
    `finish()` is idempotent and never raises."""

    def __init__(self, steps: Optional[int] = None,
                 label: Optional[str] = None):
        self.steps = int(steps) if steps else None
        self.label = label or "devprof"
        self.dispatches: List[str] = []      # the label of each
        self.result: Optional[dict] = None
        self.error: Optional[str] = None
        self._dir: Optional[str] = None
        self._t0 = 0.0
        self._armed = False

    def start(self) -> "DevprofWindow":
        """Arm the window (one active window per process — profiling
        is explicitly bounded, never stacked)."""
        global _ACTIVE
        with _LOCK:
            if _ACTIVE is not None:
                self.error = "a devprof window is already active"
                return self
            _ACTIVE = self
        try:
            import jax

            self._dir = tempfile.mkdtemp(prefix="paddle_devprof_")
            self._t0 = time.perf_counter()
            jax.profiler.start_trace(self._dir)
            self._armed = True
        except Exception as e:  # noqa: BLE001 - capture must never break a run
            self.error = f"profiler start failed: {e!r}"
            with _LOCK:
                if _ACTIVE is self:
                    _ACTIVE = None
            if self._dir:
                shutil.rmtree(self._dir, ignore_errors=True)
                self._dir = None
        return self

    def __enter__(self) -> "DevprofWindow":
        if not self._armed and self.error is None:
            self.start()
        return self

    def __exit__(self, *exc):
        self.finish()
        return False

    def finish(self) -> Optional[dict]:
        """Stop the trace, parse the xplane dump, join onto Program
        ops, compute the roofline, and publish gauges.  Runs OFF the
        dispatch path (watchlisted to stay that way)."""
        global _ACTIVE
        with _LOCK:
            if _ACTIVE is self:
                _ACTIVE = None
            if not self._armed:
                return self.result
            self._armed = False
        capture_ms = (time.perf_counter() - self._t0) * 1e3
        try:
            import jax

            jax.profiler.stop_trace()
            self.result = self._build_result(find_xplane(self._dir),
                                             capture_ms)
        except Exception as e:  # noqa: BLE001 - capture must never break a run
            self.error = f"profiler stop/read failed: {e!r}"
            self.result = self._build_result(None, capture_ms)
        finally:
            if self._dir:
                shutil.rmtree(self._dir, ignore_errors=True)
                self._dir = None
        _register_result(self.label, self.result)
        self._publish(self.result)
        return self.result

    def _build_result(self, xplane: Optional[str],
                      capture_ms: float) -> dict:
        """A chip's trace goes through `device_time`, the same function
        the benchmark calls; a CPU-backend trace has no device plane
        and goes through the thunk tiers of `join_events`."""
        from . import cost, opprof

        profs = dict(opprof.profiles())
        table = device_time(xplane, profs) if xplane else None
        space = (_cpu_space(xplane) if xplane and table is None
                 else {"planes": []})
        join = join_events(space, profs, runs=len(self.dispatches))
        cls = cost.device_class()
        pf, pb = cost.peak_flops(), cost.peak_hbm_bps()
        res = {
            "label": self.label,
            "capture_ms": round(capture_ms, 3),
            "device_class": cls,
            "steps": self.steps,
            "dispatches": list(self.dispatches),
            "events": join["events"],
            "runs": join["runs"],
            "labels": join["labels"],
            "prog_ids": join["prog_ids"],
            "measured_ms": round(join["measured_ns"] / 1e6, 6),
            "attributed_ms": round(join["attributed_ns"] / 1e6, 6),
            "attributed_pct": round(join["attributed_pct"], 3),
            "ops": {k: {"time_ms": round(v["time_ns"] / 1e6, 6),
                        "events": v["events"]}
                    for k, v in join["ops"].items()},
            "roofline": compute_roofline(join, profs, device_cls=cls,
                                         pf=pf, pb=pb),
            "skipped_lines": join["skipped_lines"],
        }
        if table is not None:
            named = sum(table["by_name"].values())
            res["chips"] = table["chips"]
            res["measured_ms"] = round(table["op_s"] * 1e3, 6)
            res["attributed_ms"] = round(named * 1e3, 6)
            res["attributed_pct"] = round(
                named / table["op_s"] * 100.0 if table["op_s"] else 0.0, 3)
            res["by_name"] = sorted(
                ([phase, path, round(sec * 1e3, 6)]
                 for (phase, path), sec in table["by_name"].items()),
                key=lambda r: -r[2])
            res["unattributed"] = {k: round(v * 1e3, 6) for k, v in
                                   table["unattributed"].items()}
        if self.error:
            res["error"] = self.error
        return res

    def _publish(self, res: dict) -> None:
        from .. import profiler

        profiler.time_add("devprof_capture_ms", res["capture_ms"])
        profiler.stat_set("devprof_attributed_pct",
                          int(round(res["attributed_pct"])))
        profiler.stat_add("devprof_windows")


def profile_window(steps: Optional[int] = None,
                   label: Optional[str] = None) -> DevprofWindow:
    """Arm a bounded device-time capture window.  Use as a context
    manager (`with obs.profile_window(): ...`) or keep the handle and
    call `finish()`; with `steps=N` the training loop auto-stops it
    after N dispatches (`maybe_autostop`)."""
    return DevprofWindow(steps=steps, label=label).start()


def maybe_autostop(end_of_pass: bool = False) -> Optional[dict]:
    """Step-boundary hook (Executor training loop): finish the active
    `steps=N` window once its dispatch budget is spent, or at the end
    of the pass.  A single attribute check when no window is armed."""
    w = _ACTIVE
    if w is None or w.steps is None or not w._armed:
        return None
    if end_of_pass or len(w.dispatches) >= w.steps:
        return w.finish()
    return None


# ---------------------------------------------------------------------------
# result registry (the opprof idiom: bounded, insertion-ordered)
# ---------------------------------------------------------------------------

def _register_result(label: str, res: dict) -> None:
    with _LOCK:
        _RESULTS[label] = res
        _RESULTS.move_to_end(label)
        while len(_RESULTS) > _RESULTS_CAP:
            _RESULTS.popitem(last=False)


def last_result() -> Optional[dict]:
    return result_for()


def reset() -> None:
    with _LOCK:
        _RESULTS.clear()


def result_for(prog_id: Optional[int] = None,
               label: Optional[str] = None) -> Optional[dict]:
    """Most recent window result, optionally filtered by the SOURCE
    program id its join attributed time to, or by exact window label."""
    with _LOCK:
        items = list(_RESULTS.items())
    for lab, res in reversed(items):
        if label is not None:
            if lab == label:
                return res
            continue
        if prog_id is None:
            return res
        if prog_id in res.get("prog_ids", []):
            return res
    return None


def trim_result(res: dict, top: int = 12) -> dict:
    """Snapshot-sized view: bounded op/roofline/name tables (the full
    result stays in the registry)."""
    out = {k: v for k, v in res.items()
           if k not in ("ops", "roofline", "dispatches", "by_name")}
    ops = sorted(res.get("ops", {}).items(),
                 key=lambda kv: -kv[1]["time_ms"])
    keep = [kv for kv in ops if kv[0] != UNATTRIBUTED][:top] \
        + [kv for kv in ops if kv[0] == UNATTRIBUTED]
    out["ops"] = dict(keep)
    rl = res.get("roofline") or {}
    out["roofline"] = {k: v for k, v in rl.items() if k != "ops"}
    out["roofline"]["ops"] = list(rl.get("ops", []))[:top]
    if "by_name" in res:
        out["by_name"] = res["by_name"][:top]
    return out


def snapshot(top: int = 12) -> Dict[str, Any]:
    """The devprof block of obs.snapshot()."""
    with _LOCK:
        items = list(_RESULTS.items())
    return {"active": _ACTIVE is not None,
            "windows": {lab: trim_result(res, top)
                        for lab, res in items}}
