"""Measured device-time profiling (ISSUE 12 tentpole).

Everything perf-shaped in the stack so far is *derived*: `obs.cost`
divides analytic FLOPs by inter-dispatch wall-clock and `obs.opprof`
attributes *estimated* FLOPs/bytes to source ops.  This module adds the
measured layer:

* **Capture** (`profile_window(steps=N)` / `PADDLE_OBS_DEVPROF=1`):
  an explicitly bounded window around real dispatches, recorded with
  `jax.profiler.start_trace` / `stop_trace` (works on the CPU backend
  too, which is what tier-1 exercises).  Profiling is never always-on:
  a window is armed, covers N dispatches, and is torn down.

* **Parse** (`parse_xplane_bytes`): the emitted `*.xplane.pb` files are
  decoded with a minimal protobuf *wire-format* reader — the opprof
  HLO-text-parser idiom: stdlib-only, no tensorflow dependency, and
  `tools/tracetool.py` can load this module by file path in
  environments without jax.  Field numbers follow tsl's xplane.proto
  (XSpace.planes=1; XPlane id=1/name=2/lines=3/event_metadata=4/
  stat_metadata=5; XLine id=1/name=2/timestamp_ns=3/events=4; XEvent
  metadata_id=1/offset_ps=2/duration_ps=3/stats=4; XStat oneof 2..7).

* **Join** (`join_events`): measured per-instruction durations are
  folded back onto source Program ops through the
  `program#<id>/block<idx>/op<id>:<type>` named_scope provenance that
  ops/registry stamps into HLO metadata (the opprof `instr_prov` map,
  built from the SAME optimized HLO the runtime executes).  Runtime
  thunk names can be renumbered against the `as_text()` dump
  (`dot.10` vs `dot.0`), so the join is tiered: exact name -> same-base
  order alignment -> unique-base fallback -> the explicit
  `unattributed` bin (never silently dropped).  Scheduler containers
  (`ThunkExecutor::Execute`, `TfrtCpuExecutable::Execute`, ...) overlap
  the leaf thunks they run and are excluded from the measured-time
  denominator.

* **Roofline** (`compute_roofline`): measured per-op time vs opprof
  FLOPs/bytes -> achieved-FLOPs / achieved-BW and a compute-/memory-/
  relayout-bound verdict per op — the measured replacement for the
  analytic `top-ops` shares.

* **Unified timeline** (`merge_chrome_trace`): device op events merged
  as their own `device:<plane>/<line>` tracks into `obs.export_trace`'s
  Chrome/Perfetto JSON, flow-linked (`devprof:<seq>` ids) from the
  `executor.dispatch` span that launched the step.

Hot-path contract: the ONLY thing the dispatch path ever does is
`note_dispatch` (append a (seq, label, t) tuple + stamp the span attr);
capture start/stop/parse run outside the dispatch path and are pinned
to the hot-path-sync WATCHLIST to keep it that way.
"""

from __future__ import annotations

import collections
import glob as _glob
import itertools
import os
import re
import shutil
import struct
import tempfile
import threading
import time
from typing import Any, Dict, Iterable, List, Optional, Tuple

UNATTRIBUTED = "unattributed"

_DEVPROF_ENV = "PADDLE_OBS_DEVPROF"

# scheduler/executable wrappers overlap the leaf thunks they run; they
# are timeline furniture, not device work — excluded from the measured
# denominator (counting ThunkExecutor::Execute once halved the
# attributed share in early testing)
CONTAINER_PREFIXES = (
    "TfrtCpuExecutable::",
    "ThunkExecutor::",
    "ThreadpoolListener",
    "XlaModule:",
    "Thunk::",
)
# one executable run is bracketed by exactly this container event; its
# start orders runs against the host dispatch sequence
RUN_MARKER = "TfrtCpuExecutable::Execute"
# host-side stack-frame lines (python frames): host time, not device
HOST_LINE_NAMES = {"python"}

# leaf events kept for the unified timeline (bounded: a long window
# must not grow host memory without limit; overflow is counted)
_TRACE_EVENT_CAP = 5000

# line-level gate: a non-host line with no run marker and under this
# fraction of profile-matchable event names is some other subsystem's
# line — binned under skipped_lines, outside the denominator
_LINE_MATCH_MIN = 0.30


# ---------------------------------------------------------------------------
# protobuf wire format: reader
# ---------------------------------------------------------------------------

def _read_varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = 0
    shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not (b & 0x80):
            return out, i
        shift += 7
        if shift > 70:
            raise ValueError("varint too long")


def _fields(buf: bytes):
    """Yield (field_number, wire_type, value) for one message payload.
    Length-delimited values come back as bytes; varints as ints."""
    i = 0
    n = len(buf)
    while i < n:
        tag, i = _read_varint(buf, i)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            val, i = _read_varint(buf, i)
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 2:
            ln, i = _read_varint(buf, i)
            val = buf[i:i + ln]
            i += ln
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, wire, val


def _utf8(v: bytes) -> str:
    return v.decode("utf-8", "replace")


def _parse_stat(buf: bytes) -> Tuple[int, Any, Optional[int]]:
    """One XStat -> (metadata_id, value, ref_id).  The value oneof:
    2=double, 3=uint64, 4=int64, 5=str, 6=bytes, 7=ref (a
    stat_metadata id whose *name* is the value)."""
    mid = 0
    val: Any = None
    ref: Optional[int] = None
    for f, w, v in _fields(buf):
        if f == 1 and w == 0:
            mid = v
        elif f == 2 and w == 1:
            val = struct.unpack("<d", v)[0]
        elif f == 3 and w == 0:
            val = v
        elif f == 4 and w == 0:
            val = v if v < (1 << 63) else v - (1 << 64)
        elif f == 5 and w == 2:
            val = _utf8(v)
        elif f == 6 and w == 2:
            val = v
        elif f == 7 and w == 0:
            ref = v
    return mid, val, ref


def _parse_meta_entry(buf: bytes) -> Tuple[int, Dict[str, str]]:
    """One map<int64, X*Metadata> entry (key=1, value=2) -> (id,
    {"name", "display_name"})."""
    key = 0
    meta = {"name": "", "display_name": ""}
    for f, w, v in _fields(buf):
        if f == 1 and w == 0:
            key = v
        elif f == 2 and w == 2:
            for mf, mw, mv in _fields(v):
                if mf == 2 and mw == 2:
                    meta["name"] = _utf8(mv)
                elif mf == 4 and mw == 2:
                    meta["display_name"] = _utf8(mv)
    return key, meta


def _parse_plane(buf: bytes) -> dict:
    name = ""
    raw_lines: List[bytes] = []
    emeta: Dict[int, Dict[str, str]] = {}
    smeta: Dict[int, Dict[str, str]] = {}
    for f, w, v in _fields(buf):
        if f == 2 and w == 2:
            name = _utf8(v)
        elif f == 3 and w == 2:
            raw_lines.append(v)
        elif f == 4 and w == 2:
            k, m = _parse_meta_entry(v)
            emeta[k] = m
        elif f == 5 and w == 2:
            k, m = _parse_meta_entry(v)
            smeta[k] = m
    lines = []
    for lb in raw_lines:
        lname = ""
        ts_ns = 0
        raw_events: List[bytes] = []
        for f, w, v in _fields(lb):
            if f == 2 and w == 2:
                lname = _utf8(v)
            elif f == 3 and w == 0:
                ts_ns = v
            elif f == 4 and w == 2:
                raw_events.append(v)
        events = []
        for eb in raw_events:
            mid = 0
            offset_ps = 0
            duration_ps = 0
            raw_stats: List[bytes] = []
            for f, w, v in _fields(eb):
                if f == 1 and w == 0:
                    mid = v
                elif f == 2 and w == 0:
                    offset_ps = v
                elif f == 3 and w == 0:
                    duration_ps = v
                elif f == 4 and w == 2:
                    raw_stats.append(v)
            md = emeta.get(mid, {})
            stats: Dict[str, Any] = {}
            for sb in raw_stats:
                smid, val, ref = _parse_stat(sb)
                sname = smeta.get(smid, {}).get("name") or str(smid)
                if ref is not None:
                    val = smeta.get(ref, {}).get("name") or ref
                stats[sname] = val
            events.append({
                "name": md.get("name") or md.get("display_name") or "",
                "offset_ps": offset_ps,
                "duration_ps": duration_ps,
                "stats": stats,
            })
        lines.append({"name": lname, "timestamp_ns": ts_ns,
                      "events": events})
    return {"name": name, "lines": lines}


def parse_xplane_bytes(data: bytes) -> dict:
    """Decode one serialized XSpace into plain dicts:
    {"planes": [{"name", "lines": [{"name", "timestamp_ns",
    "events": [{"name", "offset_ps", "duration_ps", "stats"}]}]}]}."""
    planes = []
    for f, w, v in _fields(data):
        if f == 1 and w == 2:
            planes.append(_parse_plane(v))
    return {"planes": planes}


def parse_xplane_dir(d: str) -> dict:
    """Merge every `*.xplane.pb` under a profiler session directory
    (jax writes `<d>/plugins/profile/<ts>/<host>.xplane.pb`)."""
    files = sorted(_glob.glob(
        os.path.join(d, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        for root, _dirs, names in os.walk(d):
            for nm in sorted(names):
                if nm.endswith(".xplane.pb"):
                    files.append(os.path.join(root, nm))
    planes: List[dict] = []
    for fp in files:
        with open(fp, "rb") as f:
            data = f.read()
        planes.extend(parse_xplane_bytes(data).get("planes", []))
    return {"planes": planes, "files": len(files)}


# ---------------------------------------------------------------------------
# protobuf wire format: encoder (synthetic fixtures for selftests; the
# reader must round-trip what this emits)
# ---------------------------------------------------------------------------

def _enc_varint(v: int) -> bytes:
    out = bytearray()
    v = int(v)
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _tag(field: int, wire: int) -> bytes:
    return _enc_varint((field << 3) | wire)


def _enc_int(field: int, v: int) -> bytes:
    return _tag(field, 0) + _enc_varint(v)


def _enc_len(field: int, payload) -> bytes:
    if isinstance(payload, str):
        payload = payload.encode("utf-8")
    return _tag(field, 2) + _enc_varint(len(payload)) + bytes(payload)


def _enc_double(field: int, v: float) -> bytes:
    return _tag(field, 1) + struct.pack("<d", float(v))


def encode_xspace(planes: List[dict]) -> bytes:
    """Serialize plain plane dicts (the parse_xplane_bytes shape) into
    XSpace wire bytes — event/stat metadata tables are rebuilt from the
    event names and stat keys."""
    out = b""
    for plane in planes:
        enames: Dict[str, int] = {}
        snames: Dict[str, int] = {}
        body = _enc_len(2, plane.get("name", ""))
        for li, line in enumerate(plane.get("lines", [])):
            lb = _enc_int(1, li + 1)
            lb += _enc_len(2, line.get("name", ""))
            lb += _enc_int(3, int(line.get("timestamp_ns", 0)))
            for ev in line.get("events", []):
                nm = ev.get("name", "")
                mid = enames.setdefault(nm, len(enames) + 1)
                eb = _enc_int(1, mid)
                eb += _enc_int(2, int(ev.get("offset_ps", 0)))
                eb += _enc_int(3, int(ev.get("duration_ps", 0)))
                for k, v in (ev.get("stats") or {}).items():
                    sid = snames.setdefault(k, len(snames) + 1)
                    sb = _enc_int(1, sid)
                    if isinstance(v, bool) or isinstance(v, int):
                        sb += _enc_int(3, int(v))
                    elif isinstance(v, float):
                        sb += _enc_double(2, v)
                    else:
                        sb += _enc_len(5, str(v))
                    eb += _enc_len(4, sb)
                lb += _enc_len(4, eb)
            body += _enc_len(3, lb)
        for nm, mid in enames.items():
            meta = _enc_int(1, mid) + _enc_len(2, nm)
            body += _enc_len(4, _enc_int(1, mid) + _enc_len(2, meta))
        for nm, sid in snames.items():
            meta = _enc_int(1, sid) + _enc_len(2, nm)
            body += _enc_len(5, _enc_int(1, sid) + _enc_len(2, meta))
        out += _enc_len(1, body)
    return out


# ---------------------------------------------------------------------------
# join: measured event time -> source Program ops
# ---------------------------------------------------------------------------

_SUFFIX_RE = re.compile(r"^(.*?)(?:\.(\d+))?$")


def _base(name: str) -> Tuple[str, int]:
    """('dot.10' -> ('dot', 10)); unsuffixed names rank first (-1)."""
    m = _SUFFIX_RE.match(name)
    b, s = m.group(1), m.group(2)
    return b, (int(s) if s is not None else -1)


def _is_container(name: str) -> bool:
    return name.startswith(CONTAINER_PREFIXES)


def _profile_lookup(profiles: Dict[str, dict]) -> Dict[str, tuple]:
    """label -> (instr_prov, base -> sorted [(suffix, instr_name)])
    for every registered profile that carries an instruction map."""
    lookup = {}
    for lab, prof in (profiles or {}).items():
        ip = prof.get("instr_prov")
        if not ip:
            continue
        by_base: Dict[str, List[Tuple[int, str]]] = {}
        for nm in ip:
            b, s = _base(nm)
            by_base.setdefault(b, []).append((s, nm))
        for lst in by_base.values():
            lst.sort()
        lookup[lab] = (ip, by_base)
    return lookup


def _pick_profile(distinct: Iterable[str],
                  lookup: Dict[str, tuple]) -> Tuple[Optional[str], float]:
    """Best-overlap profile for a set of event names (later-registered
    profiles win ties — the most recent compile is the likely run)."""
    distinct = set(distinct)
    best_lab, best_score = None, 0.0
    for lab, (ip, by_base) in lookup.items():
        hit = sum(1 for nm in distinct
                  if nm in ip or _base(nm)[0] in by_base)
        score = hit / max(1, len(distinct))
        if score >= best_score and score > 0.0:
            best_lab, best_score = lab, score
    return best_lab, best_score


def _resolve_group(names: Iterable[str], ip: Dict[str, str],
                   by_base: Dict[str, List[Tuple[int, str]]]) \
        -> Dict[str, Tuple[Optional[str], str]]:
    """Tiered event-name -> HLO-instruction resolution.  The runtime
    renumbers instruction suffixes (`dot.10` for `dot.0`), so after the
    exact tier, same-base names are aligned by suffix *rank* when the
    counts agree, then by unique base; everything else is explicitly
    unattributed."""
    grouped: Dict[str, List[Tuple[int, str]]] = {}
    for nm in set(names):
        b, s = _base(nm)
        grouped.setdefault(b, []).append((s, nm))
    out: Dict[str, Tuple[Optional[str], str]] = {}
    for b, lst in grouped.items():
        lst.sort()
        plst = by_base.get(b, [])
        for i, (_s, nm) in enumerate(lst):
            if nm in ip:
                out[nm] = (nm, "exact")
            elif plst and len(plst) == len(lst):
                out[nm] = (plst[i][1], "order")
            elif len(plst) == 1:
                out[nm] = (plst[0][1], "base")
            else:
                out[nm] = (None, "none")
    return out


def join_events(space: dict, profiles: Dict[str, dict],
                dispatches: Optional[List[tuple]] = None) -> dict:
    """Fold a parsed XSpace onto source Program ops.

    `profiles` is the opprof registry ({label: profile}) — only
    profiles carrying `instr_prov` participate.  `dispatches` is the
    window's [(seq, label, perf_counter_s)] log; run-marker containers
    are matched back to the dispatch that launched them so the unified
    timeline can draw host->device flow arrows.  Pure function of its
    inputs (selftest-able on synthetic bytes)."""
    lookup = _profile_lookup(profiles)
    disp = sorted(dispatches or [], key=lambda d: d[2])

    measured_ns = 0.0
    nevents = 0
    ops: Dict[str, dict] = {}
    used_labels: set = set()
    skipped_lines: List[dict] = []
    trace_events: List[dict] = []
    trace_dropped = 0
    raw_markers: List[tuple] = []  # (start_ns, dur_ns, track)

    def _emit(te: dict) -> None:
        nonlocal trace_dropped
        if len(trace_events) < _TRACE_EVENT_CAP:
            trace_events.append(te)
        else:
            trace_dropped += 1

    for plane in space.get("planes", []):
        pname = plane.get("name", "")
        for line in plane.get("lines", []):
            lname = line.get("name", "")
            events = line.get("events", [])
            if not events:
                continue
            ts0 = float(line.get("timestamp_ns", 0) or 0)
            track = f"{pname}/{lname}" if pname else lname
            if lname in HOST_LINE_NAMES:
                # host stack-frame lines carry no device time, but the
                # runtime's run markers (TfrtCpuExecutable::Execute)
                # land HERE, interleaved with python frames — they are
                # what orders runs against the dispatch sequence
                rt_track = f"{pname}/runtime" if pname else "runtime"
                for ev in events:
                    if ev["name"] == RUN_MARKER:
                        raw_markers.append(
                            (ts0 + ev["offset_ps"] / 1e3,
                             ev["duration_ps"] / 1e3, rt_track))
                continue
            leaves = [ev for ev in events if not _is_container(ev["name"])]
            containers = [ev for ev in events if _is_container(ev["name"])]
            has_run = any(ev["name"] == RUN_MARKER for ev in containers)
            _lab, score = _pick_profile(
                (ev["name"] for ev in leaves), lookup)
            if not has_run and score < _LINE_MATCH_MIN:
                skipped_lines.append({
                    "line": track,
                    "events": len(leaves),
                    "time_ns": int(sum(ev["duration_ps"]
                                       for ev in leaves) / 1e3),
                })
                continue

            for ev in containers:
                start_ns = ts0 + ev["offset_ps"] / 1e3
                if ev["name"] == RUN_MARKER:
                    raw_markers.append((start_ns,
                                        ev["duration_ps"] / 1e3, track))
                    continue  # emitted after dedup + dispatch pairing
                _emit({"name": ev["name"], "ts_ns": start_ns,
                       "dur_ns": ev["duration_ps"] / 1e3,
                       "track": track, "container": True})

            # events of different executables interleave on one thread
            # line; the program_id stat keeps their joins separate
            groups: Dict[Any, List[dict]] = {}
            for ev in leaves:
                groups.setdefault(
                    ev["stats"].get("program_id"), []).append(ev)
            for _pid, group in groups.items():
                distinct = {ev["name"] for ev in group}
                lab, score = _pick_profile(distinct, lookup)
                resolution: Dict[str, Tuple[Optional[str], str]] = {}
                if lab is not None and score >= _LINE_MATCH_MIN:
                    used_labels.add(lab)
                    resolution = _resolve_group(distinct, *lookup[lab])
                for ev in group:
                    dur_ns = ev["duration_ps"] / 1e3
                    measured_ns += dur_ns
                    nevents += 1
                    key, tier = UNATTRIBUTED, "none"
                    if resolution:
                        inm, tier = resolution[ev["name"]]
                        if inm is not None:
                            key = lookup[lab][0][inm]
                        else:
                            key, tier = UNATTRIBUTED, "none"
                    rec = ops.setdefault(
                        key, {"time_ns": 0.0, "events": 0, "match": tier})
                    rec["time_ns"] += dur_ns
                    rec["events"] += 1
                    _emit({"name": ev["name"],
                           "ts_ns": ts0 + ev["offset_ps"] / 1e3,
                           "dur_ns": dur_ns, "track": track,
                           "op": key, "container": False})

    # the runtime records the run marker once per host stack depth —
    # nested duplicates over the same interval; keep the outermost of
    # each overlapping cluster
    raw_markers.sort()
    run_markers: List[list] = []
    prev_end = -1.0
    for start_ns, dur_ns, track in raw_markers:
        if start_ns >= prev_end:
            run_markers.append([start_ns, dur_ns, track, None])
        prev_end = max(prev_end, start_ns + dur_ns)
    # run -> dispatch pairing is BY ORDER: both sequences are
    # monotonic, but the xplane clock's epoch differs from
    # perf_counter's, so absolute time cannot be the join key
    run_seqs: List[Optional[int]] = []
    for i, rm in enumerate(run_markers):
        rm[3] = disp[i][0] if i < len(disp) else None
        run_seqs.append(rm[3])
    for start_ns, dur_ns, track, seq in run_markers:
        _emit({"name": RUN_MARKER, "ts_ns": start_ns, "dur_ns": dur_ns,
               "track": track, "container": True, "seq": seq})
    # rebase the device timeline onto the host (perf_counter) clock so
    # the merged Chrome trace shows one timeline: anchor the first
    # paired run marker at its dispatch timestamp
    ts_offset_ns = 0.0
    if run_markers and disp:
        ts_offset_ns = disp[0][2] * 1e9 - run_markers[0][0]
        for te in trace_events:
            te["ts_ns"] += ts_offset_ns

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
        "runs": len(run_markers) or len(disp) or 1,
        "run_seqs": run_seqs,
        "ts_offset_ns": ts_offset_ns,
        "measured_ns": measured_ns,
        "attributed_ns": attributed_ns,
        "attributed_pct": (attributed_ns / measured_ns * 100.0
                           if measured_ns > 0.0 else 0.0),
        "ops": ops,
        "labels": sorted(used_labels),
        "prog_ids": sorted(prog_ids),
        "skipped_lines": skipped_lines,
        "trace_events": trace_events,
        "trace_events_dropped": trace_dropped,
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
            "match": rec["match"],
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
_SEQ = itertools.count(1)
_RESULTS: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
_RESULTS_CAP = 16
_LAST: Optional[dict] = None


def note_dispatch(span, label: str) -> Optional[int]:
    """The ONE devprof touch on the dispatch hot path: while a window
    is armed, log (seq, label, t) and stamp `devprof_seq` on the
    dispatch span so the exporter can draw the host->device arrow.
    A single attribute check when no window is active; never syncs,
    never transfers."""
    w = _ACTIVE
    if w is None:
        return None
    seq = next(_SEQ)
    w.dispatches.append((seq, label, time.perf_counter()))
    try:
        span.set_attr("devprof_seq", seq)
    except Exception:  # noqa: BLE001 - observability, not control flow
        pass
    return seq


class DevprofWindow:
    """One bounded capture window: start_trace -> N dispatches ->
    stop_trace -> parse -> join -> roofline.  Context-manager friendly;
    `finish()` is idempotent and never raises."""

    def __init__(self, steps: Optional[int] = None,
                 label: Optional[str] = None):
        self.steps = int(steps) if steps else None
        self.label = label or "devprof"
        self.dispatches: List[tuple] = []
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
        space: dict = {"planes": []}
        try:
            import jax

            jax.profiler.stop_trace()
            space = parse_xplane_dir(self._dir)
        except Exception as e:  # noqa: BLE001 - capture must never break a run
            self.error = f"profiler stop/parse failed: {e!r}"
        finally:
            if self._dir:
                shutil.rmtree(self._dir, ignore_errors=True)
                self._dir = None
        self.result = self._build_result(space, capture_ms)
        _register_result(self.label, self.result)
        self._publish(self.result)
        return self.result

    def _build_result(self, space: dict, capture_ms: float) -> dict:
        try:
            from . import opprof

            profs = dict(opprof.profiles())
        except Exception:  # noqa: BLE001 - registry unavailable
            profs = {}
        join = join_events(space, profs, dispatches=self.dispatches)
        try:
            from . import cost

            cls = cost.device_class()
            pf, pb = cost.peak_flops(), cost.peak_hbm_bps()
        except Exception:  # noqa: BLE001 - no jax: label the regime
            cls, pf, pb = "cpu-fallback", 0.0, 0.0
        res = {
            "label": self.label,
            "capture_ms": round(capture_ms, 3),
            "device_class": cls,
            "steps": self.steps,
            "files": space.get("files", 0),
            "dispatches": [(s, lab) for s, lab, _t in self.dispatches],
            "events": join["events"],
            "runs": join["runs"],
            "run_seqs": join["run_seqs"],
            "labels": join["labels"],
            "prog_ids": join["prog_ids"],
            "measured_ms": round(join["measured_ns"] / 1e6, 6),
            "attributed_ms": round(join["attributed_ns"] / 1e6, 6),
            "attributed_pct": round(join["attributed_pct"], 3),
            "ops": {k: {"time_ms": round(v["time_ns"] / 1e6, 6),
                        "events": v["events"], "match": v["match"]}
                    for k, v in join["ops"].items()},
            "roofline": compute_roofline(join, profs, device_cls=cls,
                                         pf=pf, pb=pb),
            "skipped_lines": join["skipped_lines"],
            "trace_events": join["trace_events"],
            "trace_events_dropped": join["trace_events_dropped"],
        }
        if self.error:
            res["error"] = self.error
        return res

    def _publish(self, res: dict) -> None:
        try:
            from .. import profiler

            profiler.time_add("devprof_capture_ms", res["capture_ms"])
            profiler.stat_set("devprof_attributed_pct",
                              int(round(res["attributed_pct"])))
            profiler.stat_add("devprof_windows")
        except Exception:  # noqa: BLE001 - observability, not control flow
            pass


def profile_window(steps: Optional[int] = None,
                   label: Optional[str] = None) -> DevprofWindow:
    """Arm a bounded device-time capture window.  Use as a context
    manager (`with obs.profile_window(): ...`) or keep the handle and
    call `finish()`; with `steps=N` the training loop auto-stops it
    after N dispatches (`maybe_autostop`)."""
    return DevprofWindow(steps=steps, label=label).start()


def maybe_autostop() -> Optional[dict]:
    """Step-boundary hook (Executor loop): finish the active window
    once its dispatch budget is spent.  A single attribute check when
    no window is armed."""
    w = _ACTIVE
    if w is None or w.steps is None or not w._armed:
        return None
    if len(w.dispatches) >= w.steps:
        return w.finish()
    return None


def devprof_env_steps() -> Optional[int]:
    """PADDLE_OBS_DEVPROF: unset/0/off -> None; 1/on/true -> the
    3-step default window; an integer > 1 -> that many steps."""
    raw = os.environ.get(_DEVPROF_ENV, "").strip().lower()
    if raw in ("", "0", "off", "false", "no"):
        return None
    try:
        n = int(raw)
    except ValueError:
        return 3
    return n if n > 1 else 3


def maybe_start_env_window(label: str = "train") -> Optional[DevprofWindow]:
    """The PADDLE_OBS_DEVPROF auto-attach seam (Executor training
    loop): arm a bounded window when the env knob asks for one."""
    if _ACTIVE is not None:
        return None
    steps = devprof_env_steps()
    if steps is None:
        return None
    w = DevprofWindow(steps=steps, label=label).start()
    return w if w.error is None else None


def active_window() -> Optional[DevprofWindow]:
    return _ACTIVE


# ---------------------------------------------------------------------------
# result registry (the opprof idiom: bounded, insertion-ordered)
# ---------------------------------------------------------------------------

def _register_result(label: str, res: dict) -> None:
    global _LAST
    with _LOCK:
        _RESULTS[label] = res
        _RESULTS.move_to_end(label)
        while len(_RESULTS) > _RESULTS_CAP:
            _RESULTS.popitem(last=False)
        _LAST = res


def last_result() -> Optional[dict]:
    return _LAST


def results() -> "collections.OrderedDict[str, dict]":
    with _LOCK:
        return collections.OrderedDict(_RESULTS)


def reset() -> None:
    global _LAST
    with _LOCK:
        _RESULTS.clear()
        _LAST = None


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


def roofline_for(prog_id: Optional[int] = None,
                 label: Optional[str] = None) -> Optional[dict]:
    res = result_for(prog_id=prog_id, label=label)
    return res.get("roofline") if res else None


def gauges() -> Dict[str, float]:
    """Telemetry gauge levels from the most recent window (empty until
    one has finished)."""
    res = _LAST
    if not res:
        return {}
    return {"devprof_attributed_pct": float(res["attributed_pct"]),
            "devprof_capture_ms": float(res["capture_ms"])}


def trim_result(res: dict, top: int = 12) -> dict:
    """Snapshot-sized view: bounded op/roofline tables, timeline kept
    as a count (the full result stays in the registry)."""
    out = {k: v for k, v in res.items()
           if k not in ("trace_events", "ops", "roofline", "dispatches")}
    ops = sorted(res.get("ops", {}).items(),
                 key=lambda kv: -kv[1]["time_ms"])
    keep = [kv for kv in ops if kv[0] != UNATTRIBUTED][:top] \
        + [kv for kv in ops if kv[0] == UNATTRIBUTED]
    out["ops"] = dict(keep)
    rl = res.get("roofline") or {}
    out["roofline"] = {k: v for k, v in rl.items() if k != "ops"}
    out["roofline"]["ops"] = list(rl.get("ops", []))[:top]
    out["trace_event_count"] = len(res.get("trace_events", []))
    return out


def snapshot(top: int = 12) -> Dict[str, Any]:
    """The devprof block of obs.snapshot()."""
    with _LOCK:
        items = list(_RESULTS.items())
    return {"active": _ACTIVE is not None,
            "windows": {lab: trim_result(res, top)
                        for lab, res in items}}


# ---------------------------------------------------------------------------
# unified timeline: merge device tracks into a Chrome-trace document
# ---------------------------------------------------------------------------

def merge_chrome_trace(doc: dict, result: Optional[dict] = None) -> dict:
    """Merge a window result's device events into a Tracer
    chrome_trace() document (in place; also returned).  Device lines
    become their own `device:<plane>/<line>` tracks past the host tids;
    run-marker events matched to a dispatch get a `devprof:<seq>` flow
    arrow FROM the `executor.dispatch` span that launched them (found
    by the `devprof_seq` attr note_dispatch stamped).  The xplane clock
    has a different epoch than perf_counter, so join_events already
    rebased every ts_ns onto the host timeline (first run marker ==
    first dispatch) — the merge just converts units."""
    if result is None:
        result = _LAST
    if not result:
        return doc
    tevs = result.get("trace_events") or []
    if not tevs:
        return doc
    events = doc.setdefault("traceEvents", [])
    host_by_seq: Dict[int, dict] = {}
    max_tid = -1
    for ev in events:
        t = ev.get("tid")
        if isinstance(t, int) and t > max_tid:
            max_tid = t
        if ev.get("ph") == "X":
            seq = (ev.get("args") or {}).get("devprof_seq")
            if seq is not None:
                host_by_seq[seq] = ev
    track_tid: Dict[str, int] = {}
    added = 0
    flows = 0
    for te in tevs:
        track = te.get("track", "device")
        vt = track_tid.get(track)
        if vt is None:
            vt = max_tid + 1 + len(track_tid)
            track_tid[track] = vt
            events.append({"ph": "M", "name": "thread_name", "pid": 0,
                           "tid": vt,
                           "args": {"name": f"device:{track}"}})
        ts = te["ts_ns"] / 1e3
        ev = {"ph": "X", "cat": "devprof", "name": te["name"],
              "ts": ts, "dur": max(te["dur_ns"] / 1e3, 0.001),
              "pid": 0, "tid": vt}
        args = {}
        if te.get("op"):
            args["op"] = te["op"]
        if te.get("seq") is not None:
            args["devprof_seq"] = te["seq"]
        if args:
            ev["args"] = args
        events.append(ev)
        added += 1
        seq = te.get("seq")
        host = host_by_seq.pop(seq, None) if seq is not None else None
        if host is not None:
            fid = f"devprof:{seq}"
            events.append({"ph": "s", "cat": "flow", "name": "devprof",
                           "id": fid, "pid": 0, "tid": host["tid"],
                           "ts": host["ts"] + 0.01})
            events.append({"ph": "f", "bp": "e", "cat": "flow",
                           "name": "devprof", "id": fid, "pid": 0,
                           "tid": vt, "ts": ts + 0.01})
            flows += 1
    other = doc.setdefault("otherData", {})
    other["devprof"] = {"label": result.get("label"),
                        "device_events": added,
                        "device_tracks": len(track_tid),
                        "flows_linked": flows,
                        "attributed_pct": result.get("attributed_pct")}
    return doc
