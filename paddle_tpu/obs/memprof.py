"""HBM memory observability: static per-op attribution, a live
device-memory ledger, and OOM forensics (ISSUE 14).

The time domain is covered end to end (spans -> per-op cost ->
telemetry -> measured device time); this module is the same treatment
for **memory** — the resource ZeRO sharding, paged KV serving and
async checkpoints all contend over, and the one whose failure mode
(RESOURCE_EXHAUSTED) previously left zero forensics.  Three pieces:

* **Static attribution** (`profile_memory_text` / `capture_compiled`):
  on each compile-cache miss the AOT executable's `memory_analysis()`
  (argument/output/temp/alias bytes) is captured and the temp-buffer
  peak is attributed back to source Program ops through the SAME
  `program#<id>/block<idx>/op<id>:<type>[pass=...]` provenance opprof
  threads into HLO metadata.  Per-instruction output-buffer bytes are
  the raw estimate, normalized to the compiler's own
  `temp_size_in_bytes` so rows are shares of the truth; instructions
  with no provenance land in an explicit `unattributed` bin.  When
  opprof already walked the same executable its `instr_prov` join map
  (consumer inheritance + fusion-dominant provenance) is reused, so
  the two attributions can never disagree about who owns a fusion.

* **Live ledger** (`memory_ledger` / `ledger_gauges`): framework-side
  accounting of every byte intentionally held on device — scope state
  (sharding-aware via `.addressable_shards`), compile-cache const/feed
  caches, feed `DeviceRing` staged batches, serving `PagedKVCache`
  pages, in-flight ckpt snapshots.  Subsystems either push entries
  (`set_entry`/`add_entry`) or register pull callables
  (`register_source`); the ledger reconciles against
  `device.memory_stats()` (gracefully absent on CPU) so
  `bytes_in_use = ledger + executable temp + unattributed` with the
  residual explicit, never silently spread.  Gauges
  (`hbm_bytes_in_use`, `hbm_peak_bytes`, `ledger_*`) fold into
  telemetry through `default_sources` — NO new sampler thread.

* **OOM forensics** (`oom_report` / `memory_doc`): the executor's
  dispatch path catches RESOURCE_EXHAUSTED and publishes a `mem_oom`
  flight bundle (ledger + top static temp buffers + series) before
  re-raising; the telemetry watchdog's `hbm_pressure` rule flips
  `/healthz` when utilization crosses the threshold or headroom drops
  below the next program's static temp requirement.
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

_MEMPROF_ENV = "PADDLE_OBS_MEMPROF"

# provenance minted by ops/registry.op_provenance (the opprof format)
PROVENANCE_RE = re.compile(
    r"program#(\d+)/block(\d+)/op(\d+):([A-Za-z0-9_.]+)"
    r"(?:\[pass=([A-Za-z0-9_,.\-]+)\])?")

UNATTRIBUTED = "unattributed"


def memprof_enabled() -> bool:
    return os.environ.get(_MEMPROF_ENV, "1").lower() not in ("0", "off",
                                                             "false")


def parse_provenance(s: str) -> Optional[dict]:
    """Last (deepest-scoped) provenance occurrence in `s`, or None."""
    last = None
    for m in PROVENANCE_RE.finditer(s):
        last = m
    if last is None:
        return None
    prog, blk, op, typ, passes = last.groups()
    return {"prog": int(prog), "block": int(blk), "op": int(op),
            "type": typ, "passes": passes.split(",") if passes else []}


def _format_provenance(p: dict) -> str:
    s = f"program#{p['prog']}/block{p['block']}/op{p['op']}:{p['type']}"
    if p.get("passes"):
        s += f"[pass={','.join(p['passes'])}]"
    return s


# ---------------------------------------------------------------------------
# HLO text parsing — the buffer-bytes subset of opprof's walk
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
    "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e5m2": 1, "f8e4m3": 1, "f8e4m3fn": 1, "f8e4m3b11fnuz": 1,
    "f8e5m2fnuz": 1, "f8e4m3fnuz": 1, "token": 0, "opaque": 0,
}

_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*")
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s+\([^=]*\)\s*->")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_CALLS_RE = re.compile(r"calls=%([\w.\-]+)")
_BLOCK_COMMENT_RE = re.compile(r"/\*.*?\*/")

# opcodes that allocate no buffer of their own: inputs, literals,
# aliases and pure bookkeeping
_NOBUF = {"parameter", "constant", "tuple", "get-tuple-element",
          "bitcast", "after-all", "domain", "add-dependency",
          "optimization-barrier", "partition-id", "replica-id",
          "get-dimension-size"}


def _shape_bytes(text: str) -> int:
    """Byte count of a result type string ('f32[64,256]{1,0}',
    '(f32[2]{0}, s32[])', ...).  Tuples sum their leaves."""
    nbytes = 0
    for m in _SHAPE_RE.finditer(text):
        dtype, dims = m.groups()
        if dtype not in _DTYPE_BYTES:
            continue  # layout annotations like {1,0:T(8,128)} match too
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        nbytes += n * _DTYPE_BYTES[dtype]
    return nbytes


def _take_balanced(s: str, start: int) -> Tuple[str, int]:
    depth = 0
    for i in range(start, len(s)):
        if s[i] == "(":
            depth += 1
        elif s[i] == ")":
            depth -= 1
            if depth == 0:
                return s[start + 1:i], i + 1
    return s[start + 1:], len(s)


class _Buf:
    __slots__ = ("name", "opcode", "nbytes", "op_name", "comp", "line")

    def __init__(self, name, opcode, nbytes, op_name, comp, line):
        self.name = name
        self.opcode = opcode
        self.nbytes = nbytes
        self.op_name = op_name
        self.comp = comp
        self.line = line


def _parse_buffers(text: str) -> List[_Buf]:
    out: List[_Buf] = []
    comp = ""
    for raw in text.splitlines():
        line = _BLOCK_COMMENT_RE.sub("", raw).rstrip()
        if not line or line.lstrip().startswith(("//", "#")):
            continue
        if line.endswith("{") and "=" not in line.split("{")[0]:
            mc = _COMP_RE.match(line)
            if mc:
                comp = mc.group(2)
            continue
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        name = m.group(1)
        rest = line[m.end():]
        if rest.startswith("("):
            shape_txt, idx = _take_balanced(rest, 0)
        else:
            idx = rest.find(" ")
            if idx < 0:
                continue
            shape_txt = rest[:idx]
        tail = rest[idx:].lstrip()
        mo = re.match(r"([a-zA-Z][\w\-]*)\s*\(", tail)
        if mo is None:
            continue
        mn = _OPNAME_RE.search(line)
        out.append(_Buf(name, mo.group(1), _shape_bytes(shape_txt),
                        mn.group(1) if mn else "", comp, line))
    return out


def _new_row(key: str) -> dict:
    return {"op": key, "temp_bytes_raw": 0.0, "buffers": 0,
            "largest_bytes_raw": 0.0}


def profile_memory_text(text: str, label: str = "",
                        memory: Optional[Dict[str, int]] = None,
                        instr_prov: Optional[Dict[str, str]] = None
                        ) -> dict:
    """Fold an optimized-HLO dump into a per-Program-op temp-buffer
    table.

    Each top-level buffer-allocating instruction's OUTPUT bytes are its
    temp-peak contribution estimate (fused interiors excluded — only
    computation-boundary buffers exist in the allocator's world).
    `memory` is the executable's own `memory_analysis()` numbers
    ({"temp_bytes", "argument_bytes", "output_bytes", "alias_bytes",
    "generated_code_bytes"}); when present the raw estimates are
    normalized so the table sums to the compiler's temp total.
    `instr_prov` is opprof's instruction->provenance join map; when
    given it overrides the local metadata parse (consumer inheritance
    and fusion-dominant attribution come for free)."""
    bufs = _parse_buffers(text)

    # interior computations reached via a fusion's calls= allocate
    # nothing of their own: the fusion's output buffer is the temp.
    # Their metadata still votes for the fusion's dominant provenance.
    fused_comps = set()
    for b in bufs:
        if b.opcode == "fusion":
            mc = _CALLS_RE.search(b.line)
            if mc:
                fused_comps.add(mc.group(1))
    interior_votes: Dict[str, collections.Counter] = \
        collections.defaultdict(collections.Counter)
    for b in bufs:
        if b.comp in fused_comps:
            p = parse_provenance(b.op_name)
            if p is not None:
                interior_votes[b.comp][_format_provenance(p)] += 1

    def _key_of(b: _Buf) -> str:
        if instr_prov is not None:
            k = instr_prov.get(b.name)
            if k:
                return k
        p = parse_provenance(b.op_name)
        if p is not None:
            return _format_provenance(p)
        if b.opcode == "fusion":
            mc = _CALLS_RE.search(b.line)
            cnt = interior_votes.get(mc.group(1)) if mc else None
            if cnt:
                return sorted(cnt.items(),
                              key=lambda kv: (-kv[1], kv[0]))[0][0]
        return UNATTRIBUTED

    rows: Dict[str, dict] = collections.OrderedDict()
    top: List[dict] = []
    raw_total = 0.0
    for b in bufs:
        if b.comp in fused_comps or b.opcode in _NOBUF or b.nbytes <= 0:
            continue
        key = _key_of(b)
        row = rows.get(key)
        if row is None:
            row = rows[key] = _new_row(key)
            src = parse_provenance(key)
            if src is not None:
                row["source"] = src
        row["buffers"] += 1
        row["temp_bytes_raw"] += float(b.nbytes)
        row["largest_bytes_raw"] = max(row["largest_bytes_raw"],
                                       float(b.nbytes))
        raw_total += float(b.nbytes)
        top.append({"instr": b.name, "opcode": b.opcode, "op": key,
                    "bytes_raw": float(b.nbytes)})

    memory = memory or {}
    temp_total = float(memory.get("temp_bytes", 0) or 0)
    scale = temp_total / raw_total if temp_total > 0.0 \
        and raw_total > 0.0 else 1.0

    attributed_raw = 0.0
    table: List[dict] = []
    for key, row in rows.items():
        row["temp_bytes"] = row["temp_bytes_raw"] * scale
        row["largest_bytes"] = row["largest_bytes_raw"] * scale
        row["temp_pct"] = (row["temp_bytes_raw"] / raw_total * 100.0
                           if raw_total > 0.0 else 0.0)
        if key != UNATTRIBUTED:
            attributed_raw += row["temp_bytes_raw"]
        table.append(row)
    table.sort(key=lambda r: -r["temp_bytes_raw"])
    top.sort(key=lambda r: -r["bytes_raw"])
    top = top[:10]
    for t in top:
        t["bytes"] = t["bytes_raw"] * scale

    return {
        "label": label,
        "rows": table,
        "buffer_count": sum(r["buffers"] for r in table),
        "temp_bytes": temp_total or raw_total,
        "temp_bytes_raw": raw_total,
        "argument_bytes": float(memory.get("argument_bytes", 0) or 0),
        "output_bytes": float(memory.get("output_bytes", 0) or 0),
        "alias_bytes": float(memory.get("alias_bytes", 0) or 0),
        "generated_code_bytes": float(
            memory.get("generated_code_bytes", 0) or 0),
        "attributed_temp_pct": (attributed_raw / raw_total * 100.0
                                if raw_total > 0.0 else 0.0),
        "top_buffers": top,
    }


def top_buffers(profile: dict, k: int = 8) -> List[dict]:
    """Top-k individual temp buffers of a profile (the OOM-forensics
    view: which single allocations would not have fit)."""
    return list(profile.get("top_buffers", []))[:k]


def trim_profile(profile: dict, k: int = 8) -> dict:
    """Snapshot-sized view: top-k rows + the unattributed bin +
    totals (the full table stays in the registry)."""
    rows = [r for r in profile.get("rows", [])
            if r["op"] != UNATTRIBUTED][:k]
    rows += [r for r in profile.get("rows", [])
             if r["op"] == UNATTRIBUTED]
    out = {kk: v for kk, v in profile.items()
           if kk not in ("rows", "top_buffers")}
    out["rows"] = [{f: (round(v, 3) if isinstance(v, float) else v)
                    for f, v in r.items()} for r in rows]
    for f in ("temp_bytes", "temp_bytes_raw", "attributed_temp_pct"):
        if f in out:
            out[f] = round(float(out[f]), 3)
    return out


# ---------------------------------------------------------------------------
# Profile registry (the opprof idiom: bounded, insertion-ordered)
# ---------------------------------------------------------------------------

_PROFILES: "collections.OrderedDict[str, dict]" = \
    collections.OrderedDict()
_PROFILES_LOCK = threading.Lock()
_PROFILES_CAP = 64


def register_profile(label: str, profile: dict) -> dict:
    with _PROFILES_LOCK:
        _PROFILES[label] = profile
        _PROFILES.move_to_end(label)
        while len(_PROFILES) > _PROFILES_CAP:
            _PROFILES.popitem(last=False)
    return profile


def profiles() -> "collections.OrderedDict[str, dict]":
    with _PROFILES_LOCK:
        return collections.OrderedDict(_PROFILES)


def reset_profiles() -> None:
    with _PROFILES_LOCK:
        _PROFILES.clear()


def profile_for(prog_id: Optional[int] = None,
                label: Optional[str] = None) -> Optional[dict]:
    """Most recent registered memory profile, optionally filtered by
    the SOURCE program id its rows attribute to, or by exact label."""
    with _PROFILES_LOCK:
        items = list(_PROFILES.items())
    for lab, prof in reversed(items):
        if label is not None:
            if lab == label:
                return prof
            continue
        if prog_id is None:
            return prof
        for row in prof.get("rows", []):
            src = row.get("source")
            if src and src.get("prog") == prog_id:
                return prof
    return None


def static_temp_peak_bytes() -> float:
    """Largest static temp requirement among registered executables —
    the headroom the NEXT dispatch of the biggest program needs."""
    with _PROFILES_LOCK:
        vals = [float(p.get("temp_bytes", 0.0) or 0.0)
                for p in _PROFILES.values()]
    return max(vals) if vals else 0.0


def capture_compiled(compiled, label: str,
                     opprof_profile: Optional[dict] = None,
                     register: bool = True) -> Optional[dict]:
    """Capture an AOT executable's memory_analysis + HLO walk and
    register the per-op temp table.  Duck-typed on `.memory_analysis()`
    / `.as_text()` so this module stays jax-free; returns None (never
    raises) when the backend can't report memory."""
    if not memprof_enabled():
        return None
    memory = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            memory = {
                "temp_bytes": int(getattr(
                    ma, "temp_size_in_bytes", 0) or 0),
                "argument_bytes": int(getattr(
                    ma, "argument_size_in_bytes", 0) or 0),
                "output_bytes": int(getattr(
                    ma, "output_size_in_bytes", 0) or 0),
                "alias_bytes": int(getattr(
                    ma, "alias_size_in_bytes", 0) or 0),
                "generated_code_bytes": int(getattr(
                    ma, "generated_code_size_in_bytes", 0) or 0),
            }
    except Exception:  # noqa: BLE001 - optional on some PJRT plugins
        memory = None
    try:
        text = compiled.as_text() or ""
    except Exception:  # noqa: BLE001
        text = ""
    if not text and memory is None:
        return None
    try:
        prof = profile_memory_text(
            text, label=label, memory=memory,
            instr_prov=(opprof_profile or {}).get("instr_prov"))
    except Exception:  # noqa: BLE001 - attribution must never break a run
        return None
    if register:
        register_profile(label, prof)
    return prof


# ---------------------------------------------------------------------------
# Live device-memory ledger
# ---------------------------------------------------------------------------

_LEDGER_LOCK = threading.Lock()
_ENTRIES: Dict[str, int] = {}             # push-style accounting
_SOURCES: Dict[str, Callable[[], Any]] = {}   # pull-style callables
_DEVICE_STATS_FN: List[Optional[Callable[[], Optional[dict]]]] = [None]
_LEDGER_PEAK = [0]
_HBM_PEAK = [0]
# ledger samples for the Chrome counter track, perf_counter-clocked so
# they align with the span tracer's timeline
_SERIES_CAP = 512
_MEM_SERIES: "collections.deque" = collections.deque(maxlen=_SERIES_CAP)


def set_entry(name: str, nbytes: int) -> None:
    """Set a push-style ledger entry to an absolute byte count
    (<= 0 removes it)."""
    with _LEDGER_LOCK:
        if nbytes <= 0:
            _ENTRIES.pop(name, None)
        else:
            _ENTRIES[name] = int(nbytes)


def add_entry(name: str, delta: int) -> None:
    """Adjust a push-style ledger entry incrementally (a result of
    <= 0 removes it)."""
    with _LEDGER_LOCK:
        v = _ENTRIES.get(name, 0) + int(delta)
        if v <= 0:
            _ENTRIES.pop(name, None)
        else:
            _ENTRIES[name] = v


def get_entry(name: str) -> int:
    with _LEDGER_LOCK:
        return _ENTRIES.get(name, 0)


def register_source(name: str, fn: Callable[[], Any]) -> None:
    """Register a pull-style ledger source.  `fn()` returns either an
    int byte count (one entry named `name`) or a dict of
    entry-name -> bytes (one subsystem reporting several entries with
    shared internal dedup).  Called at ledger/poll time only — never
    on the dispatch hot path."""
    with _LEDGER_LOCK:
        _SOURCES[name] = fn


def unregister_source(name: str) -> None:
    with _LEDGER_LOCK:
        _SOURCES.pop(name, None)


def set_device_stats_fn(fn: Optional[Callable[[], Optional[dict]]]
                        ) -> None:
    """Override the device memory_stats probe (tests inject TPU-shaped
    stats here; None restores the default jax probe)."""
    _DEVICE_STATS_FN[0] = fn


def device_memory_stats() -> Optional[dict]:
    """`device.memory_stats()` summed over every local device (the
    ledger it is reconciled against counts shards on all of them), with
    the fullest device beside the sums — `fullest_frac` and
    `min_headroom_bytes`, because a sum hides one full device among
    empty ones and a program's temp must fit on EACH device.  None when
    the backend doesn't report stats (CPU) or jax is absent."""
    fn = _DEVICE_STATS_FN[0]
    if fn is not None:
        try:
            return fn()
        except Exception:  # noqa: BLE001 - injected probes never break
            return None
    try:
        import jax  # noqa: PLC0415 - lazy by design (stdlib module scope)

        per_dev = [d.memory_stats() for d in jax.local_devices()]
    except Exception:  # noqa: BLE001 - no jax / no backend stats
        return None
    if not per_dev or any(s is None for s in per_dev):
        return None
    doc = {k: sum(s.get(k, 0) for s in per_dev)
           for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}
    limited = [(s.get("bytes_in_use", 0), s["bytes_limit"])
               for s in per_dev if s.get("bytes_limit", 0) > 0]
    if limited:
        doc["fullest_frac"] = max(u / lim for u, lim in limited)
        doc["min_headroom_bytes"] = min(lim - u for u, lim in limited)
    return doc


def _collect_entries() -> Dict[str, int]:
    with _LEDGER_LOCK:
        entries = dict(_ENTRIES)
        sources = list(_SOURCES.items())
    for name, fn in sources:
        try:
            got = fn()
        except Exception:  # noqa: BLE001 - a broken source reports 0,
            continue       # never breaks the poll
        if isinstance(got, dict):
            for k, v in got.items():
                if isinstance(v, (int, float)) and v > 0:
                    entries[str(k)] = int(v)
        elif isinstance(got, (int, float)) and got > 0:
            entries[name] = int(got)
    return entries


def ledger_gauges(record: bool = True) -> Dict[str, float]:
    """The telemetry-facing gauge set, computed on demand at sample
    time (rides `default_sources` — no new sampler thread).  Ledger
    entries surface as `ledger_<entry>`, device truth as `hbm_*`
    (absent when `memory_stats()` is — so the hbm_pressure rule stays
    silent on CPU)."""
    entries = _collect_entries()
    total = sum(entries.values())
    with _LEDGER_LOCK:
        if total > _LEDGER_PEAK[0]:
            _LEDGER_PEAK[0] = total
        ledger_peak = _LEDGER_PEAK[0]
    g: Dict[str, float] = {"ledger_total_bytes": float(total),
                           "ledger_peak_bytes": float(ledger_peak)}
    for k, v in entries.items():
        g[f"ledger_{k}"] = float(v)
    static = static_temp_peak_bytes()
    if static > 0:
        g["hbm_static_temp_bytes"] = static
    stats = device_memory_stats()
    if stats and isinstance(stats.get("bytes_in_use"), (int, float)):
        in_use = float(stats["bytes_in_use"])
        g["hbm_bytes_in_use"] = in_use
        limit = stats.get("bytes_limit")
        if isinstance(limit, (int, float)) and limit > 0:
            g["hbm_limit_bytes"] = float(limit)
        if "fullest_frac" in stats:
            g["hbm_fullest_device_frac"] = float(stats["fullest_frac"])
            g["hbm_min_headroom_bytes"] = float(
                stats["min_headroom_bytes"])
        peak = stats.get("peak_bytes_in_use")
        with _LEDGER_LOCK:
            cand = float(peak) if isinstance(peak, (int, float)) \
                else in_use
            if cand > _HBM_PEAK[0]:
                _HBM_PEAK[0] = int(cand)
            g["hbm_peak_bytes"] = float(_HBM_PEAK[0])
    if record:
        with _LEDGER_LOCK:
            _MEM_SERIES.append((time.perf_counter(), entries))
    return g


def memory_ledger() -> dict:
    """The structured ledger: every entry, the device truth when the
    backend reports it, and the explicit residual —
    `bytes_in_use = ledger total + executable temp + unattributed`."""
    entries = _collect_entries()
    total = sum(entries.values())
    with _LEDGER_LOCK:
        if total > _LEDGER_PEAK[0]:
            _LEDGER_PEAK[0] = total
        ledger_peak = _LEDGER_PEAK[0]
        hbm_peak = _HBM_PEAK[0]
        _MEM_SERIES.append((time.perf_counter(), dict(entries)))
    static = static_temp_peak_bytes()
    stats = device_memory_stats()
    doc = {
        "entries": {k: int(v) for k, v in sorted(entries.items())},
        "total": int(total),
        "ledger_peak_bytes": int(ledger_peak),
        "static_temp_bytes": int(static),
        "device": dict(stats) if stats else None,
        "bytes_in_use": None,
        "peak_bytes": int(hbm_peak) if hbm_peak else int(ledger_peak),
        "unattributed": None,
        "explains": "bytes_in_use = ledger total + executable temp "
                    "+ unattributed",
    }
    if stats and isinstance(stats.get("bytes_in_use"), (int, float)):
        in_use = int(stats["bytes_in_use"])
        doc["bytes_in_use"] = in_use
        doc["unattributed"] = max(0, in_use - int(total))
        peak = stats.get("peak_bytes_in_use")
        if isinstance(peak, (int, float)):
            doc["peak_bytes"] = max(doc["peak_bytes"], int(peak))
    return doc


def reset_peak() -> None:
    with _LEDGER_LOCK:
        _LEDGER_PEAK[0] = 0
        _HBM_PEAK[0] = 0


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------

_LAST_OOM: List[Optional[dict]] = [None]


def is_oom_error(exc: BaseException) -> bool:
    """Whether an exception is the allocator saying no — the
    RESOURCE_EXHAUSTED signature XLA raises on all PJRT backends."""
    return "RESOURCE_EXHAUSTED" in str(exc) \
        or "RESOURCE_EXHAUSTED" in type(exc).__name__ \
        or "out of memory" in str(exc).lower()


def oom_report(label: str = "", error: Any = "") -> dict:
    """Assemble (and remember) the mem_oom forensics document: the
    live ledger at failure time + the failing program's static top
    temp buffers.  Host-registry reads only — safe to call from the
    dispatch except-path (lint-watched)."""
    prof = profile_for(label=label) if label else None
    if prof is None:
        prof = profile_for()
    doc = {
        "kind": "mem_oom",
        "label": label,
        "error": str(error)[:2000],
        "at": time.time(),
        "ledger": memory_ledger(),
        "top_buffers": top_buffers(prof) if prof else [],
        "static_profile": trim_profile(prof) if prof else None,
    }
    _LAST_OOM[0] = doc
    return doc


def last_oom() -> Optional[dict]:
    return _LAST_OOM[0]


def reset_oom() -> None:
    _LAST_OOM[0] = None


def memory_doc() -> dict:
    """The memory.json payload of a flight bundle: ledger + trimmed
    static profiles + the last OOM report (if any)."""
    with _PROFILES_LOCK:
        items = list(_PROFILES.items())
    return {
        "ledger": memory_ledger(),
        "profiles": {lab: trim_profile(p) for lab, p in items},
        "last_oom": _LAST_OOM[0],
    }


# ---------------------------------------------------------------------------
# Surfaces: Chrome counter track + snapshot block
# ---------------------------------------------------------------------------

def chrome_counter_events(pid: int = 1, tid: int = 0) -> List[dict]:
    """The recorded ledger samples as Chrome-trace "C" (counter)
    events — one `memory` track whose stacked series are the ledger
    entries.  Timestamps are perf_counter-based like every span, so
    the track aligns with the rest of the unified trace."""
    with _LEDGER_LOCK:
        samples = list(_MEM_SERIES)
    out = []
    for t, entries in samples:
        if not entries:
            continue
        out.append({"name": "memory", "ph": "C", "pid": pid,
                    "tid": tid, "ts": t * 1e6,
                    "args": {k: int(v) for k, v in entries.items()}})
    return out


def snapshot(top: int = 8) -> Dict[str, Any]:
    """The memory block of obs.snapshot(): live ledger + one trimmed
    static table per registered executable."""
    with _PROFILES_LOCK:
        items = list(_PROFILES.items())
    return {
        "ledger": memory_ledger(),
        "profiles": {lab: trim_profile(p, top) for lab, p in items},
    }
