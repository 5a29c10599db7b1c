#!/usr/bin/env python
"""tracetool: summarize / diff / selftest paddle_tpu.obs trace files.

The obs layer exports one Chrome-trace/Perfetto JSON per run
(`obs.export_trace`, also `profiler.export_chrome_tracing`) with the
structured snapshot riding in otherData.  This CLI answers the
questions the ROADMAP perf items keep asking WITHOUT opening a trace
viewer:

  summarize  top spans by total time, per-thread tracks, cross-thread
             flow links, MFU per program (from the embedded cost
             gauges) and stall attribution (from the embedded feed
             pipeline timers)
  diff       per-span-name total/count deltas between two traces
             (before/after a perf change — the measurement half of
             "measure the layout win, then fuse")
  top-ops    per-op cost attribution (ISSUE 7): top Program ops by
             FLOPs / bytes / transposes from an op_profile table —
             found in a trace's embedded snapshot, a BENCH JSON, a
             saved profile JSON, or computed fresh from a raw
             optimized-HLO dump (obs/opprof.py walks it)
  metrics    live-telemetry post-mortem (ISSUE 10): per-metric
             min/mean/max/last over a telemetry JSON dump (a flight
             bundle's series.json or the /metrics?format=json body
             saved to a file) plus which watchdog rules WOULD have
             fired replayed over the series
  roofline   measured device time per op (ISSUE 12): the devprof
             join + roofline table — per-op measured ms, share,
             achieved MFU/BW and the compute-/memory-/relayout-bound
             verdict — from a devprof result, obs.snapshot(), a trace
             with an embedded snapshot, or a BENCH JSON
             (detail.device_profile)
  mem        HBM memory post-mortem (ISSUE 14): the device-memory
             ledger, per-op static temp attribution and any mem_oom
             report — from a flight bundle (memory.json), a BENCH
             JSON (detail.memory), a trace/snapshot JSON, or computed
             fresh from a raw optimized-HLO dump (obs/memprof.py
             walks it; --temp-bytes normalizes to the compiler's
             temp total)
  selftest   build a synthetic multi-thread trace through the span
             layer, export it, summarize it, verify the invariants
             end to end, run the op-profile HLO walk + top-ops
             rendering over a synthetic HLO dump, run synthetic
             CPU thunk planes through the devprof join/roofline,
             drive the telemetry
             collector/watchdog/flight-recorder over scripted
             sources, and exercise the memprof attribution + ledger
             + OOM-report math (wired into tools/ci.sh)

stdlib-only; paddle_tpu.obs.tracing, obs.opprof and obs.telemetry are
loaded by FILE PATH (the tpulint idiom), so this tool runs in
environments without jax.  Exit status: 0 ok, 1 findings/failure,
2 usage error.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import threading
from typing import Dict, List, Optional

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TRACING = os.path.join(REPO_ROOT, "paddle_tpu", "obs", "tracing.py")
_OPPROF = os.path.join(REPO_ROOT, "paddle_tpu", "obs", "opprof.py")
_TELEMETRY = os.path.join(REPO_ROOT, "paddle_tpu", "obs", "telemetry.py")
_DEVPROF = os.path.join(REPO_ROOT, "paddle_tpu", "obs", "devprof.py")
_MEMPROF = os.path.join(REPO_ROOT, "paddle_tpu", "obs", "memprof.py")
_NUMERICS = os.path.join(REPO_ROOT, "paddle_tpu", "obs", "numerics.py")


def _load_by_path(name: str, path: str):
    """Load a stdlib-only paddle_tpu module by file path — no
    paddle_tpu (and so no jax) import."""
    mod = sys.modules.get(name)
    if mod is not None:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_tracing():
    return _load_by_path("paddle_tpu_obs_tracing", _TRACING)


def load_opprof():
    return _load_by_path("paddle_tpu_obs_opprof", _OPPROF)


def load_telemetry():
    return _load_by_path("paddle_tpu_obs_telemetry", _TELEMETRY)


def load_devprof():
    return _load_by_path("paddle_tpu_obs_devprof", _DEVPROF)


def load_memprof():
    return _load_by_path("paddle_tpu_obs_memprof", _MEMPROF)


def load_numerics():
    return _load_by_path("paddle_tpu_obs_numerics", _NUMERICS)


def load_trace(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    if "traceEvents" not in doc:
        raise ValueError(f"{path}: not a Chrome-trace document "
                         "(no traceEvents)")
    return doc


# ---------------------------------------------------------------------------
# summarize
# ---------------------------------------------------------------------------

def attribute_stall(times_ms: Dict[str, float]) -> str:
    """Feed-pipeline stall classification from the counters alone —
    the same logic as dataset.feed_pipeline.attribute_stall, duplicated
    here ON PURPOSE so the tool stays importable without jax."""
    full = float(times_ms.get("ring_full_wait_ms", 0.0))
    empty = float(times_ms.get("ring_empty_wait_ms", 0.0))
    parser = float(times_ms.get("parser_wait_ms", 0.0))
    stage = float(times_ms.get("host_feed_ms", 0.0))
    if full < 1e-6 and empty < 1e-6:
        return "balanced"
    if full >= empty:
        return "compute-bound"
    return "parser-bound" if parser >= stage else "transfer-bound"


def summarize(doc: dict, top: int = 15) -> dict:
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    metas = {e["tid"]: e.get("args", {}).get("name", "")
             for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "thread_name"}
    flows = [e for e in doc["traceEvents"] if e.get("cat") == "flow"]

    by_name: Dict[str, dict] = {}
    by_tid: Dict[int, dict] = {}
    for e in spans:
        n = by_name.setdefault(e["name"], {"count": 0, "total_ms": 0.0,
                                           "max_ms": 0.0})
        ms = e.get("dur", 0.0) / 1e3
        n["count"] += 1
        n["total_ms"] += ms
        n["max_ms"] = max(n["max_ms"], ms)
        t = by_tid.setdefault(e["tid"], {"events": 0, "busy_ms": 0.0})
        t["events"] += 1
        t["busy_ms"] += ms

    flow_ids: Dict[int, set] = {}
    for e in flows:
        flow_ids.setdefault(e.get("id"), set()).add(e.get("tid"))
    cross = sum(1 for tids in flow_ids.values() if len(tids) > 1)

    top_spans = sorted(
        ({"name": k, **{kk: (round(vv, 3) if isinstance(vv, float) else vv)
                        for kk, vv in v.items()}}
         for k, v in by_name.items()),
        key=lambda r: -r["total_ms"])[:top]

    other = doc.get("otherData", {})
    snap = other.get("snapshot", {})
    cost = snap.get("cost", {})
    mfu = [{"label": p.get("label"), "mfu_pct": p.get("mfu_pct"),
            "hbm_bw_pct": p.get("hbm_bw_pct"),
            "step_ms": p.get("step_ms"),
            "dispatches": p.get("dispatches")}
           for p in cost.get("programs", [])]
    return {
        "spans": len(spans),
        "span_names": len(by_name),
        "threads": [{"tid": tid, "name": metas.get(tid, ""),
                     "events": t["events"],
                     "busy_ms": round(t["busy_ms"], 3)}
                    for tid, t in sorted(by_tid.items())],
        "flows": len(flow_ids),
        "cross_thread_flows": cross,
        "dropped_events": other.get("dropped_events", 0),
        "top_spans": top_spans,
        "device_class": cost.get("device_class"),
        "mfu_per_program": mfu,
        "live_mfu_pct": cost.get("mfu_pct"),
        "collective_bytes": cost.get("collective_bytes", {}),
        "stall_attribution": attribute_stall(snap.get("timers_ms", {})),
    }


def print_summary(s: dict) -> None:
    print(f"spans: {s['spans']} ({s['span_names']} names), "
          f"threads: {len(s['threads'])}, flows: {s['flows']} "
          f"({s['cross_thread_flows']} cross-thread), "
          f"dropped: {s['dropped_events']}")
    for t in s["threads"]:
        print(f"  tid {t['tid']:>3} {t['name']:<24} "
              f"{t['events']:>6} ev {t['busy_ms']:>10.3f} ms busy")
    print(f"{'span':<32}{'count':>8}{'total_ms':>12}{'max_ms':>10}")
    for r in s["top_spans"]:
        print(f"{r['name']:<32}{r['count']:>8}{r['total_ms']:>12.3f}"
              f"{r['max_ms']:>10.3f}")
    if s.get("device_class"):
        print(f"device_class: {s['device_class']}  "
              f"live MFU: {s.get('live_mfu_pct')}%  "
              f"stall: {s['stall_attribution']}")
    for p in s["mfu_per_program"]:
        print(f"  {p['label']:<40} mfu {p['mfu_pct']:>8}% "
              f"hbm {p['hbm_bw_pct']:>8}% step {p['step_ms']} ms "
              f"x{p['dispatches']}")
    for ctype, nbytes in sorted(s["collective_bytes"].items()):
        print(f"  bytes-on-wire {ctype}: {nbytes}")


# ---------------------------------------------------------------------------
# diff
# ---------------------------------------------------------------------------

def diff_traces(a: dict, b: dict) -> List[dict]:
    def totals(doc):
        out: Dict[str, dict] = {}
        for e in doc["traceEvents"]:
            if e.get("ph") != "X":
                continue
            r = out.setdefault(e["name"], {"count": 0, "total_ms": 0.0})
            r["count"] += 1
            r["total_ms"] += e.get("dur", 0.0) / 1e3
        return out

    ta, tb = totals(a), totals(b)
    rows = []
    for name in sorted(set(ta) | set(tb)):
        ra = ta.get(name, {"count": 0, "total_ms": 0.0})
        rb = tb.get(name, {"count": 0, "total_ms": 0.0})
        rows.append({"name": name,
                     "a_ms": round(ra["total_ms"], 3),
                     "b_ms": round(rb["total_ms"], 3),
                     "delta_ms": round(rb["total_ms"] - ra["total_ms"], 3),
                     "a_count": ra["count"], "b_count": rb["count"]})
    rows.sort(key=lambda r: -abs(r["delta_ms"]))
    return rows


def print_diff(rows: List[dict]) -> None:
    print(f"{'span':<32}{'a_ms':>12}{'b_ms':>12}{'delta_ms':>12}"
          f"{'a#':>7}{'b#':>7}")
    for r in rows:
        print(f"{r['name']:<32}{r['a_ms']:>12.3f}{r['b_ms']:>12.3f}"
              f"{r['delta_ms']:>12.3f}{r['a_count']:>7}{r['b_count']:>7}")


# ---------------------------------------------------------------------------
# top-ops
# ---------------------------------------------------------------------------

def find_profiles(path: str) -> Dict[str, dict]:
    """op_profile tables from any artifact that carries them:

    * a raw optimized-HLO dump (non-JSON) -> walk it fresh via opprof
    * a saved profile JSON (has "rows")
    * a BENCH JSON (detail.op_profile / detail.resnet50... — bench
      embeds a trimmed summary, full tables live in obs.snapshot())
    * a trace / snapshot JSON (otherData.snapshot.op_profile or a bare
      snapshot with "op_profile")
    """
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        # not JSON: treat as an optimized-HLO text dump
        opprof = load_opprof()
        return {os.path.basename(path):
                opprof.profile_hlo_text(text, label=path)}
    if isinstance(doc, dict) and isinstance(doc.get("rows"), list):
        return {doc.get("label") or os.path.basename(path): doc}
    profs: Dict[str, dict] = {}

    def walk(node):
        if not isinstance(node, dict):
            return
        op = node.get("op_profile")
        if isinstance(op, dict):
            if isinstance(op.get("rows"), list):
                profs[op.get("label") or "op_profile"] = op
            else:
                for label, prof in op.items():
                    if isinstance(prof, dict) \
                            and isinstance(prof.get("rows"), list):
                        profs[label] = prof
        for v in node.values():
            if isinstance(v, dict):
                walk(v)

    walk(doc)
    return profs


def print_top_ops(label: str, prof: dict, top: int, key: str) -> None:
    opprof = load_opprof()
    rows = opprof.top_ops(prof, top, key)
    attributed = prof.get("attributed_flops_pct")
    print(f"== {label}  (total_flops={prof.get('total_flops', 0):.4g}, "
          f"attributed {attributed if attributed is None else round(attributed, 2)}%"
          f", {prof.get('instruction_count', '?')} instructions)")
    print(f"{'op':<56}{'flops':>12}{'pct':>7}{'bytes':>12}"
          f"{'fus':>5}{'transp':>7}{'coll_B':>10}")
    for r in rows:
        print(f"{r['op']:<56}{r.get('flops', 0):>12.4g}"
              f"{r.get('flops_pct', 0):>7.2f}{r.get('bytes', 0):>12.4g}"
              f"{r.get('fusions', 0):>5}{r.get('transposes', 0):>7}"
              f"{r.get('collective_bytes', 0):>10.4g}")
    unattr = [r for r in prof.get("rows", [])
              if r.get("op") == opprof.UNATTRIBUTED]
    if unattr:
        r = unattr[0]
        print(f"{'(unattributed)':<56}{r.get('flops', 0):>12.4g}"
              f"{r.get('flops_pct', 0):>7.2f}")


def top_ops_cmd(path: str, top: int, key: str, as_json: bool) -> int:
    profs = find_profiles(path)
    if not profs:
        print(f"tracetool top-ops: no op_profile table found in {path} "
              "(need a trace/BENCH JSON with an embedded snapshot, a "
              "profile JSON, or a raw HLO dump)", file=sys.stderr)
        return 1
    if as_json:
        print(json.dumps({label: {**prof,
                                  "rows": prof.get("rows", [])[:top]}
                          for label, prof in profs.items()}))
        return 0
    for label, prof in profs.items():
        print_top_ops(label, prof, top, key)
    return 0


# ---------------------------------------------------------------------------
# roofline (measured device time per op, ISSUE 12)
# ---------------------------------------------------------------------------

def find_rooflines(path: str) -> Dict[str, dict]:
    """Roofline tables from any artifact that carries them:

    * a saved devprof window result or obs.snapshot() (the `roofline`
      key under each window)
    * a trace JSON (otherData.snapshot.devprof.windows...)
    * a BENCH JSON — detail.device_profile is the trimmed form
      (top_time rows with share/bound only)
    * a bare roofline JSON (`obs.roofline()` output saved to a file)
    """
    with open(path) as f:
        doc = json.load(f)
    out: Dict[str, dict] = {}
    if isinstance(doc, dict) and isinstance(doc.get("ops"), list) \
            and "attributed_pct" in doc and "rows" not in doc:
        return {os.path.basename(path): doc}

    def walk(node, label):
        if not isinstance(node, dict):
            return
        rl = node.get("roofline")
        if isinstance(rl, dict) and isinstance(rl.get("ops"), list):
            out[node.get("label") or label or "roofline"] = rl
        dp = node.get("device_profile")
        if isinstance(dp, dict) and isinstance(dp.get("top_time"), list):
            out.setdefault("device_profile", {
                "device_class": dp.get("device_class"),
                "runs": dp.get("runs"),
                "measured_ms": dp.get("measured_ms"),
                "attributed_pct": dp.get("attributed_pct"),
                "ops": [dict(r) for r in dp["top_time"]],
            })
        for k, v in node.items():
            if isinstance(v, dict):
                walk(v, k)
            elif isinstance(v, list):
                for item in v:
                    walk(item, k)

    walk(doc, None)
    return out


def print_roofline(label: str, roof: dict, top: int) -> None:
    print(f"== {label}  (device_class={roof.get('device_class')}, "
          f"runs={roof.get('runs', '?')}, "
          f"measured {roof.get('measured_ms', '?')} ms, "
          f"attributed {roof.get('attributed_pct', '?')}%)")
    print(f"{'op':<56}{'per_run_ms':>12}{'share%':>8}{'mfu%':>9}"
          f"{'hbm%':>9}  {'bound':<16}{'passes'}")
    for r in roof.get("ops", [])[:top]:
        passes = ",".join(r.get("passes", []))
        print(f"{r.get('op', '?'):<56}"
              f"{r.get('per_run_ms', 0.0):>12.6f}"
              f"{r.get('share_pct', 0.0):>8.2f}"
              f"{r.get('mfu_pct', 0.0):>9.3f}"
              f"{r.get('hbm_bw_pct', 0.0):>9.3f}  "
              f"{r.get('bound', '?'):<16}{passes}")


def roofline_cmd(path: str, top: int, as_json: bool) -> int:
    roofs = find_rooflines(path)
    if not roofs:
        print(f"tracetool roofline: no roofline table found in {path} "
              "(need a devprof result/snapshot JSON, a trace with an "
              "embedded snapshot, or a BENCH JSON with "
              "detail.device_profile)", file=sys.stderr)
        return 1
    if as_json:
        print(json.dumps({label: {**roof,
                                  "ops": roof.get("ops", [])[:top]}
                          for label, roof in roofs.items()}))
        return 0
    for label, roof in roofs.items():
        print_roofline(label, roof, top)
    return 0


# ---------------------------------------------------------------------------
# mem (HBM memory post-mortem, ISSUE 14)
# ---------------------------------------------------------------------------

def load_memory_doc(path: str,
                    temp_bytes: Optional[int] = None) -> dict:
    """Memory artifacts from any file that carries them:

    * a raw optimized-HLO dump (non-JSON) -> walk it fresh via memprof
      (`--temp-bytes` supplies the compiler's temp total to normalize
      against)
    * a flight bundle DIRECTORY or its memory.json (obs/telemetry.py
      `_dump` / the mem_oom standalone bundle)
    * a BENCH JSON (detail.memory), a trace JSON
      (otherData.snapshot.memory) or a bare obs.snapshot()

    Returns {"ledger", "profiles", "last_oom"} with absent pieces None
    / empty.
    """
    if os.path.isdir(path):
        path = os.path.join(path, "memory.json")
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        # not JSON: an optimized-HLO text dump
        memprof = load_memprof()
        memory = {"temp_bytes": int(temp_bytes)} if temp_bytes else None
        prof = memprof.profile_memory_text(
            text, label=os.path.basename(path), memory=memory)
        return {"ledger": None,
                "profiles": {prof["label"]: prof}, "last_oom": None}
    out: dict = {"ledger": None, "profiles": {}, "last_oom": None}

    def walk(node, label):
        if not isinstance(node, dict):
            return
        if isinstance(node.get("rows"), list) \
                and "attributed_temp_pct" in node:
            out["profiles"].setdefault(
                node.get("label") or label or "memory", node)
            return
        if "entries" in node and "total" in node \
                and out["ledger"] is None:
            out["ledger"] = node
        if node.get("kind") == "mem_oom" and out["last_oom"] is None:
            out["last_oom"] = node
        for k, v in node.items():
            if isinstance(v, dict):
                walk(v, k)

    walk(doc, None)
    return out


def print_mem_profile(label: str, prof: dict, top: int) -> None:
    attributed = prof.get("attributed_temp_pct")
    print(f"== {label}  (temp={prof.get('temp_bytes', 0):.4g} B, "
          f"attributed "
          f"{attributed if attributed is None else round(attributed, 2)}%"
          f", {prof.get('buffer_count', '?')} buffers)")
    print(f"{'op':<56}{'temp_bytes':>14}{'pct':>7}{'bufs':>6}"
          f"{'largest':>14}")
    for r in prof.get("rows", [])[:top]:
        print(f"{r.get('op', '?'):<56}"
              f"{r.get('temp_bytes', 0.0):>14.4g}"
              f"{r.get('temp_pct', 0.0):>7.2f}"
              f"{r.get('buffers', 0):>6}"
              f"{r.get('largest_bytes', 0.0):>14.4g}")


def print_memory(doc: dict, top: int) -> None:
    led = doc.get("ledger")
    if led:
        in_use = led.get("bytes_in_use")
        print(f"ledger: {led.get('total', 0)} B over "
              f"{len(led.get('entries', {}))} entries, "
              f"static temp {led.get('static_temp_bytes', 0)} B, "
              f"device in_use "
              f"{in_use if in_use is not None else 'n/a (no stats)'}, "
              f"unattributed {led.get('unattributed')}, "
              f"peak {led.get('peak_bytes', 0)} B")
        for name, nbytes in sorted(led.get("entries", {}).items(),
                                   key=lambda kv: -kv[1]):
            print(f"  {name:<40}{nbytes:>16}")
    for label, prof in doc.get("profiles", {}).items():
        print_mem_profile(label, prof, top)
    oom = doc.get("last_oom")
    if oom:
        print(f"mem_oom: {oom.get('label', '?')} — "
              f"{oom.get('error', '')[:160]}")
        for b in oom.get("top_buffers", [])[:top]:
            print(f"  {b.get('instr', '?'):<40}"
                  f"{b.get('opcode', ''):<16}"
                  f"{b.get('bytes', b.get('bytes_raw', 0)):>14.4g}  "
                  f"{b.get('op', '')}")


def mem_cmd(path: str, top: int, temp_bytes: Optional[int],
            as_json: bool) -> int:
    doc = load_memory_doc(path, temp_bytes)
    if not doc["ledger"] and not doc["profiles"] \
            and not doc["last_oom"]:
        print(f"tracetool mem: no memory artifacts found in {path} "
              "(need a flight bundle / memory.json, a BENCH JSON with "
              "detail.memory, a trace/snapshot JSON, or a raw HLO "
              "dump)", file=sys.stderr)
        return 1
    if as_json:
        memprof = load_memprof()
        print(json.dumps({
            "ledger": doc["ledger"],
            "profiles": {lab: memprof.trim_profile(p, top)
                         for lab, p in doc["profiles"].items()},
            "last_oom": doc["last_oom"],
        }))
        return 0
    print_memory(doc, top)
    return 0


# ---------------------------------------------------------------------------
# numerics (numeric-health post-mortem)
# ---------------------------------------------------------------------------

def load_numerics_doc(path: str) -> Optional[dict]:
    """The numeric-health document from any artifact that carries one:
    a flight bundle DIRECTORY or its numerics.json
    (obs/numerics.numerics_doc), a BENCH JSON (detail.numerics), a
    trace JSON (otherData.snapshot.numerics) or a bare
    obs.snapshot().  Returns None when nothing is found."""
    if os.path.isdir(path):
        path = os.path.join(path, "numerics.json")
    with open(path) as f:
        doc = json.load(f)
    found: List[dict] = []

    def walk(node):
        if not isinstance(node, dict):
            return
        if node.get("mode") in ("off", "on", "bisect") \
                and ("ops" in node or "ops_tracked" in node
                     or "overhead_pct" in node):
            found.append(node)
            return
        for v in node.values():
            if isinstance(v, dict):
                walk(v)

    walk(doc)
    return found[0] if found else None


def print_numerics(doc: dict, top: int) -> None:
    print(f"mode: {doc.get('mode')}  "
          f"first_nonfinite_step: {doc.get('first_nonfinite_step')}  "
          f"loss_scale: {doc.get('loss_scale')}")
    if "overhead_pct" in doc:  # BENCH detail.numerics summary
        print(f"stats-mode overhead: {doc.get('overhead_pct')}% "
              f"(step_ms {doc.get('step_ms_off')} -> "
              f"{doc.get('step_ms_on')})")
    health = doc.get("health") or {}
    if health:
        print("health gauges:")
        for name in sorted(health):
            print(f"  {name:<40}{health[name]:>14.6g}")
    rows = doc.get("ops") or doc.get("nonfinite_ops") or []
    bad = [r for r in rows
           if r.get("nan_count", 0) + r.get("inf_count", 0) > 0]
    if bad:
        print(f"non-finite ops ({len(bad)}):")
        print(f"{'provenance':<52}{'var':<24}{'nan':>8}{'inf':>8}"
              f"{'absmax':>12}")
        for r in bad[:top]:
            print(f"{r.get('provenance', '?'):<52}"
                  f"{r.get('var', ''):<24}"
                  f"{r.get('nan_count', 0):>8}"
                  f"{r.get('inf_count', 0):>8}"
                  f"{r.get('absmax', 0.0):>12.4g}")
    elif rows:
        print(f"all {len(rows)} instrumented op outputs finite")
    b = doc.get("bisection")
    if b:
        if b.get("found"):
            op = b["op"]
            print(f"bisection: FIRST non-finite op is "
                  f"{op.get('provenance')} (type={op.get('type')}, "
                  f"var={op.get('var')}, nan={op.get('nan_count')}, "
                  f"inf={op.get('inf_count')}) at step {b.get('step')}"
                  f" after {b.get('ops_replayed')} op(s)")
            passes = op.get("passes") or []
            if passes:
                print(f"  rewritten by pass(es): {','.join(passes)}")
            stack = op.get("op_callstack")
            if stack:
                tail = stack[-3:] if isinstance(stack, list) else [stack]
                for fr in tail:
                    print(f"  {str(fr).strip()}")
            for i in op.get("inputs", []):
                print(f"  input {i.get('slot')}/{i.get('var')}: "
                      f"nan={i.get('nan_count')} "
                      f"absmax={i.get('absmax')}")
        elif b.get("replay_error"):
            print(f"bisection: replay failed at "
                  f"{(b.get('failed_op') or {}).get('provenance')}: "
                  f"{b['replay_error']}")
        else:
            print(f"bisection: no non-finite output in "
                  f"{b.get('ops_replayed')} replayed op(s)")
    hit = doc.get("last_hit")
    if hit:
        print(f"last hit: step {hit.get('step')} vars {hit.get('hits')}")


def numerics_cmd(path: str, top: int, as_json: bool) -> int:
    doc = load_numerics_doc(path)
    if doc is None:
        print(f"tracetool numerics: no numeric-health document found "
              f"in {path} (need a flight bundle / numerics.json, a "
              f"BENCH JSON with detail.numerics, or a trace/snapshot "
              f"JSON)", file=sys.stderr)
        return 1
    if as_json:
        print(json.dumps(doc))
        return 0
    print_numerics(doc, top)
    return 0


# ---------------------------------------------------------------------------
# metrics (live-telemetry dump post-mortem)
# ---------------------------------------------------------------------------

def load_metrics_doc(path: str) -> dict:
    """A telemetry JSON dump: Collector.to_json() output — a flight
    bundle's series.json, or the /metrics?format=json body saved to a
    file.  A flight-bundle DIRECTORY is accepted too (reads its
    series.json)."""
    if os.path.isdir(path):
        path = os.path.join(path, "series.json")
    with open(path) as f:
        doc = json.load(f)
    if "series" not in doc:
        raise ValueError(f"{path}: not a telemetry dump (no 'series'; "
                         "expected Collector.to_json() output)")
    return doc


def print_metrics(doc: dict, rows: List[dict],
                  fired: List[dict]) -> None:
    health = doc.get("health") or {}
    print(f"samples: {doc.get('samples', '?')} every "
          f"{doc.get('sample_s', '?')} s, series: {len(rows)}, "
          f"drops: {doc.get('drops', 0)}, sampler overhead: "
          f"{doc.get('sampler_overhead_ms', 0)} ms total")
    if health:
        state = "healthy" if health.get("healthy") else "UNHEALTHY"
        print(f"health at dump: {state}"
              + (f" ({health['reason']})" if health.get("reason")
                 else ""))
    print(f"{'metric':<36}{'kind':>8}{'count':>7}{'min':>12}"
          f"{'mean':>12}{'max':>12}{'last':>12}{'drop':>6}")
    for r in rows:
        print(f"{r['metric']:<36}{r['kind']:>8}{r['count']:>7}"
              f"{r['min']:>12.4g}{r['mean']:>12.4g}{r['max']:>12.4g}"
              f"{r['last']:>12.4g}{r['dropped']:>6}")
    if fired:
        print("watchdog replay: rules that would have fired:")
        for f in fired:
            print(f"  [{f['rule']}] at sample {f['sample']}: "
                  f"{f['reason']}")
    else:
        print("watchdog replay: no rule fires over this series")


def metrics_cmd(path: str, as_json: bool) -> int:
    telemetry = load_telemetry()
    doc = load_metrics_doc(path)
    rows = telemetry.series_stats(doc)
    fired = telemetry.replay_rules(doc)
    if as_json:
        print(json.dumps({"stats": rows, "fired": fired,
                          "health": doc.get("health")}))
    else:
        print_metrics(doc, rows, fired)
    return 0


# ---------------------------------------------------------------------------
# selftest
# ---------------------------------------------------------------------------

_SELFTEST_HLO = """\
HloModule selftest, entry_computation_layout={(f32[64,128]{1,0})->f32[64,64]{1,0}}

%fused_computation (param_0: f32[64,64]) -> f32[64,64] {
  %param_0 = f32[64,64]{1,0} parameter(0)
  %constant.1 = f32[] constant(0)
  %broadcast.1 = f32[64,64]{1,0} broadcast(f32[] %constant.1), dimensions={}, metadata={op_name="jit(f)/program#7/block0/op2:relu[pass=layout_optimize]/max"}
  ROOT %maximum.1 = f32[64,64]{1,0} maximum(f32[64,64]{1,0} %param_0, f32[64,64]{1,0} %broadcast.1), metadata={op_name="jit(f)/program#7/block0/op2:relu[pass=layout_optimize]/max"}
}

ENTRY %main (Arg_0.1: f32[64,128]) -> f32[64,64] {
  %Arg_0.1 = f32[64,128]{1,0} parameter(0)
  %constant.9 = f32[128,64]{1,0} constant({...})
  %transpose.2 = f32[128,64]{0,1} transpose(f32[128,64]{1,0} %constant.9), dimensions={1,0}
  %dot.4 = f32[64,64]{1,0} dot(f32[64,128]{1,0} %Arg_0.1, f32[128,64]{0,1} %transpose.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/program#7/block0/op1:mul/dot_general"}
  %all-reduce = f32[64,64]{1,0} all-reduce(f32[64,64]{1,0} %dot.4), replica_groups={}, to_apply=%region_0, metadata={op_name="jit(f)/program#7/block0/op3:c_allreduce_sum/psum"}
  ROOT %relu_fusion = f32[64,64]{1,0} fusion(f32[64,64]{1,0} %all-reduce), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/program#7/block0/op2:relu[pass=layout_optimize]/max"}
}
"""


def _opprof_selftest_checks() -> List[tuple]:
    """The op-profile half of the selftest: walk a synthetic HLO dump
    through opprof (loaded by file path) and assert the attribution
    invariants top-ops relies on."""
    opprof = load_opprof()
    prof = opprof.profile_hlo_text(_SELFTEST_HLO, label="selftest",
                                   cost={"flops": 2.0 * 64 * 64 * 128,
                                         "bytes_accessed": 0.0})
    by_op = {r["op"]: r for r in prof["rows"]}
    dot = by_op.get("program#7/block0/op1:mul", {})
    relu = by_op.get(
        "program#7/block0/op2:relu[pass=layout_optimize]", {})
    coll = by_op.get("program#7/block0/op3:c_allreduce_sum", {})
    top = opprof.top_ops(prof, 3, "flops")
    return [
        ("op-profile: dot attributed with K-scaled flops",
         dot.get("flops_raw") == 2.0 * 64 * 64 * 128),
        ("op-profile: pass tag survives into the table",
         relu.get("source", {}).get("passes") == ["layout_optimize"]),
        ("op-profile: fusion membership counted",
         relu.get("fusions", 0) >= 1),
        ("op-profile: metadata-less transpose inherits its consumer",
         dot.get("transposes", 0) >= 1),
        ("op-profile: collective bytes attributed (ring-true: "
         "all-reduce moves ~2x its shape over the wire)",
         coll.get("collective_bytes", 0) == 2 * 64 * 64 * 4),
        ("op-profile: >=95% of flops attributed",
         prof["attributed_flops_pct"] >= 95.0),
        ("op-profile: normalized total matches cost_analysis",
         abs(prof["total_flops"] - 2.0 * 64 * 64 * 128) < 1e-6),
        ("top-ops: dot ranks first by flops",
         bool(top) and top[0]["op"] == "program#7/block0/op1:mul"),
    ]

def _devprof_selftest_checks() -> List[tuple]:
    """The measured-device-time half of the selftest: synthetic CPU
    thunk planes through the tiered join against the _SELFTEST_HLO
    profile, and the roofline verdicts — all by file path, no jax.
    (A chip's trace goes through `devprof.device_time`, which
    tests/test_devprof.py checks on a recorded v5e trace.)"""
    devprof = load_devprof()
    opprof = load_opprof()
    checks: List[tuple] = []

    prof = opprof.profile_hlo_text(_SELFTEST_HLO, label="selftest",
                                   cost={"flops": 2.0 * 64 * 64 * 128,
                                         "bytes_accessed": 64 * 64 * 8.0})
    profiles = {"selftest": prof}

    # one device thunk line
    space = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "tf_XLATfrtCpuClient/7", "events": [
             {"name": "ThunkExecutor::Execute (wait for completion)",
              "offset_ps": 0, "duration_ps": 9_000_000, "stats": {}},
             {"name": "dot.4", "offset_ps": 200_000,
              "duration_ps": 4_000_000, "stats": {"program_id": 7}},
             {"name": "relu_fusion", "offset_ps": 4_400_000,
              "duration_ps": 3_000_000, "stats": {"program_id": 7}},
             {"name": "all-reduce", "offset_ps": 7_600_000,
              "duration_ps": 2_000_000, "stats": {"program_id": 7}},
             {"name": "custom-call.9", "offset_ps": 9_800_000,
              "duration_ps": 1_000_000, "stats": {"program_id": 7}},
         ]},
    ]}]}

    join = devprof.join_events(space, profiles, runs=2)
    checks.append(("devprof: containers excluded from measured time",
                   join["measured_ns"] == 10_000.0
                   and join["events"] == 4 and join["runs"] == 2))
    by_op = join["ops"]
    checks.append(("devprof: thunks join their instructions by name",
                   by_op.get("program#7/block0/op1:mul",
                             {}).get("time_ns") == 4_000.0
                   and by_op.get(
                       "program#7/block0/op2:relu[pass=layout_optimize]",
                       {}).get("time_ns") == 3_000.0
                   and by_op.get("program#7/block0/op3:c_allreduce_sum",
                                 {}).get("time_ns") == 2_000.0))
    checks.append(("devprof: unknown thunk lands in an explicit "
                   "unattributed bin",
                   by_op.get(devprof.UNATTRIBUTED,
                             {}).get("time_ns") == 1_000.0
                   and abs(join["attributed_pct"] - 90.0) < 1e-9))

    roof = devprof.compute_roofline(join, profiles, "cpu-fallback",
                                    pf=2e11, pb=5e10)
    rops = {r["op"]: r for r in roof["ops"]}
    dot_r = rops.get("program#7/block0/op1:mul", {})
    checks.append(("devprof: roofline verdicts + pass tags",
                   dot_r.get("bound") == "compute-bound"
                   and dot_r.get("mfu_pct", 0.0) > 0.0
                   and rops.get(devprof.UNATTRIBUTED,
                                {}).get("bound") == devprof.UNATTRIBUTED
                   and "layout_optimize" in rops.get(
                       "program#7/block0/op2:relu[pass=layout_optimize]",
                       {}).get("passes", [])))
    return checks


def _memprof_selftest_checks() -> List[tuple]:
    """The memory half of the selftest: walk the synthetic HLO through
    memprof (loaded by file path), assert the attribution +
    normalization invariants, then the ledger/gauge/OOM-report math
    over injected device stats — no jax anywhere."""
    memprof = load_memprof()
    opprof = load_opprof()
    checks: List[tuple] = []

    op_prof = opprof.profile_hlo_text(_SELFTEST_HLO, label="selftest")
    prof = memprof.profile_memory_text(
        _SELFTEST_HLO, label="selftest",
        memory={"temp_bytes": 40960},
        instr_prov=op_prof.get("instr_prov"))
    by_op = {r["op"]: r for r in prof["rows"]}
    dot = by_op.get("program#7/block0/op1:mul", {})
    relu = by_op.get(
        "program#7/block0/op2:relu[pass=layout_optimize]", {})
    checks.append(("memprof: dot owns its buffer AND its metadata-less "
                   "transpose's (consumer inheritance via instr_prov)",
                   dot.get("temp_bytes_raw") == 49152.0
                   and dot.get("buffers") == 2))
    checks.append(("memprof: fused interiors excluded — one boundary "
                   "buffer per fusion",
                   relu.get("buffers") == 1
                   and relu.get("temp_bytes_raw") == 16384.0))
    checks.append(("memprof: rows normalized to the compiler's temp "
                   "total",
                   abs(prof["temp_bytes"] - 40960.0) < 1e-6
                   and abs(sum(r["temp_bytes"] for r in prof["rows"])
                           - 40960.0) < 1e-6))
    checks.append(("memprof: >=80% of temp bytes attributed",
                   prof["attributed_temp_pct"] >= 80.0))
    bare = memprof.profile_memory_text(_SELFTEST_HLO)
    unattr = {r["op"]: r for r in bare["rows"]}.get(
        memprof.UNATTRIBUTED)
    checks.append(("memprof: provenance-less buffer lands in the "
                   "explicit unattributed bin",
                   unattr is not None
                   and unattr["temp_bytes_raw"] == 32768.0))

    memprof.reset_ledger()
    try:
        memprof.set_entry("scope_bytes", 1000)
        memprof.add_entry("scope_bytes", 500)
        memprof.register_source("kv",
                                lambda: {"kv_cache_bytes": 300})
        memprof.set_device_stats_fn(
            lambda: {"bytes_in_use": 5000, "bytes_limit": 10000,
                     "peak_bytes_in_use": 6000})
        g = memprof.ledger_gauges()
        checks.append(("memprof: gauges fold push + pull ledger "
                       "entries",
                       g.get("ledger_total_bytes") == 1800.0
                       and g.get("ledger_scope_bytes") == 1500.0
                       and g.get("ledger_kv_cache_bytes") == 300.0))
        checks.append(("memprof: device truth surfaces as hbm_* gauges",
                       g.get("hbm_bytes_in_use") == 5000.0
                       and g.get("hbm_limit_bytes") == 10000.0
                       and g.get("hbm_peak_bytes") == 6000.0))
        led = memprof.memory_ledger()
        checks.append(("memprof: ledger reconciles with an explicit "
                       "unattributed residual",
                       led["bytes_in_use"] == 5000
                       and led["unattributed"] == 3200))
        memprof.register_profile("selftest", prof)
        oom = memprof.oom_report("selftest",
                                 "RESOURCE_EXHAUSTED: 1.5G > 1G")
        checks.append(("memprof: oom report carries ledger + top "
                       "static buffers",
                       oom["kind"] == "mem_oom"
                       and oom["ledger"]["total"] == 1800
                       and len(oom["top_buffers"]) > 0))
        evs = memprof.chrome_counter_events()
        checks.append(("memprof: ledger samples render as Chrome "
                       "counter events",
                       bool(evs) and evs[-1]["ph"] == "C"
                       and evs[-1]["args"].get("scope_bytes") == 1500))
    finally:
        memprof.reset_ledger()
        memprof.reset_profiles()
        memprof.reset_oom()
    return checks


def _numerics_selftest_checks() -> List[tuple]:
    """Numeric-health layer (ISSUE 15): mode parsing, the synthetic
    stats-array attribution fold, the bisection-order invariant and
    the disabled-mode contract — all through the pure stdlib helpers,
    no jax/numpy import."""
    numerics = load_numerics()
    keys = [
        (numerics.KIND_OP,
         "program#1/block0/op0:conv2d[pass=layout_nhwc]", "conv_out"),
        (numerics.KIND_OP, "program#1/block0/op1:log", "log_out"),
        (numerics.KIND_OP, "program#1/block0/op2:softmax", "sm_out"),
        (numerics.KIND_HEALTH, "grad_norm_total", ""),
    ]
    rows = [
        [0, 0, 3.5, 9.0],     # clean conv output
        [4, 0, 88.0, 12.0],   # the FIRST non-finite op (4 nans)
        [2, 1, 5.0, 2.0],     # a later casualty — must NOT win
        [0, 0, 7.25, 7.25],   # health row (value in absmax/l2 cols)
    ]
    ops, health = numerics.fold_stats(keys, rows)
    first = numerics.first_nonfinite(keys, rows)
    clean = numerics.first_nonfinite(keys[:1], rows[:1])
    health_only = numerics.first_nonfinite([keys[3]], [[9, 9, 1, 1]])
    prov = numerics.parse_provenance(keys[0][1])
    return [
        ("numerics: mode parsing normalizes",
         numerics.parse_mode("ON") == "on"
         and numerics.parse_mode("Bisect") == "bisect"
         and numerics.parse_mode("1") == "on"
         and numerics.parse_mode(None) == "off"
         and numerics.parse_mode("garbage") == "off"),
        ("numerics: synthetic stats fold attributes per op",
         len(ops) == 3 and ops[1]["provenance"] == keys[1][1]
         and ops[1]["nan_count"] == 4 and ops[2]["inf_count"] == 1
         and ops[0]["absmax"] == 3.5 and ops[0]["l2"] == 9.0),
        ("numerics: health rows fold to gauges, not op rows",
         health == {"grad_norm_total": 7.25}),
        ("numerics: bisection-order invariant — FIRST flagged op wins",
         first is not None and first["provenance"] == keys[1][1]
         and first["index"] == 1 and first["nan_count"] == 4),
        ("numerics: health rows never win the bisection",
         health_only is None),
        ("numerics: clean dispatch bisects to None",
         clean is None),
        ("numerics: provenance parse carries pass tags",
         prov is not None and prov["type"] == "conv2d"
         and prov["passes"] == ["layout_nhwc"] and prov["op"] == 0),
        ("numerics: disabled mode folds to nothing",
         numerics.parse_mode("off") == "off"
         and numerics.fold_stats([], []) == ([], {})
         and numerics.first_nonfinite([], []) is None),
    ]


def _telemetry_selftest_checks() -> List[tuple]:
    """The live-telemetry half of the selftest: drive the collector,
    watchdog and flight recorder (loaded by file path — no jax) over
    scripted sources, then replay the rules from the JSON dump the
    `metrics` subcommand consumes."""
    import shutil as _shutil

    telemetry = load_telemetry()
    checks: List[tuple] = []

    # scripted sources: a healthy ramp, then a step-time spike + a NaN
    state = {"steps": 0, "step_ms": 10.0, "nan_hits": 0}

    def sources():
        state["steps"] += 100
        return {"counters": {"executor_steps_total": state["steps"],
                             "nan_inf_hits_total": state["nan_hits"]},
                "timers_ms": {},
                "gauges": {"step_ms": state["step_ms"],
                           "mfu_pct": 40.0}}

    tmpdir = tempfile.mkdtemp(prefix="tracetool_telemetry_")
    try:
        clock = {"t": 1000.0}
        wd = telemetry.Watchdog(artifacts_dir=tmpdir, keep=2,
                                min_interval_s=30.0,
                                clock=lambda: clock["t"])
        col = telemetry.Collector(sources=sources, sample_s=1.0,
                                  capacity=16, watchdog=wd,
                                  clock=lambda: clock["t"])
        for _ in range(8):
            clock["t"] += 1.0
            col.sample_once()
        checks.append(("telemetry: healthy run fires nothing",
                       wd.healthy and not os.listdir(tmpdir)))
        checks.append(("telemetry: counters sampled as deltas",
                       col.store.vals("executor_steps_total")[1:]
                       == [100.0] * 7))
        checks.append(("telemetry: gauges sampled as levels",
                       col.store.last("step_ms") == 10.0))

        state["step_ms"] = 200.0   # 20x the rolling median
        state["nan_hits"] = 3      # non-finite loss
        clock["t"] += 1.0
        fired = col.sample_once()
        rules = {f["rule"] for f in fired}
        checks.append(("telemetry: step spike + NaN fire the watchdog",
                       {"step_time_spike", "non_finite_loss"} <= rules))
        checks.append(("telemetry: /healthz flips with a reason",
                       not wd.healthy and "step_ms"
                       in (wd.reason or "")))
        bundles = [n for n in os.listdir(tmpdir)
                   if n.startswith(telemetry.BUNDLE_PREFIX)]
        checks.append(("telemetry: flight bundle published",
                       len(bundles) == 1))
        bundle = os.path.join(tmpdir, bundles[0]) if bundles else None
        checks.append(("telemetry: bundle carries reason + series",
                       bundle is not None
                       and os.path.exists(os.path.join(bundle,
                                                       "reason.json"))
                       and os.path.exists(os.path.join(bundle,
                                                       "series.json"))))

        # rate limit: an immediate second anomaly must NOT dump again
        clock["t"] += 1.0
        col.sample_once()
        checks.append(("telemetry: second dump rate-limited",
                       wd.dumps_rate_limited >= 1
                       and wd.bundles_written == 1))
        # past the window: dumps again, retention keeps newest `keep`
        for _ in range(3):
            clock["t"] += 31.0
            col.sample_once()
        bundles = [n for n in os.listdir(tmpdir)
                   if n.startswith(telemetry.BUNDLE_PREFIX)]
        checks.append(("telemetry: GC keeps newest bundles",
                       wd.bundles_written >= 3 and len(bundles) == 2))

        # the metrics-subcommand surface over the same dump
        doc = col.to_json()
        rows = telemetry.series_stats(doc)
        by_name = {r["metric"]: r for r in rows}
        checks.append(("telemetry: series_stats rows complete",
                       by_name.get("step_ms", {}).get("max") == 200.0
                       and by_name.get("executor_steps_total",
                                       {}).get("last") == 100.0))
        replay = {f["rule"] for f in telemetry.replay_rules(doc)}
        checks.append(("telemetry: replay re-fires the rules",
                       {"step_time_spike", "non_finite_loss"}
                       <= replay))
        prom = telemetry.prometheus_text(col)
        checks.append(("telemetry: prometheus text renders",
                       "# TYPE paddle_tpu_step_ms gauge" in prom
                       and "paddle_tpu_healthy 0" in prom
                       and "paddle_tpu_executor_steps_total" in prom))
    finally:
        _shutil.rmtree(tmpdir, ignore_errors=True)
    return checks


def selftest(verbose: bool = True) -> int:
    """Build a 3-thread trace with flow links through the span layer,
    export, summarize, and assert every invariant the real subsystems
    rely on.  Returns 0 on success."""
    tracing = load_tracing()
    tr = tracing.Tracer(capacity=1000)
    tr.enable()

    flows = [tr.new_flow() for _ in range(4)]

    def producer():
        for f in flows:
            with tr.span("feed.stage", flow=f):
                pass

    def consumer():
        for f in flows:
            with tr.span("executor.dispatch", flow=f):
                with tr.span("executor.prepare"):
                    pass

    def completer():
        for f in flows:
            tr.add_span("serving.complete", 0.0, 1e-4, flow=f)

    threads = [threading.Thread(target=fn, name=nm)
               for fn, nm in ((producer, "feed-producer"),
                              (consumer, "serving-dispatch"),
                              (completer, "serving-complete"))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # exception safety: the span must record even when the body raises
    try:
        with tr.span("raises"):
            raise RuntimeError("boom")
    except RuntimeError:
        pass

    fd, path = tempfile.mkstemp(suffix=".trace.json")
    os.close(fd)
    try:
        n = tr.export(path, other_data={
            "snapshot": {"cost": {"device_class": "selftest",
                                  "mfu_pct": 1.0,
                                  "programs": [{"label": "p", "mfu_pct": 1.0,
                                                "hbm_bw_pct": 0.0,
                                                "step_ms": 1.0,
                                                "dispatches": 2}]},
                         "timers_ms": {"ring_full_wait_ms": 1.0}}})
        s = summarize(load_trace(path))
        # 4 stage + 4 dispatch + 4 prepare + 4 complete + 1 raises
        checks = [
            ("span count", n == 17 and s["spans"] == 17),
            ("all three threads present",
             {"feed-producer", "serving-dispatch", "serving-complete"}
             <= {t["name"] for t in s["threads"]}),
            ("flows link across threads",
             s["flows"] == 4 and s["cross_thread_flows"] == 4),
            ("exception-path span recorded",
             any(r["name"] == "raises" for r in s["top_spans"])),
            ("nothing dropped", s["dropped_events"] == 0),
            ("mfu per program surfaced",
             s["mfu_per_program"] and s["mfu_per_program"][0]["mfu_pct"]
             == 1.0),
            ("stall attribution computed",
             s["stall_attribution"] == "compute-bound"),
        ]
        checks += _opprof_selftest_checks()
        checks += _devprof_selftest_checks()
        checks += _memprof_selftest_checks()
        checks += _telemetry_selftest_checks()
        checks += _numerics_selftest_checks()
        failed = [name for name, ok in checks if not ok]
        if verbose:
            for name, ok in checks:
                print(f"  [{'ok' if ok else 'FAIL'}] {name}")
        if failed:
            print(f"tracetool selftest: {len(failed)} check(s) failed: "
                  f"{failed}", file=sys.stderr)
            return 1
        print("tracetool selftest: ok "
              f"({s['spans']} spans, {len(s['threads'])} threads, "
              f"{s['cross_thread_flows']} cross-thread flows)")
        return 0
    finally:
        os.unlink(path)


# ---------------------------------------------------------------------------

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tracetool", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    sub = ap.add_subparsers(dest="cmd")
    p_sum = sub.add_parser("summarize", help="summarize one trace file")
    p_sum.add_argument("trace")
    p_sum.add_argument("--top", type=int, default=15)
    p_sum.add_argument("--json", action="store_true",
                       help="machine-readable output")
    p_diff = sub.add_parser("diff", help="diff two trace files (a -> b)")
    p_diff.add_argument("trace_a")
    p_diff.add_argument("trace_b")
    p_diff.add_argument("--json", action="store_true")
    p_top = sub.add_parser(
        "top-ops", help="per-op cost attribution from a trace/BENCH/"
        "profile JSON or raw HLO dump")
    p_top.add_argument("artifact")
    p_top.add_argument("--top", type=int, default=10)
    p_top.add_argument("--key", default="flops",
                       choices=["flops", "bytes", "transposes",
                                "collective_bytes"])
    p_top.add_argument("--json", action="store_true")
    p_met = sub.add_parser(
        "metrics", help="per-metric stats + watchdog-rule replay over "
        "a telemetry JSON dump (or a flight-bundle dir)")
    p_met.add_argument("dump")
    p_met.add_argument("--json", action="store_true")
    p_roof = sub.add_parser(
        "roofline", help="measured device time per op with roofline "
        "bound verdicts from a devprof/snapshot/trace/BENCH JSON")
    p_roof.add_argument("artifact")
    p_roof.add_argument("--top", type=int, default=10)
    p_roof.add_argument("--json", action="store_true")
    p_mem = sub.add_parser(
        "mem", help="HBM memory post-mortem: ledger + per-op static "
        "temp attribution + mem_oom report from a flight bundle / "
        "BENCH / trace / snapshot JSON or a raw HLO dump")
    p_mem.add_argument("artifact")
    p_mem.add_argument("--top", type=int, default=10)
    p_mem.add_argument("--temp-bytes", type=int, default=None,
                       help="compiler temp total to normalize a raw "
                            "HLO dump against")
    p_mem.add_argument("--json", action="store_true")
    p_num = sub.add_parser(
        "numerics", help="numeric-health post-mortem: top non-finite "
        "ops, health gauges and the first-NaN bisection report from a "
        "flight bundle / numerics.json, a BENCH JSON with "
        "detail.numerics, or a trace/snapshot JSON")
    p_num.add_argument("artifact")
    p_num.add_argument("--top", type=int, default=10)
    p_num.add_argument("--json", action="store_true")
    sub.add_parser("selftest", help="exercise the span layer, the "
                                    "op-profile HLO walk, the devprof "
                                    "xplane parse/join/roofline, the "
                                    "telemetry collector/watchdog, the "
                                    "memprof attribution/ledger and "
                                    "the numerics attribution/"
                                    "bisection helpers end to end")
    args = ap.parse_args(argv)

    if args.cmd == "summarize":
        s = summarize(load_trace(args.trace), top=args.top)
        if args.json:
            print(json.dumps(s))
        else:
            print_summary(s)
        return 0
    if args.cmd == "diff":
        rows = diff_traces(load_trace(args.trace_a),
                           load_trace(args.trace_b))
        if args.json:
            print(json.dumps(rows))
        else:
            print_diff(rows)
        return 0
    if args.cmd == "top-ops":
        return top_ops_cmd(args.artifact, args.top, args.key,
                           args.json)
    if args.cmd == "metrics":
        return metrics_cmd(args.dump, args.json)
    if args.cmd == "roofline":
        return roofline_cmd(args.artifact, args.top, args.json)
    if args.cmd == "mem":
        return mem_cmd(args.artifact, args.top, args.temp_bytes,
                       args.json)
    if args.cmd == "numerics":
        return numerics_cmd(args.artifact, args.top, args.json)
    if args.cmd == "selftest":
        return selftest()
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
