#!/usr/bin/env python
"""bench_diff: the perf-regression gate over BENCH JSON (ISSUE 7).

PR 6 made the bench output machine-readable (cost_analysis-derived MFU,
`device_class` labels, embedded obs snapshot); this tool is the first
ENFORCEMENT layer over that trajectory: diff the current BENCH JSON
against a committed baseline (artifacts/bench_baseline.json) with
per-metric thresholds and fail CI on a regression.

Metrics compared (each only when present in BOTH files):

  mfu              headline value of a *_mfu metric    (drop  > 5% rel)
  step_ms          detail.step_ms                      (rise  > 10% rel)
  resnet50_mfu     detail.resnet50.detail.mfu_pct      (drop  > 5% rel)
  resnet50_step_ms detail.resnet50.detail.step_ms      (rise  > 10% rel)
  serving_p99_ms   headline of serving_p99_latency_ms  (rise  > 15% rel)
  decode_token_ms  detail.decode.decode_token_ms       (rise  > 10% rel
                   — steady-state autoregressive decode-step latency;
                   the fast-decode path must not regress)
  collective_bytes sum of detail.obs.cost.collective_bytes (rise > 10%)
  interior_transposes  detail...layout.interior_transposes (ANY rise)
  op_attribution_pct   detail...op_profile.attributed_flops_pct
                                                       (drop > 5 abs)
  telemetry_overhead_ms  detail.telemetry.sampler_overhead_ms
                         (rise > 50% rel AND > 2 ms abs — the live
                         sampler must stay invisible next to a step)
  devprof_attributed_pct  detail...device_profile.attributed_pct
                          (drop > 5 abs — the measured-time join must
                          keep resolving thunks to Program ops; under
                          cpu-fallback the usual warn-only regime
                          applies)
  optimizer_bytes_per_device  detail.sharding.optimizer_bytes_per_device
                              (ANY rise — the ZeRO layout regressed
                              toward replication)
  hbm_peak_bytes   detail.memory.hbm_peak_bytes        (rise  > 5% rel
                   — the device-memory high-water mark grew; on CPU
                   the field is the framework-side ledger peak and the
                   usual warn-only fallback regime applies)
  numerics_overhead_pct  detail.numerics.overhead_pct  (rise > 50% rel
                         AND > 5 points abs — the per-op numeric-stats
                         collection must stay a fused-reduction tax,
                         not a sync; under cpu-fallback the usual
                         warn-only regime applies)
Exit status: 1 when any regression fires AND the current run is
on-chip; under `device_class: cpu-fallback` (or a stale re-emitted
on-chip record — detail.stale_s / detail.cpu_fallback_now) the gate is
WARN-ONLY (exit 0): CPU-fallback numbers are environment noise, not
perf signal.  --strict fails regardless; --warn-only never fails.

stdlib-only (the tracetool/tpulint idiom) so CI can run it before any
jax import.  `--selftest` proves the gate trips on a synthetic 10% MFU
regression and passes an identical pair.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, List, Optional

# metric -> (direction, relative threshold, absolute floor)
# direction "up" = bigger is better (regression when it DROPS),
# "down" = smaller is better (regression when it RISES).
# The absolute floor suppresses noise-level absolute deltas.
DEFAULT_THRESHOLDS = {
    "mfu": ("up", 0.05, 0.05),
    "step_ms": ("down", 0.10, 0.05),
    "resnet50_mfu": ("up", 0.05, 0.05),
    "resnet50_step_ms": ("down", 0.10, 0.05),
    "serving_p99_ms": ("down", 0.15, 0.5),
    # fast decode (ISSUE 20): steady-state per-token decode-step
    # latency from bench --mode serving detail.decode — a >10% rise
    # means the ragged-kernel / chunked-prefill / lazy-growth path
    # slowed; warn-only under cpu-fallback like everything else
    "decode_token_ms": ("down", 0.10, 0.05),
    "collective_bytes": ("down", 0.10, 1024.0),
    "interior_transposes": ("down", 0.0, 0.0),
    "op_attribution_pct": ("up", 0.0, 5.0),
    "telemetry_overhead_ms": ("down", 0.5, 2.0),
    "devprof_attributed_pct": ("up", 0.0, 5.0),
    # ZeRO guard (ISSUE 13): optimizer state resident per device must
    # never grow — ANY rise means the sharded layout regressed toward
    # replication
    "optimizer_bytes_per_device": ("down", 0.0, 0.0),
    # HBM high-water mark (ISSUE 14): a >5% rise in peak device bytes
    # means some subsystem started holding more than it used to
    "hbm_peak_bytes": ("down", 0.05, 0.0),
    # numeric-stats collection tax (ISSUE 15): stats-on vs stats-off
    # step time must stay a cheap fused reduction — a blowup means a
    # host sync crept into the instrumented lowering.  The 5-point
    # absolute floor keeps the gate from flapping on toy-model noise.
    "numerics_overhead_pct": ("down", 0.5, 5.0),
    # persistent AOT cache (ISSUE 17): first-dispatch latency of a
    # fresh process with a WARM cache — a rise means warm starts
    # stopped hitting the disk cache and fell back to full recompiles.
    # Warn-only under cpu-fallback like everything else (CPU compile
    # times are noisy); the 20-ms floor rides over load-time jitter.
    "cold_start_compile_ms": ("down", 0.25, 20.0),
    # static sharding analyzer (ISSUE 18): the comm_report prediction
    # for the bench model is deterministic for a fixed program/mesh —
    # a drift in predicted wire bytes means the analyzer's cost model
    # or spec resolution changed; a rise in prediction error means it
    # drifted away from what XLA actually inserts
    "predicted_collective_bytes": ("down", 0.10, 1024.0),
    "sharding_pred_err_pct": ("down", 0.5, 10.0),
}

# metrics whose value moves BY DESIGN when FLAGS_quant_collectives
# flips: the baseline comparison is reset rather than gated
_QUANT_RESET_METRICS = frozenset(
    {"collective_bytes", "predicted_collective_bytes",
     "sharding_pred_err_pct"})


def _get(d: dict, *path, default=None):
    cur = d
    for p in path:
        if not isinstance(cur, dict):
            return default
        cur = cur.get(p)
    return cur if cur is not None else default


def extract_metrics(doc: dict) -> Dict[str, float]:
    """Flatten one BENCH JSON into the comparable metric table."""
    out: Dict[str, float] = {}
    metric = str(doc.get("metric", ""))
    value = doc.get("value")
    detail = doc.get("detail") or {}
    if isinstance(value, (int, float)):
        if "_mfu" in metric:
            out["mfu"] = float(value)
        elif metric == "serving_p99_latency_ms":
            out["serving_p99_ms"] = float(value)
    if isinstance(_get(detail, "step_ms"), (int, float)):
        out["step_ms"] = float(detail["step_ms"])
    rd = _get(detail, "resnet50", "detail", default={})
    if isinstance(_get(rd, "mfu_pct"), (int, float)):
        out["resnet50_mfu"] = float(rd["mfu_pct"])
    if isinstance(_get(rd, "step_ms"), (int, float)):
        out["resnet50_step_ms"] = float(rd["step_ms"])
    coll = _get(detail, "obs", "cost", "collective_bytes") \
        or _get(rd, "obs", "cost", "collective_bytes")
    if isinstance(coll, dict) and coll:
        out["collective_bytes"] = float(sum(coll.values()))
    for layout in (_get(rd, "layout"), _get(detail, "layout")):
        it = _get(layout or {}, "interior_transposes")
        if isinstance(it, (int, float)):
            out["interior_transposes"] = float(it)
            break
    for opp in (_get(rd, "op_profile"), _get(detail, "op_profile")):
        ap = _get(opp or {}, "attributed_flops_pct")
        if isinstance(ap, (int, float)):
            out["op_attribution_pct"] = float(ap)
            break
    tel = _get(detail, "telemetry", "sampler_overhead_ms")
    if isinstance(tel, (int, float)):
        out["telemetry_overhead_ms"] = float(tel)
    for dp in (_get(detail, "device_profile"),
               _get(rd, "device_profile")):
        dap = _get(dp or {}, "attributed_pct")
        if isinstance(dap, (int, float)):
            out["devprof_attributed_pct"] = float(dap)
            break
    ob = _get(detail, "sharding", "optimizer_bytes_per_device")
    if isinstance(ob, (int, float)):
        out["optimizer_bytes_per_device"] = float(ob)
    pb = _get(detail, "sharding", "predicted_collective_bytes")
    if isinstance(pb, (int, float)) and pb > 0:
        out["predicted_collective_bytes"] = float(pb)
    pe = _get(detail, "sharding", "prediction", "err_pct")
    if isinstance(pe, (int, float)):
        out["sharding_pred_err_pct"] = float(pe)
    for mem in (_get(detail, "memory"), _get(rd, "memory")):
        hp = _get(mem or {}, "hbm_peak_bytes")
        if isinstance(hp, (int, float)) and hp > 0:
            out["hbm_peak_bytes"] = float(hp)
            break
    num = _get(detail, "numerics", "overhead_pct")
    if isinstance(num, (int, float)):
        out["numerics_overhead_pct"] = float(num)
    cs = _get(detail, "fleet", "cold_start", "cold_start_compile_ms")
    if isinstance(cs, (int, float)):
        out["cold_start_compile_ms"] = float(cs)
    dt = _get(detail, "decode", "decode_token_ms")
    if isinstance(dt, (int, float)) and dt > 0:
        out["decode_token_ms"] = float(dt)
    return out


def is_fallback(doc: dict) -> bool:
    """Whether the current run's numbers came from a cpu-fallback (or a
    re-emitted stale on-chip record) — warn-only regimes."""
    detail = doc.get("detail") or {}
    if str(_get(detail, "device_class", default="")) == "cpu-fallback":
        return True
    if "stale_s" in detail or "cpu_fallback_now" in detail:
        return True
    return str(doc.get("metric", "")).endswith("_cpu")


def quant_stamp(doc: dict) -> str:
    """The FLAGS_quant_collectives value stamped into BENCH
    detail.sharding (bench.py).  Missing stamp == 'off' so pre-stamp
    baselines compare cleanly."""
    return str(_get(doc, "detail", "sharding", "quant_collectives",
                    default="off") or "off")


def diff(baseline: dict, current: dict,
         thresholds: Optional[dict] = None) -> List[dict]:
    """Rows for every shared metric; each carries a `regressed` bool."""
    thresholds = thresholds or DEFAULT_THRESHOLDS
    base_m = extract_metrics(baseline)
    cur_m = extract_metrics(current)
    rows: List[dict] = []
    b_q, c_q = quant_stamp(baseline), quant_stamp(current)
    for name, (direction, rel, floor) in thresholds.items():
        if name not in base_m or name not in cur_m:
            continue
        b, c = base_m[name], cur_m[name]
        if name in _QUANT_RESET_METRICS and b_q != c_q:
            # quantization-aware baseline reset (docs/spmd.md): a
            # deliberate FLAGS_quant_collectives flip moves wire bytes
            # ~4x BY DESIGN in either direction — the comparison is
            # meaningless until a baseline with the new stamp lands
            rows.append({"metric": name, "baseline": b, "current": c,
                         "delta": round(c - b, 4), "rel_pct": 0.0,
                         "direction": direction, "regressed": False,
                         "note": f"quant_collectives {b_q}->{c_q}: "
                                 "baseline reset, not compared"})
            continue
        delta = c - b
        bad = delta < 0 if direction == "up" else delta > 0
        magnitude = abs(delta)
        rel_delta = magnitude / abs(b) if b else (1.0 if magnitude
                                                 else 0.0)
        regressed = bool(bad and magnitude > floor
                         and rel_delta > rel)
        rows.append({"metric": name, "baseline": b, "current": c,
                     "delta": round(delta, 4),
                     "rel_pct": round(rel_delta * 100.0, 2),
                     "direction": direction, "regressed": regressed})
    return rows


def _load(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    # driver-wrapper files (BENCH_r*.json) hold the bench line under
    # "parsed"; accept both shapes
    if "metric" not in doc and isinstance(doc.get("parsed"), dict):
        doc = doc["parsed"]
    if "metric" not in doc:
        raise ValueError(f"{path}: not a BENCH JSON (no 'metric')")
    return doc


def run_gate(baseline_path: str, current_path: str, strict: bool,
             warn_only: bool, as_json: bool) -> int:
    baseline = _load(baseline_path)
    current = _load(current_path)
    rows = diff(baseline, current)
    fallback = is_fallback(current)
    regressions = [r for r in rows if r["regressed"]]
    enforce = (strict or not fallback) and not warn_only

    if as_json:
        print(json.dumps({"rows": rows, "fallback": fallback,
                          "enforced": enforce,
                          "regressions": len(regressions)}))
    else:
        print(f"{'metric':<22}{'baseline':>14}{'current':>14}"
              f"{'delta':>12}{'rel%':>8}  verdict")
        for r in rows:
            verdict = "REGRESSED" if r["regressed"] else \
                "skipped" if r.get("note") else "ok"
            print(f"{r['metric']:<22}{r['baseline']:>14.3f}"
                  f"{r['current']:>14.3f}{r['delta']:>12.3f}"
                  f"{r['rel_pct']:>8.2f}  {verdict}")
        if not rows:
            print("bench_diff: no comparable metrics "
                  "(different benchmark variants?)")
        mode = "ENFORCING" if enforce else \
            "warn-only (cpu-fallback run)" if fallback else "warn-only"
        print(f"bench_diff: {len(regressions)} regression(s), "
              f"mode: {mode}")
    return 1 if regressions and enforce else 0


# ---------------------------------------------------------------------------
# selftest
# ---------------------------------------------------------------------------

def _synthetic(mfu: float, step_ms: float, transposes: int = 0,
               coll_bytes: int = 4096, device_class: str = "tpu",
               telemetry_ms: float = 0.5,
               devprof_pct: float = 95.0,
               opt_bytes: int = 65536,
               hbm_peak: int = 1 << 30,
               numerics_pct: float = 8.0,
               quant: str = "off",
               cold_start_ms: float = 50.0,
               pred_bytes: int = 411720,
               pred_err: float = 15.0,
               decode_ms: float = 1.0) -> dict:
    return {
        "metric": "bert_base_pretrain_mfu",
        "value": mfu, "unit": "%", "vs_baseline": mfu / 45.0,
        "detail": {
            "device_class": device_class,
            "step_ms": step_ms,
            "sharding": {"mesh_axes": {"data": 2, "fsdp": 2, "tp": 2},
                         "optimizer_bytes_per_device": opt_bytes,
                         "specs_applied": 6,
                         "quant_collectives": quant,
                         "predicted_collective_bytes": pred_bytes,
                         "prediction": {"predicted_total": pred_bytes,
                                        "measured_total": pred_bytes,
                                        "err_pct": pred_err}},
            "telemetry": {"sampler_overhead_ms": telemetry_ms,
                          "samples": 50, "drops": 0,
                          "rules_fired": 0},
            "device_profile": {"attributed_pct": devprof_pct,
                               "capture_ms": 40.0, "runs": 2},
            "memory": {"hbm_peak_bytes": hbm_peak,
                       "ledger_total_bytes": hbm_peak // 2,
                       "static_temp_bytes": hbm_peak // 8},
            "numerics": {"mode": "on", "overhead_pct": numerics_pct,
                         "ops_tracked": 25, "nonfinite_ops_total": 0,
                         "grad_norm_total": 0.5},
            "obs": {"cost": {"collective_bytes":
                             {"c_allreduce_sum": coll_bytes}}},
            "fleet": {"cold_start":
                      {"cold_start_compile_ms": cold_start_ms}},
            "decode": {"decode_token_ms": decode_ms,
                       "decode_token_p99_ms": decode_ms * 1.5,
                       "prefill_chunk_ms": 0.3,
                       "ttft_long_prompt_ms": 10.0,
                       "kv_pages_per_seq": 13.0},
            "resnet50": {"metric": "resnet50_images_per_sec_per_chip",
                         "value": 1000.0,
                         "detail": {"mfu_pct": 30.0, "step_ms": 50.0,
                                    "layout": {"interior_transposes":
                                               transposes}}},
        },
    }


def selftest(verbose: bool = True) -> int:
    base = _synthetic(mfu=42.0, step_ms=100.0)
    checks = []

    # 1. identical pair passes
    rows = diff(base, base)
    checks.append(("identical pair passes",
                   rows and not any(r["regressed"] for r in rows)))
    # 2. a 10% MFU drop trips the gate on-chip
    cur = _synthetic(mfu=42.0 * 0.9, step_ms=100.0)
    rows = diff(base, cur)
    checks.append(("10% MFU regression fires",
                   any(r["metric"] == "mfu" and r["regressed"]
                       for r in rows)))
    checks.append(("on-chip run enforces", not is_fallback(cur)))
    # 3. the same drop under cpu-fallback is warn-only
    cur_cpu = _synthetic(mfu=42.0 * 0.9, step_ms=100.0,
                         device_class="cpu-fallback")
    checks.append(("cpu-fallback is warn-only", is_fallback(cur_cpu)))
    # 4. a within-threshold wiggle does not fire
    cur_ok = _synthetic(mfu=42.0 * 0.98, step_ms=103.0)
    rows = diff(base, cur_ok)
    checks.append(("2% wiggle passes",
                   not any(r["regressed"] for r in rows)))
    # 5. step_ms rise fires
    cur_slow = _synthetic(mfu=42.0, step_ms=125.0)
    rows = diff(base, cur_slow)
    checks.append(("25% step_ms rise fires",
                   any(r["metric"] == "step_ms" and r["regressed"]
                       for r in rows)))
    # 6. any new interior transpose fires (the NHWC win is guarded)
    cur_tr = _synthetic(mfu=42.0, step_ms=100.0, transposes=2)
    rows = diff(base, cur_tr)
    checks.append(("new interior transpose fires",
                   any(r["metric"] == "interior_transposes"
                       and r["regressed"] for r in rows)))
    # 7. collective bytes growth fires (the EQuARX guard direction)
    cur_coll = _synthetic(mfu=42.0, step_ms=100.0, coll_bytes=16384)
    rows = diff(base, cur_coll)
    checks.append(("4x collective bytes fires",
                   any(r["metric"] == "collective_bytes"
                       and r["regressed"] for r in rows)))
    # 8. telemetry sampler-overhead blowup fires; a sub-floor wiggle
    # does not (the sampler gate must not flap on sub-ms noise)
    cur_tel = _synthetic(mfu=42.0, step_ms=100.0, telemetry_ms=5.0)
    rows = diff(base, cur_tel)
    checks.append(("10x telemetry overhead fires",
                   any(r["metric"] == "telemetry_overhead_ms"
                       and r["regressed"] for r in rows)))
    cur_tel_ok = _synthetic(mfu=42.0, step_ms=100.0, telemetry_ms=1.2)
    rows = diff(base, cur_tel_ok)
    checks.append(("sub-floor telemetry wiggle passes",
                   not any(r["metric"] == "telemetry_overhead_ms"
                           and r["regressed"] for r in rows)))
    # 9. a >5-point drop in MEASURED attribution fires (the devprof
    # join decayed — a renamed pass or runtime renumbering change);
    # a 3-point wiggle stays under the absolute floor
    cur_dev = _synthetic(mfu=42.0, step_ms=100.0, devprof_pct=80.0)
    rows = diff(base, cur_dev)
    checks.append(("devprof attribution drop fires",
                   any(r["metric"] == "devprof_attributed_pct"
                       and r["regressed"] for r in rows)))
    cur_dev_ok = _synthetic(mfu=42.0, step_ms=100.0, devprof_pct=92.0)
    rows = diff(base, cur_dev_ok)
    checks.append(("devprof attribution wiggle passes",
                   not any(r["metric"] == "devprof_attributed_pct"
                           and r["regressed"] for r in rows)))
    # 10. ANY optimizer-bytes-per-device rise fires (ZeRO layout
    # regressed toward replication); equal bytes pass
    cur_opt = _synthetic(mfu=42.0, step_ms=100.0, opt_bytes=65536 * 4)
    rows = diff(base, cur_opt)
    checks.append(("optimizer bytes-per-device rise fires",
                   any(r["metric"] == "optimizer_bytes_per_device"
                       and r["regressed"] for r in rows)))
    rows = diff(base, _synthetic(mfu=42.0, step_ms=100.0))
    checks.append(("equal optimizer bytes pass",
                   not any(r["metric"] == "optimizer_bytes_per_device"
                           and r["regressed"] for r in rows)))
    # 11. a >5% HBM-peak rise fires (some subsystem holds more than it
    # used to); an equal peak and a 3% wiggle pass
    cur_hbm = _synthetic(mfu=42.0, step_ms=100.0,
                         hbm_peak=int((1 << 30) * 1.10))
    rows = diff(base, cur_hbm)
    checks.append(("10% hbm peak rise fires",
                   any(r["metric"] == "hbm_peak_bytes"
                       and r["regressed"] for r in rows)))
    cur_hbm_ok = _synthetic(mfu=42.0, step_ms=100.0,
                            hbm_peak=int((1 << 30) * 1.03))
    rows = diff(base, cur_hbm_ok)
    checks.append(("3% hbm peak wiggle passes",
                   not any(r["metric"] == "hbm_peak_bytes"
                           and r["regressed"] for r in rows)))
    # 12. a numeric-stats overhead blowup fires (a host sync crept
    # into the instrumented lowering); a sub-floor wiggle passes
    cur_num = _synthetic(mfu=42.0, step_ms=100.0, numerics_pct=30.0)
    rows = diff(base, cur_num)
    checks.append(("numerics overhead blowup fires",
                   any(r["metric"] == "numerics_overhead_pct"
                       and r["regressed"] for r in rows)))
    cur_num_ok = _synthetic(mfu=42.0, step_ms=100.0,
                            numerics_pct=11.0)
    rows = diff(base, cur_num_ok)
    checks.append(("sub-floor numerics wiggle passes",
                   not any(r["metric"] == "numerics_overhead_pct"
                           and r["regressed"] for r in rows)))
    # 13. quantization-aware gate (docs/spmd.md): a deliberate
    # FLAGS_quant_collectives flip resets the collective_bytes baseline
    # in BOTH directions — int8->off quadruples wire bytes without
    # firing, off->int8 shrinks them without firing — while an
    # equal-stamp 4x growth (check 7 above) still fires
    base_q = _synthetic(mfu=42.0, step_ms=100.0, coll_bytes=4096,
                        quant="int8")
    cur_unquant = _synthetic(mfu=42.0, step_ms=100.0, coll_bytes=16384,
                             quant="off")
    rows = diff(base_q, cur_unquant)
    checks.append(("int8->off flip: 4x bytes rise does not fire",
                   not any(r["metric"] == "collective_bytes"
                           and r["regressed"] for r in rows)
                   and any(r["metric"] == "collective_bytes"
                           and r.get("note") for r in rows)))
    cur_quant = _synthetic(mfu=42.0, step_ms=100.0, coll_bytes=1024,
                           quant="int8")
    rows = diff(base, cur_quant)
    checks.append(("off->int8 flip: bytes drop does not fire",
                   not any(r["metric"] == "collective_bytes"
                           and r["regressed"] for r in rows)))
    rows = diff(base_q, _synthetic(mfu=42.0, step_ms=100.0,
                                   coll_bytes=16384, quant="int8"))
    checks.append(("equal-stamp (int8) 4x bytes growth still fires",
                   any(r["metric"] == "collective_bytes"
                       and r["regressed"] for r in rows)))
    # 14. warm cold-start blowup fires (the persistent AOT cache
    # stopped hitting and fresh processes recompile from scratch); a
    # sub-floor wiggle passes (load-time jitter must not flap the gate)
    cur_cs = _synthetic(mfu=42.0, step_ms=100.0, cold_start_ms=400.0)
    rows = diff(base, cur_cs)
    checks.append(("warm cold-start blowup fires",
                   any(r["metric"] == "cold_start_compile_ms"
                       and r["regressed"] for r in rows)))
    cur_cs_ok = _synthetic(mfu=42.0, step_ms=100.0, cold_start_ms=60.0)
    rows = diff(base, cur_cs_ok)
    checks.append(("sub-floor cold-start wiggle passes",
                   not any(r["metric"] == "cold_start_compile_ms"
                           and r["regressed"] for r in rows)))
    # 15. stale re-emitted on-chip record is warn-only
    stale = dict(base)
    stale["detail"] = dict(base["detail"], stale_s=1234)
    checks.append(("stale on-chip record is warn-only",
                   is_fallback(stale)))
    # 16. static sharding prediction gates (ISSUE 18): a prediction
    # error blowup fires (the comm_report cost model drifted away from
    # the XLA-inserted collectives); a sub-floor wiggle passes; a
    # predicted-bytes jump fires at an equal quant stamp but resets on
    # a deliberate quant flip (the prediction is quant-aware)
    cur_err = _synthetic(mfu=42.0, step_ms=100.0, pred_err=45.0)
    rows = diff(base, cur_err)
    checks.append(("prediction error blowup fires",
                   any(r["metric"] == "sharding_pred_err_pct"
                       and r["regressed"] for r in rows)))
    cur_err_ok = _synthetic(mfu=42.0, step_ms=100.0, pred_err=19.0)
    rows = diff(base, cur_err_ok)
    checks.append(("sub-floor prediction error wiggle passes",
                   not any(r["metric"] == "sharding_pred_err_pct"
                           and r["regressed"] for r in rows)))
    cur_pb = _synthetic(mfu=42.0, step_ms=100.0,
                        pred_bytes=411720 * 2)
    rows = diff(base, cur_pb)
    checks.append(("predicted collective bytes jump fires",
                   any(r["metric"] == "predicted_collective_bytes"
                       and r["regressed"] for r in rows)))
    cur_pb_q = _synthetic(mfu=42.0, step_ms=100.0,
                          pred_bytes=411720 * 2, quant="int8")
    rows = diff(base, cur_pb_q)
    checks.append(("quant flip resets predicted bytes baseline",
                   not any(r["metric"] == "predicted_collective_bytes"
                           and r["regressed"] for r in rows)))
    # 17. fast-decode gate (ISSUE 20): a >10% decode-step latency rise
    # fires on-chip; a sub-floor wiggle passes; under cpu-fallback the
    # same regression is warn-only (decode timings on CPU are noise)
    cur_dec = _synthetic(mfu=42.0, step_ms=100.0, decode_ms=1.25)
    rows = diff(base, cur_dec)
    checks.append(("25% decode_token_ms rise fires",
                   any(r["metric"] == "decode_token_ms"
                       and r["regressed"] for r in rows)))
    cur_dec_ok = _synthetic(mfu=42.0, step_ms=100.0, decode_ms=1.04)
    rows = diff(base, cur_dec_ok)
    checks.append(("sub-floor decode_token_ms wiggle passes",
                   not any(r["metric"] == "decode_token_ms"
                           and r["regressed"] for r in rows)))
    cur_dec_cpu = _synthetic(mfu=42.0, step_ms=100.0, decode_ms=1.25,
                             device_class="cpu-fallback")
    rows = diff(base, cur_dec_cpu)
    checks.append(("cpu-fallback decode regression is warn-only",
                   any(r["metric"] == "decode_token_ms"
                       and r["regressed"] for r in rows)
                   and is_fallback(cur_dec_cpu)))

    failed = [name for name, ok in checks if not ok]
    if verbose:
        for name, ok in checks:
            print(f"  [{'ok' if ok else 'FAIL'}] {name}")
    if failed:
        print(f"bench_diff selftest: {len(failed)} check(s) failed: "
              f"{failed}", file=sys.stderr)
        return 1
    print(f"bench_diff selftest: ok ({len(checks)} checks)")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="bench_diff", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--baseline",
                    default="artifacts/bench_baseline.json")
    ap.add_argument("--current")
    ap.add_argument("--strict", action="store_true",
                    help="fail on regression even off-chip")
    ap.add_argument("--warn-only", action="store_true",
                    help="never fail, only report")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--selftest", action="store_true")
    args = ap.parse_args(argv)

    if args.selftest:
        return selftest()
    if not args.current:
        ap.error("--current is required (or use --selftest)")
    return run_gate(args.baseline, args.current, args.strict,
                    args.warn_only, args.json)


if __name__ == "__main__":
    sys.exit(main())
