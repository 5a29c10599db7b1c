"""Numeric-health observability: per-op stats, first-NaN bisection,
and training-health telemetry (ISSUE 15).

The observability stack answers "where does the time go" (spans ->
per-op cost -> measured device time), "where do the bytes go" (the
memory ledger) and "is the job healthy" (telemetry watchdog) — this
module answers **"which op broke the numbers, and what led there"**.
Three pieces riding the existing seams:

* **Per-op numeric stats** (`PADDLE_OBS_NUMERICS=on|bisect`): the
  executor arms an instrumented lowering mode where every float op
  output gets one fused device-side reduction — `[nan_count,
  inf_count, absmax, l2]` — stacked into a single stats array the
  dispatch hands to `note_dispatch_stats` as a device reference (host
  deque append, zero sync).  `drain()` materializes pending arrays off
  the hot path (the LazyFetch idiom) and folds them into a bounded
  per-provenance aggregate keyed by the same
  `program#<id>/block<idx>/op<id>:<type>[pass=...]` provenance
  opprof/devprof/memprof attribute through.  The mode joins the
  compile-cache `enabled_signature`, so flag flips are cache misses
  and `off` leaves the compiled HLO byte-identical.

* **First-NaN bisection** (`bisect_nonfinite` / `handle_nan_hit`):
  under `bisect` mode the executor snapshots each dispatch's inputs
  (an async device copy — mutable state is donated); when the async
  NaN monitor reports a hit, the saved feed replays op-by-op eagerly
  and the FIRST op in program order whose output goes non-finite is
  named — provenance, pass tags, construction stack
  (`op_callstack`), and input stats — published as `numerics.json`
  in the `non_finite_loss` flight bundle.

* **Training-health series** (`health_gauges`): `grad_norm_total`,
  per-prefix grad/param norms, `update_ratio` (the step-size-sanity
  gauge) and the AMP `loss_scale` fold into telemetry via
  `default_sources` — NO new thread — feeding the watchdog's
  `grad_norm_spike` and `loss_scale_collapse` rules.

Profiler stat table (asserted complete by tests/test_numerics.py):

| stat                            | kind    | meaning                     |
|---------------------------------|---------|-----------------------------|
| `numerics_steps_total`          | counter | dispatch stat records drained|
| `numerics_nonfinite_ops_total`  | counter | op rows with nan+inf > 0    |
| `numerics_pending_dropped_total`| counter | records evicted pre-drain   |
| `numerics_bisect_runs_total`    | counter | bisection replays executed  |
| `nan_inf_first_step`            | gauge   | step index of the first hit |
| `loss_scale`                    | gauge   | current AMP dynamic scale   |
| `loss_scale_decr_total`         | counter | observed scale decrements   |
| `numerics_drain_ms`             | timer   | stats materialization time  |
"""

from __future__ import annotations

import collections
import os
import re
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

_NUMERICS_ENV = "PADDLE_OBS_NUMERICS"

MODES = ("off", "on", "bisect")

# stat-vector column layout (one row per instrumented op output)
COL_NAN, COL_INF, COL_ABSMAX, COL_L2 = 0, 1, 2, 3
STAT_COLS = ("nan_count", "inf_count", "absmax", "l2")

# key kinds in a dispatch's keys list: ("op", provenance, var_name) for
# instrumented op outputs, ("health", gauge_name, "") for the
# training-health rows appended after them (value in COL_ABSMAX/COL_L2)
KIND_OP = "op"
KIND_HEALTH = "health"

# provenance minted by ops/registry.op_provenance (the opprof format)
PROVENANCE_RE = re.compile(
    r"program#(\d+)/block(\d+)/op(\d+):([A-Za-z0-9_.]+)"
    r"(?:\[pass=([A-Za-z0-9_,.\-]+)\])?")

_PENDING_CAP = 256       # un-drained dispatch records kept
_AGG_CAP = 512           # per-(provenance, var) aggregate rows kept
_INPUT_STAT_CAP = 8      # input rows in a bisection report


def parse_mode(value: Optional[str]) -> str:
    """Normalize a PADDLE_OBS_NUMERICS value to off|on|bisect (unknown
    values are OFF — instrumentation must never arm by accident)."""
    v = (value or "").strip().lower()
    if v == "bisect":
        return "bisect"
    if v in ("on", "1", "true", "stats"):
        return "on"
    return "off"


def mode() -> str:
    """The armed instrumentation mode (late env read: processes that
    set the variable after import still count)."""
    return parse_mode(os.environ.get(_NUMERICS_ENV))


def numerics_enabled() -> bool:
    return mode() != "off"


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


# ---------------------------------------------------------------------------
# Pending queue: the dispatch hot path appends device references only;
# materialization happens in drain() (telemetry sampler / explicit call)
# ---------------------------------------------------------------------------

_LOCK = threading.Lock()
_PENDING: "collections.deque" = collections.deque()
_PENDING_SCALE: "collections.deque" = collections.deque()
_DROPPED = 0

# drained state (all under _LOCK)
_AGG: "collections.OrderedDict[Tuple[str, str], dict]" = \
    collections.OrderedDict()
_AGG_DROPPED = 0
_HEALTH: Dict[str, float] = {}
_LAST_SCALE: Optional[float] = None
_SCALE_DECR = 0
_FIRST_STEP: Optional[int] = None
_LAST_HIT: Optional[dict] = None
_LAST_BISECTION: Optional[dict] = None
_STEPS_DRAINED = 0
_NONFINITE_OPS = 0


def note_dispatch_stats(label: str, keys: List[tuple], stats: Any,
                        step: int) -> None:
    """Hand one dispatch's stacked stats array to the drain queue.
    Called on the dispatch hot path: `stats` stays a DEVICE reference —
    this is a bounded host deque append, never a transfer."""
    global _DROPPED
    with _LOCK:
        if len(_PENDING) >= _PENDING_CAP:
            _PENDING.popleft()
            _DROPPED += 1
        _PENDING.append((label, keys, stats, int(step)))


def note_loss_scale(ref: Any, step: int) -> None:
    """Queue the AMP dynamic loss scale (a device scalar the executor
    copy-detached from the donated state) for the next drain.  Hot
    path: bounded append only."""
    global _DROPPED
    with _LOCK:
        if len(_PENDING_SCALE) >= _PENDING_CAP:
            _PENDING_SCALE.popleft()
            _DROPPED += 1
        _PENDING_SCALE.append((int(step), ref))


def fold_stats(keys: List[tuple], rows: List[List[float]]) \
        -> Tuple[List[dict], Dict[str, float]]:
    """Pure fold of one dispatch's (keys, materialized rows) into
    per-op row dicts + the health-gauge dict, on plain lists."""
    ops: List[dict] = []
    health: Dict[str, float] = {}
    for key, row in zip(keys, rows):
        kind, a, b = key[0], key[1], key[2]
        if kind == KIND_HEALTH:
            health[a] = float(row[COL_ABSMAX])
            continue
        ops.append({
            "provenance": a, "var": b,
            "nan_count": int(row[COL_NAN]),
            "inf_count": int(row[COL_INF]),
            "absmax": float(row[COL_ABSMAX]),
            "l2": float(row[COL_L2]),
        })
    return ops, health


def _agg_row(key: Tuple[str, str]) -> dict:
    return {"provenance": key[0], "var": key[1], "steps": 0,
            "nan_count": 0, "inf_count": 0, "absmax": 0.0,
            "l2_last": 0.0, "last_step": -1,
            "first_nonfinite_step": None}


def _fold_into_agg(ops: List[dict], step: int) -> int:
    """Fold one dispatch's op rows into the bounded aggregate.  Caller
    holds _LOCK.  Returns the number of non-finite op rows."""
    global _AGG_DROPPED
    bad = 0
    for r in ops:
        key = (r["provenance"], r["var"])
        row = _AGG.get(key)
        if row is None:
            if len(_AGG) >= _AGG_CAP:
                _AGG.popitem(last=False)
                _AGG_DROPPED += 1
            row = _AGG[key] = _agg_row(key)
        row["steps"] += 1
        row["nan_count"] += r["nan_count"]
        row["inf_count"] += r["inf_count"]
        row["absmax"] = max(row["absmax"], r["absmax"])
        row["l2_last"] = r["l2"]
        row["last_step"] = step
        if r["nan_count"] + r["inf_count"] > 0:
            bad += 1
            if row["first_nonfinite_step"] is None:
                row["first_nonfinite_step"] = step
    return bad


def drain() -> int:
    """Materialize every queued stats array and fold it into the
    aggregate.  This is the sanctioned LazyFetch-style boundary — it
    runs on the telemetry sampler thread (via health_gauges) or an
    explicit caller, never inside the dispatch; it does NOT book
    executor_sync_count (that counter is the fetch-path contract the
    zero-overhead test pins).  Returns drained record count."""
    global _LAST_SCALE, _SCALE_DECR, _STEPS_DRAINED, _NONFINITE_OPS
    with _LOCK:
        pending = list(_PENDING)
        _PENDING.clear()
        scales = list(_PENDING_SCALE)
        _PENDING_SCALE.clear()
        dropped = _DROPPED
    if not pending and not scales:
        return 0
    import numpy as np  # noqa: PLC0415 - lazy by design (stdlib module scope)

    t0 = time.perf_counter()
    drained = 0
    bad_total = 0
    for label, keys, stats, step in pending:
        try:
            rows = np.asarray(stats)  # sync-ok: numerics drain — the
            # LazyFetch-style materialization boundary, off the
            # dispatch hot path by construction
        except Exception:  # noqa: BLE001 - donated/deleted buffer
            continue
        ops, health = fold_stats(keys, rows.tolist())
        with _LOCK:
            bad_total += _fold_into_agg(ops, step)
            _HEALTH.update(health)
        drained += 1
    for step, ref in scales:
        try:
            v = float(np.asarray(ref)  # sync-ok: numerics drain — AMP
                      .reshape(-1)[0])  # scale scalar, off the
            # dispatch hot path
        except Exception:  # noqa: BLE001 - donated/deleted buffer
            continue
        with _LOCK:
            if _LAST_SCALE is not None and v < _LAST_SCALE:
                _SCALE_DECR += 1
            # the loss_scale SERIES rides the profiler stat below
            # (GAUGE_STATS level) — not _HEALTH, which would double-
            # record the same name through the gauges source
            _LAST_SCALE = v
    with _LOCK:
        _STEPS_DRAINED += drained
        _NONFINITE_OPS += bad_total
        scale = _LAST_SCALE
        decr = _SCALE_DECR
    try:
        from .. import profiler

        profiler.time_add("numerics_drain_ms",
                          (time.perf_counter() - t0) * 1e3)
        if drained:
            profiler.stat_add("numerics_steps_total", drained)
        if bad_total:
            profiler.stat_add("numerics_nonfinite_ops_total", bad_total)
        if dropped:
            # report the cumulative eviction count as a monotone level
            profiler.stat_set("numerics_pending_dropped_total", dropped)
        if scale is not None:
            profiler.stat_set("loss_scale", int(round(scale)))
            profiler.stat_set("loss_scale_decr_total", decr)
    except Exception:  # noqa: BLE001 - standalone load (no profiler)
        pass
    return drained


def health_gauges() -> Dict[str, float]:
    """Drain pending stats, then return the training-health gauges
    (`grad_norm_total`, `update_ratio`, `param_norm_total`, per-prefix
    norms, `loss_scale`).  Runs on the telemetry sampler thread via
    `default_sources` — the drain is this gauge source's read, not a
    hot-path sync."""
    drain()
    with _LOCK:
        return dict(_HEALTH)


# ---------------------------------------------------------------------------
# First-NaN bisection: replay a saved dispatch op-by-op, eagerly
# ---------------------------------------------------------------------------

def _value_stats(arr: Any) -> dict:
    import numpy as np  # noqa: PLC0415 - lazy by design

    a = np.asarray(arr)
    out: Dict[str, Any] = {"shape": list(a.shape), "dtype": str(a.dtype)}
    if a.size and np.issubdtype(a.dtype, np.floating):
        finite = np.isfinite(a)
        masked = np.where(finite, a, 0.0).astype(np.float64)
        out.update({
            "nan_count": int(np.isnan(a).sum()),
            "inf_count": int(np.isinf(a).sum()),
            "absmax": float(np.abs(masked).max()),
            "l2": float(np.sqrt((masked * masked).sum())),
        })
    return out


def _op_report(op, prov: str, env: dict, index: int) -> dict:
    info = parse_provenance(prov) or {}
    inputs = []
    from ..fluid.framework import EMPTY_VAR_NAME

    seen = set()
    for slot, names in op.inputs.items():
        for name in names:
            if name == EMPTY_VAR_NAME or name in seen \
                    or name not in env:
                continue
            seen.add(name)
            if len(inputs) >= _INPUT_STAT_CAP:
                break
            try:
                st = _value_stats(env[name])
            except Exception as e:  # noqa: BLE001 - stats best-effort
                st = {"error": f"{type(e).__name__}: {e}"}
            st.update({"var": name, "slot": slot})
            inputs.append(st)
    return {"provenance": prov, "type": op.type, "index": index,
            "passes": info.get("passes", []),
            "op_callstack": op.attrs.get("op_callstack"),
            "inputs": inputs}


def _replay_block(block, env: dict, seed: int, label: str = "",
                  step: Optional[int] = None) -> dict:
    """Eager op-by-op replay of `block` over concrete `env` values;
    the first op whose float output goes non-finite is the report.
    Forensics: every materialization here IS the bisection."""
    import jax  # noqa: PLC0415 - lazy by design
    import numpy as np  # noqa: PLC0415 - lazy by design

    from ..fluid.framework import EMPTY_VAR_NAME
    from ..ops import registry

    ctx = registry.LowerCtx(jax.random.PRNGKey(int(seed) & 0xFFFFFFFF),
                            block=block)
    ctx.need_vjp |= registry.scan_need_vjp(block)
    report: Dict[str, Any] = {"found": False, "label": label,
                              "step": step, "ops_replayed": 0}
    for i, op in enumerate(block.ops):
        prov = registry.op_provenance(op)
        try:
            registry.lower_op(ctx, op, env)
        except Exception as e:  # noqa: BLE001 - a replay error is
            # itself the finding: the op cannot even re-evaluate
            report.update({"replay_error": f"{type(e).__name__}: {e}",
                           "failed_op": _op_report(op, prov, env, i),
                           "ops_replayed": i + 1})
            return report
        report["ops_replayed"] = i + 1
        for names in op.outputs.values():
            for name in names:
                if name == EMPTY_VAR_NAME or name not in env:
                    continue
                a = np.asarray(env[name])  # sync-ok: bisection replay —
                # materializing each output IS the forensics pass
                if not np.issubdtype(a.dtype, np.floating) or not a.size:
                    continue
                if np.isfinite(a).all():
                    continue
                out = _op_report(op, prov, env, i)
                out.update(_value_stats(a))
                out["var"] = name
                report.update({"found": True, "op": out})
                return report
    return report


def bisect_record(record: dict) -> dict:
    """Replay one saved dispatch (the executor's bisect-mode input
    snapshot: block + detached mutable state + const state + feeds +
    seed) and report the first non-finite-producing op.  Runs on the
    NaN monitor thread — every materialization is the forensics
    boundary, not a hot-path sync."""
    env: Dict[str, Any] = {}
    env.update(record.get("const") or {})
    env.update(record.get("mutable") or {})
    env.update(record.get("feeds") or {})
    report = _replay_block(record["block"], env,
                           record.get("seed", 0),
                           label=record.get("label", ""),
                           step=record.get("step"))
    _register_bisection(report)
    return report


def bisect_nonfinite(program, feed: Optional[dict] = None, scope=None,
                     fetch_list: Optional[list] = None,
                     transform: bool = True) -> dict:
    """Public entry point: transform `program` exactly as the executor
    would (so provenance carries the [pass=...] tags of the compiled
    graph), seed an eager environment from `scope` + `feed`, and
    replay op-by-op to name the first non-finite-producing op.  When
    `fetch_list` is None the last op's outputs anchor the transform
    pipeline (DCE must not prune the path under bisection).  This is
    offline forensics — materializations here are the point."""
    import numpy as np  # noqa: PLC0415 - lazy by design

    from ..fluid.executor import global_scope
    from ..fluid.framework import EMPTY_VAR_NAME

    scope = scope if scope is not None else global_scope()
    feed = feed or {}
    feed_arrays = {n: np.asarray(v) for n, v in feed.items()}  # sync-ok:
    # offline bisection entry — normalizing the user feed is forensics
    # input prep, not a dispatch-path transfer
    fetch_names: List[str] = []
    for f in (fetch_list or []):
        fetch_names.append(getattr(f, "name", f))
    if not fetch_names:
        ops = program.global_block().ops
        if ops:
            for names in ops[-1].outputs.values():
                fetch_names.extend(n for n in names
                                   if n != EMPTY_VAR_NAME)
    lowered = program
    if transform:
        from ..transforms import maybe_transform_program

        lowered = maybe_transform_program(
            program, feed_names=feed_arrays.keys(),
            fetch_names=fetch_names, scope=scope)
    block = lowered.global_block()
    env: Dict[str, Any] = dict(feed_arrays)
    for op in block.ops:
        for names in op.inputs.values():
            for name in names:
                if name == EMPTY_VAR_NAME or name in env:
                    continue
                if scope.has(name) and scope.get(name) is not None:
                    env[name] = scope.get(name)
    seed = getattr(program, "random_seed", 0) or 0
    report = _replay_block(block, env, seed,
                           label=getattr(program, "name", "") or
                           f"program#{getattr(program, 'prog_id', 0)}")
    _register_bisection(report)
    return report


def _register_bisection(report: dict) -> None:
    global _LAST_BISECTION
    with _LOCK:
        _LAST_BISECTION = report
    try:
        from .. import profiler

        profiler.stat_add("numerics_bisect_runs_total")
    except Exception:  # noqa: BLE001 - standalone load
        pass


# ---------------------------------------------------------------------------
# NaN-monitor hit hook + flight-bundle publication
# ---------------------------------------------------------------------------

def handle_nan_hit(hits: List[str], context: Optional[dict]) -> None:
    """Called from the executor's async NaN monitor on every detected
    non-finite batch of flags.  Records `nan_inf_first_step`, runs the
    bisection when a dispatch snapshot rode along (bisect mode), and
    publishes a `non_finite_loss` flight bundle through the live
    watchdog — or `write_standalone_bundle` when no sampler thread is
    running.  Never raises (monitor-thread context)."""
    global _FIRST_STEP, _LAST_HIT
    context = context or {}
    step = context.get("step")
    with _LOCK:
        first = _FIRST_STEP is None
        if first and step is not None:
            _FIRST_STEP = int(step)
        _LAST_HIT = {"step": step, "hits": list(hits),
                     "label": context.get("label", "")}
    if first and step is not None:
        try:
            from .. import profiler

            profiler.stat_set("nan_inf_first_step", int(step))
        except Exception:  # noqa: BLE001 - standalone load
            pass
    record = context.get("record")
    if record is not None and mode() == "bisect":
        try:
            drain()  # fold the per-op stats that led up to the hit
            bisect_record(record)
        except Exception:  # noqa: BLE001 - forensics must not take
            # down the monitor thread
            pass
    _publish_hit(hits, context)


def _publish_hit(hits: List[str], context: dict) -> None:
    reason = (f"non-finite value in {hits[0]!r}"
              + (f" at step {context['step']}"
                 if context.get("step") is not None else "")
              + (f" ({len(hits)} var(s) affected)" if len(hits) > 1
                 else ""))
    try:
        from .. import obs
    except Exception:  # noqa: BLE001 - standalone load: no bundle
        return
    try:
        handle = obs.telemetry_handle()
        if handle is not None and handle.watchdog is not None:
            handle.watchdog.trigger("non_finite_loss", reason)
        else:
            flight_dir = obs._obs_flag("obs_flight_dir",
                                       "PADDLE_OBS_FLIGHT_DIR", "", str)
            if flight_dir:
                obs.telemetry.write_standalone_bundle(
                    flight_dir, "non_finite_loss", reason,
                    {"numerics.json": numerics_doc()})
    except Exception:  # noqa: BLE001 - forensics must not mask the hit
        pass


# ---------------------------------------------------------------------------
# Export surface
# ---------------------------------------------------------------------------

def numerics_doc() -> dict:
    """The full numeric-health document (`numerics.json` in flight
    bundles): mode, the per-(provenance, var) aggregate sorted worst
    first, health gauges, the last hit and bisection report."""
    drain()
    with _LOCK:
        rows = [dict(r) for r in _AGG.values()]
        doc = {
            "mode": mode(),
            "first_nonfinite_step": _FIRST_STEP,
            "steps_drained": _STEPS_DRAINED,
            "nonfinite_ops_total": _NONFINITE_OPS,
            "health": dict(_HEALTH),
            "loss_scale": _LAST_SCALE,
            "loss_scale_decr_total": _SCALE_DECR,
            "last_hit": dict(_LAST_HIT) if _LAST_HIT else None,
            "bisection": dict(_LAST_BISECTION) if _LAST_BISECTION
            else None,
            "dropped": {"pending": _DROPPED, "agg_rows": _AGG_DROPPED},
        }
    rows.sort(key=lambda r: (-(r["nan_count"] + r["inf_count"]),
                             -r["absmax"], r["provenance"], r["var"]))
    doc["ops"] = rows
    return doc


def snapshot(top: int = 8) -> dict:
    """Condensed view for `obs.snapshot()` / bench detail."""
    doc = numerics_doc()
    bad = [r for r in doc["ops"]
           if r["nan_count"] + r["inf_count"] > 0]
    return {"mode": doc["mode"],
            "first_nonfinite_step": doc["first_nonfinite_step"],
            "ops_tracked": len(doc["ops"]),
            "nonfinite_ops": bad[:top],
            "health": doc["health"],
            "loss_scale": doc["loss_scale"],
            "bisection": doc["bisection"]}


def reset() -> None:
    """Clear all drained/pending state (test + bench isolation)."""
    global _DROPPED, _AGG_DROPPED, _LAST_SCALE, _SCALE_DECR
    global _FIRST_STEP, _LAST_HIT, _LAST_BISECTION
    global _STEPS_DRAINED, _NONFINITE_OPS
    with _LOCK:
        _PENDING.clear()
        _PENDING_SCALE.clear()
        _AGG.clear()
        _HEALTH.clear()
        _DROPPED = 0
        _AGG_DROPPED = 0
        _LAST_SCALE = None
        _SCALE_DECR = 0
        _FIRST_STEP = None
        _LAST_HIT = None
        _LAST_BISECTION = None
        _STEPS_DRAINED = 0
        _NONFINITE_OPS = 0
