"""Device time under the program's own names, for the per-layer readers.

The program names what it runs: `nn.Layer.__call__` opens a
`jax.named_scope` per layer, the BERT step names its `loss`,
`optimizer` and `cast`, the flash kernels are called `flash_fwd` /
`flash_bwd_dkv` / `flash_bwd_dq`, and every Program op lowers under its
provenance.  `paddle_tpu.obs.devprof.device_time` joins the traced
window's `XLA Ops` events to those names, through the executables the
cell holds, and returns seconds by `(phase, path)`; the Executor's
stages are in the same trace as `pt.executor.*` annotations.

This file calls it once per run (memoised on the `Run`), writes the
whole table to `.bench_out/<workload>/device_time_by_name.json`, and
offers the two sums the readers under `layers/` are made of.  The path
patterns live in the reader files: the program knows no model, and
this file knows none either.

On a program without `devprof.device_time` (the parent of the PR that
brought it) and on a run without a device trace, everything here
returns None and the metric is left out.
"""

from __future__ import annotations

import json
import os
import re
import time

from benchmark.lib import trace_reduce

_UNSET = object()


def _executables(system) -> dict:
    """What the process holds: every executable the `Executor` compiled
    or loaded (the program's `opprof` registry), and a functional
    system's own compiled step."""
    from paddle_tpu.obs import opprof

    out = dict(opprof.profiles())
    compiled = getattr(system, "_compiled", None)
    if compiled is not None:
        out["step"] = compiled
    return out


def table(run):
    """`devprof.device_time` of the run's traced window, or None."""
    cached = getattr(run, "_device_time", _UNSET)
    if cached is not _UNSET:
        return cached
    run._device_time = None
    if not run.trace:
        return None
    from benchmark import run as harness
    from paddle_tpu.obs import devprof

    device_time = getattr(devprof, "device_time", None)
    if device_time is None:
        return None
    out_dir = os.path.join(harness.OUT_DIR, run.cell["name"])
    t0 = time.perf_counter()
    result = device_time(
        trace_reduce.find_xplane(os.path.join(out_dir, "trace")),
        _executables(run.system), window_ns=trace_reduce.WINDOW_SPAN)
    if result is None:
        return None
    result["steps"] = run.trace["steps"]
    result["reduce_s"] = time.perf_counter() - t0
    run._device_time = result
    with open(os.path.join(out_dir, "device_time_by_name.json"), "w") as f:
        json.dump(_as_json(result), f, indent=1)
    return result


def _as_json(result: dict) -> dict:
    steps = result["steps"]
    rows = sorted(result["by_name"].items(), key=lambda kv: -kv[1])
    return {
        "chips": result["chips"], "steps": steps,
        "window_s": (result["window_ns"][1] - result["window_ns"][0]) / 1e9,
        "op_s": result["op_s"], "reduce_s": result["reduce_s"],
        "programs": result["programs"],
        "ms_per_step_by_phase": {
            phase: sum(s for (p, _), s in rows if p == phase) / steps * 1e3
            for phase in sorted({p for (p, _), _ in rows})},
        "by_name": [{"phase": phase, "path": path, "seconds": s,
                     "ms_per_step": s / steps * 1e3}
                    for (phase, path), s in rows],
        "unattributed": dict(sorted(result["unattributed"].items(),
                                    key=lambda kv: -kv[1])),
        "host_spans": {name: {"count": len(spans), "seconds": sum(
            e - s for s, e in spans) / 1e9}
            for name, spans in result["host_spans"].items()},
    }


def ms_per_step(run, phase=None, path_regex=None):
    """Device milliseconds a traced step spends under the names whose
    phase is `phase` (one, or a tuple of them) and whose path
    `path_regex` finds; None selects all."""
    t = table(run)
    if t is None:
        return None
    phases = (phase,) if isinstance(phase, str) else phase
    pattern = re.compile(path_regex) if path_regex else None
    seconds = sum(
        s for (p, path), s in t["by_name"].items()
        if (phases is None or p in phases)
        and (pattern is None or pattern.search(path)))
    return seconds / t["steps"] * 1e3


def named_share(run):
    """1 - unattributed / summed op time of the traced window."""
    t = table(run)
    if t is None or not t["op_s"]:
        return None
    return 1.0 - sum(t["unattributed"].values()) / t["op_s"]


def exposed_ms_per_step(run, span_name: str):
    """Milliseconds a traced step's device sits idle while the host is
    inside the program's `span_name` annotation: the window minus the
    busy union (gaps under `trace_reduce`'s floor left out), cut with
    the union of the span's intervals; mean over chips."""
    t = table(run)
    if t is None:
        return None
    spans = trace_reduce._union(t["host_spans"].get(span_name, ()))
    lo, hi = t["window_ns"]
    exposed = 0
    for busy in t["busy"].values():
        for gap in trace_reduce._minus([(lo, hi)], busy):
            if gap[1] - gap[0] >= trace_reduce._GAP_FLOOR_NS:
                exposed += (gap[1] - gap[0]) - trace_reduce._length(
                    trace_reduce._minus([gap], spans))
    return exposed / t["chips"] / t["steps"] / 1e6
