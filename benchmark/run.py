#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, on the machine it is started on.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (imports, building the system from `--seed`, compile or cache
load, the reference check, warm-up), then a measured window of at
least `--seconds`, then the last line of stdout: one JSON object with
`correct`, `attempted`, `failed`, `metrics` and `device`.  With
`--trace 0` the metrics are the cell's end-to-end metrics.  With
`--trace 1` they are its per-layer metrics: the same window with the
profiler off gives the host-clock ones, and a short window under
`jax.profiler` after it gives the device's, `device.busy_s` /
`window_s` and the `breakdown`.

There is no CPU mode: without a TPU that `benchmark/lib/peaks.py`
knows, or with fewer chips than the cell asks for, it exits non-zero
and prints no result.

Everything that belongs to one configuration, traffic mix or metric is
a file the harness finds by the name in BENCHMARK.json — see
benchmark/README.md.  This file names none of them.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()          # set-up counts from process start

import argparse                     # noqa: E402
import dataclasses                  # noqa: E402
import importlib.util               # noqa: E402
import json                         # noqa: E402
import math                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".bench_out")     # traces; in .gitignore


class BenchmarkError(Exception):
    """The run cannot produce a result; the command exits non-zero."""


def load_module(path: str):
    """A file of the benchmark as a module, found by path so that its
    name may be a metric's (`layers/step.ms_p50.py`)."""
    if not os.path.isfile(path):
        raise BenchmarkError(f"no such file: {os.path.relpath(path, ROOT)}")
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, ROOT))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module      # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Run:
    """What the metric readers read.  `base` is the directory that
    holds `configs/`, `traffic/`, `layers/` and `end_to_end/`."""
    base: str
    cell: dict
    config: dict
    traffic: dict
    chips: int
    peaks: dict | None
    system: object
    spans: object
    setup_s: float
    window: object
    marks: dict                     # counter snapshots by moment
    memory_peak_bytes: int = 0
    trace: dict | None = None

    def window_delta(self, name: str) -> float:
        return (self.marks["window_end"].get(name, 0)
                - self.marks["window_start"].get(name, 0))

    def setup_delta(self, name: str) -> float:
        return self.marks["window_start"].get(name, 0)

    def read(self, kind: str, name: str):
        """The value of metric `name` of `kind` ("end_to_end" or
        "layers"), or None where its reader finds nothing."""
        return load_module(os.path.join(self.base, kind, name + ".py")).read(
            self)


def require_chip(chips: int):
    """`(first device, peak row)`, or BenchmarkError: JAX falls back to
    the CPU in silence without a chip."""
    import jax

    from benchmark.lib import peaks

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise BenchmarkError(
            f"no chip found: JAX reports platform {d.platform!r}; the "
            "benchmark has no CPU mode")
    if len(devices) < chips:
        raise BenchmarkError(f"the cell needs {chips} chips, JAX reports "
                             f"{len(devices)}")
    return d, peaks.peaks_for(d.device_kind)


def device_report() -> dict:
    """The device as JAX reports it.  `memory_peak_bytes` is, on the
    fullest chip, the peak of the bytes in use by arrays plus the peak
    of the bytes reserved: libtpu's allocator counts the scratch memory
    of a loaded program (the compiler's `temp_size_in_bytes`, most of
    what a train step holds) under `bytes_reserved`, not `bytes_in_use`."""
    import jax

    def peak(stats):
        return (stats.get("peak_bytes_in_use", 0)
                + stats.get("peak_bytes_reserved", 0))

    devices = jax.local_devices()
    fullest = max((d.memory_stats() or {} for d in devices), key=peak)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": int(peak(fullest)),
            "memory_stats": fullest}


def find_cell(manifest: dict, workload: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchmarkError(f"no workload {workload!r} in BENCHMARK.json "
                             f"(known: {sorted(cells)})")
    cell = cells[workload]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == cell["config"])
    return cell, config_entry


def metrics_of(run: Run, manifest: dict, kind: str) -> dict:
    """`{name: {"value", "unit"}}` for the cell's metrics of `kind`
    ("end_to_end" | "per_layer"): each from its own reader file, left
    out where the reader returns nothing."""
    folder = "end_to_end" if kind == "end_to_end" else "layers"
    out = {}
    for m in manifest[kind]:
        if "workloads" in m and run.cell["name"] not in m["workloads"]:
            continue
        value = run.read(folder, m["name"])
        if value is None:
            continue
        if not math.isfinite(value):
            raise BenchmarkError(f"metric {m['name']} is {value}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def traced_window(system, loop, traffic, spans, trace_dir: str):
    """`traffic["trace_chunks"]` chunks under the profiler, after one
    chunk of lead-in (the first launch after the profiler starts takes
    the host ~0.1 s, which is the profiler's cost and stays outside);
    returns the window and the events of the trace
    (benchmark/lib/trace_reduce.py: load)."""
    import jax

    from benchmark.lib import trace_reduce

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # spans and device ops, no frames
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        loop.run(system, traffic, spans, chunks=1)
        with spans.span(trace_reduce.WINDOW_SPAN):
            window = loop.run(system, traffic, spans,
                              chunks=traffic["trace_chunks"])
    finally:
        jax.profiler.stop_trace()
    return window, trace_reduce.load(trace_reduce.find_xplane(trace_dir))


def run_cell(manifest_path: str, workload: str, seed: int, seconds: float,
             trace: bool, peaks: dict | None = None) -> dict:
    """Builds the cell's system, runs its loop and returns the result
    object.  It does not look at the platform: `main` refuses anything
    but a known TPU before it gets here, and benchmark/tests drive this
    function at a tiny preset on the CPU, where `peaks` is None and no
    device metric comes out."""
    from benchmark.lib import counters as counters_lib
    from benchmark.lib import spans as spans_lib
    from benchmark.lib import trace_reduce

    manifest = load_json(manifest_path)
    root = os.path.dirname(os.path.abspath(manifest_path))
    cell, config_entry = find_cell(manifest, workload)
    config = load_json(os.path.join(root, config_entry["file"]))
    base = os.path.dirname(os.path.dirname(
        os.path.join(root, config_entry["file"])))
    traffic = load_json(os.path.join(base, "traffic",
                                     cell["traffic"] + ".json"))
    builder = load_module(os.path.join(base, "configs",
                                       config["builder"] + ".py"))
    loop = load_module(os.path.join(HERE, "lib", traffic["loop"] + ".py"))

    counters = counters_lib.Counters()
    spans = spans_lib.SpanLog()
    imports_s = time.perf_counter() - _T0
    system = builder.build(config, traffic, cell["chips"], seed, spans)
    try:
        with spans.span("setup.warm_up"):
            warm_losses = loop.warm_up(system, traffic)
            system.sync()
        marks = {"window_start": counters.snapshot()}
        setup_s = time.perf_counter() - _T0
        window = loop.run(system, traffic, spans, seconds=seconds)
        marks["window_end"] = counters.snapshot()
        run = Run(base, cell, config, traffic, cell["chips"], peaks, system,
                  spans, setup_s, window, marks)

        device_extra, breakdown, traced = {}, None, None
        if trace:
            traced, events = traced_window(
                system, loop, traffic, spans,
                os.path.join(OUT_DIR, workload, "trace"))
            run.trace = trace_reduce.reduce(events, traced.steps,
                                            system.kernel_ops)
            if run.trace is not None:
                device_extra = {"busy_s": run.trace["busy_s"],
                                "window_s": run.trace["window_s"]}
                breakdown = {k: run.trace[k]
                             for k in ("device_ops", "idle_gaps")}
        device = device_report()
        run.memory_peak_bytes = device["memory_peak_bytes"]

        compiles = run.read("layers", "cache.compiles_in_window")
        losses = warm_losses + window.losses + (traced.losses if traced
                                                else [])
        checks = {
            "losses_finite": all(math.isfinite(v) for v in losses),
            "first_loss_near_untrained": abs(
                warm_losses[0] - system.untrained_loss)
            < system.first_loss_band,
            "no_compile_in_window": compiles == 0,
            **system.checks(marks["window_end"], warm_losses[0]),
        }
        result = {
            "correct": all(checks.values()),
            "attempted": window.steps,
            "failed": window.failed_steps,
            "metrics": metrics_of(
                run, manifest, "per_layer" if trace else "end_to_end"),
            "device": {**device, **device_extra},
        }
        if breakdown is not None:
            result["breakdown"] = breakdown
        # beyond the contract, for PERF.md and for whoever reads a log
        result["checks"] = checks
        result["reference"] = system.reference
        result["memory_analysis"] = system.memory_analysis
        result["kernel_ops"] = system.kernel_ops
        result["setup_spans_s"] = {
            "setup.imports": imports_s,
            **{n: (e - b) / 1e9 for n, b, e in spans.spans
               if n.startswith("setup.")}}
        result["losses"] = {"warm_up": warm_losses,
                            "window_first": window.losses[0],
                            "window_last": window.losses[-1]}
        result["chunk_s"] = window.chunk_s
        result["workload"] = workload
        result["seed"] = seed
        return result
    finally:
        system.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        print("benchmark: no paddle_tpu package beside benchmark/ — the "
              "benchmark measures the repo's system and is nothing alone",
              file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    manifest_path = os.path.join(ROOT, "BENCHMARK.json")
    try:
        cell, _ = find_cell(load_json(manifest_path), args.workload)
        _, peaks = require_chip(cell["chips"])

        from paddle_tpu.fluid.compile_cache import enable_persistent_cache

        # JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.jax_cache
        # and <checkout>/artifacts/aot_cache: fixed paths, so the second
        # run of a cell in a checkout compiles nothing
        enable_persistent_cache()
        result = run_cell(manifest_path, args.workload, args.seed,
                          args.seconds, bool(args.trace), peaks)
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
