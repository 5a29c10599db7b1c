"""`setup_s` taken apart: every instant from process start to the
window's start belongs to exactly one of nine named times or to none.

Two sources on one clock (`time.perf_counter`): the program's own
phase log, `paddle_tpu.profiler.get_phases()` — `(name, start_s, dur_s,
...)`, recorded where the work happens —, and the benchmark's spans
around its calls into the program (`lib/spans.py`, nanoseconds).
Process start is the window's start less `setup_s`.

The reduction takes SELF time, not sums.  Phases nest (a compile
happens inside a trace's probe, a trace inside the benchmark's
reference check): an instant goes to the innermost interval that
covers it — the one that started last —, so intervals of one name may
overlap and count once.  Two rules beside that: everything inside the
benchmark's `setup.reference` span is the reference check's, whatever
the program did there; and the seconds before the package's import
began are `reach` (the interpreter, `import jax`, reaching the chip).
The benchmark's other spans (`setup.model`, `setup.lower`, ...) name
nothing here: what the program does not name inside them is the
unnamed rest, which `setup.named_share` reports.

A program without a phase log (a parent commit) gives `None`: the
readers return nothing and the result line leaves the metrics out.
"""

from __future__ import annotations

# metric <- the program's phase names (a name's children `name/...` too)
PROGRAM_PHASES = {
    "setup.import_s": ("setup.import",),
    "setup.param_init_s": ("setup.param_init", "setup.state_build"),
    "setup.trace_lower_s": ("setup.trace", "setup.lower",
                            "setup.transform", "setup.verify"),
    "setup.kernel_trace_s": ("setup.kernel_trace",),
    "setup.compile_s": ("setup.backend_compile",),
    "setup.cache_load_s": ("setup.cache_load",),
}
# metric <- the benchmark's own spans
BENCHMARK_SPANS = {
    "setup.reference_s": "setup.reference",
    "setup.warm_up_s": "setup.warm_up",
}
REACH, REFERENCE = "setup.reach_s", "setup.reference_s"
TIMES = (REACH, *PROGRAM_PHASES, *BENCHMARK_SPANS)
IMPORT_PHASE = "setup.import"

_BY_PHASE = {phase: metric for metric, phases in PROGRAM_PHASES.items()
             for phase in phases}
_BY_SPAN = {span: metric for metric, span in BENCHMARK_SPANS.items()}


def partition(phases, spans, start_s: float, end_s: float) -> dict:
    """`{metric: seconds}` for the nine times of `TIMES`, `unnamed_s`
    and `setup.named_share`, over `[start_s, end_s)`.  `phases`:
    `(name, start_s, dur_s, ...)` in seconds; `spans`: `(name,
    start_ns, end_ns)`.  The nine times and `unnamed_s` add up to
    `end_s - start_s`."""
    intervals = []                      # (start, end, metric)
    import_starts = []
    for name, p_start, dur, *_ in phases:
        metric = _BY_PHASE.get(name.split("/", 1)[0])
        if metric is not None:
            intervals.append((p_start, p_start + dur, metric))
        if name == IMPORT_PHASE:
            import_starts.append(p_start)
    for name, start_ns, end_ns in spans:
        metric = _BY_SPAN.get(name)
        if metric is not None:
            intervals.append((start_ns / 1e9, end_ns / 1e9, metric))
    if import_starts:
        intervals.append((start_s, min(import_starts), REACH))
    intervals = sorted((max(a, start_s), min(b, end_s), m)
                       for a, b, m in intervals
                       if min(b, end_s) > max(a, start_s))

    out = dict.fromkeys(TIMES, 0.0)
    cuts = sorted({t for a, b, _ in intervals for t in (a, b)})
    active, nxt = [], 0
    for lo, hi in zip(cuts, cuts[1:]):
        while nxt < len(intervals) and intervals[nxt][0] <= lo:
            active.append(intervals[nxt])
            nxt += 1
        active = [iv for iv in active if iv[1] > lo]
        if not active:
            continue
        if any(m == REFERENCE for _, _, m in active):
            owner = REFERENCE
        else:               # innermost: started last, then ends first
            owner = max(active, key=lambda iv: (iv[0], -iv[1]))[2]
        out[owner] += hi - lo
    total = end_s - start_s
    out["unnamed_s"] = total - sum(out[m] for m in TIMES)
    out["setup.named_share"] = 1.0 - out["unnamed_s"] / total
    return out


def of_run(run, metric: str):
    """Metric `metric` of a run of `run.py`, or None where the program
    keeps no phase log.  The partition is made once a run."""
    cached = getattr(run, "_setup_partition", None)
    if cached is None:
        from paddle_tpu import profiler

        get_phases = getattr(profiler, "get_phases", None)
        if get_phases is None:
            return None
        end_s = run.window.start_ns / 1e9
        cached = run._setup_partition = partition(
            get_phases(), run.spans.spans, end_s - run.setup_s, end_s)
    return cached[metric]
