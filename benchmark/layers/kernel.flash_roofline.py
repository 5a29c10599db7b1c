"""The flash-attention kernels' share of their roofline, in %: the
least time the chip could take for the attention the step needs —
per call the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s,
from shapes (benchmark/lib/flops.py: flash_attention_cost) — over the
kernels' time in the device trace.  Which bound holds follows from
the shapes alone; PERF.md names it for each cell."""

from benchmark.lib import flops


def read(run):
    t = run.trace
    if not t or not t["kernel_s"] or run.peaks is None:
        return None
    least = sum(flops.roofline_seconds(c["flops"], c["bytes"], run.peaks)[0]
                for c in run.system.kernels.values())
    return 100.0 * least * t["steps"] / sum(t["kernel_s"].values())
