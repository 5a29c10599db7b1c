"""The latent attention's flash kernels' share of their roofline, in %:
the least time the chip could take for the CAUSAL pairs — per call the
larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, scores at the
q/k width (192) and values at the v width (128)
(benchmark/lib/flops_joyai.py: mla_flash_cost; compute-bound at this
cell's shape) — over the kernels' time in the device trace.  Dead
tiles, the masked halves of the diagonal tiles and a recomputed forward
count in the time and not in the work."""

from benchmark.lib import flops


def read(run):
    t = run.trace
    if not t or run.peaks is None:
        return None
    seconds = sum(s for kind, s in t["kernel_s"].items()
                  if kind.startswith("flash_"))
    if not seconds:
        return None
    least = sum(flops.roofline_seconds(c["flops"], c["bytes"], run.peaks)[0]
                for c in run.system.kernels.values())
    return 100.0 * least * t["steps"] / seconds
