"""The position-free latent attention's flash kernels' share of their
roofline, in %: the least time the chip could take for the CAUSAL pairs
at this cell's tiles (one sequence of 16,384: of a head's 1,024 (512,
512) tiles 528 live) — scores at the q/k width (192), values at the v
width (128) (benchmark/lib/flops_joyai.py: mla_flash_cost) — over the
flash kernels' time in the device trace.  Dead tiles, the masked halves
of the diagonal tiles and a recomputed forward count in the time and
not in the work."""

from benchmark.lib import flops


def read(run):
    t = run.trace
    if not t or run.peaks is None:
        return None
    seconds = sum(s for kind, s in t["kernel_s"].items()
                  if kind.startswith("flash_"))
    costs = [c for k, c in getattr(run.system, "kernels", {}).items()
             if k.startswith("flash_")]
    if not seconds or not costs:
        return None
    least = sum(flops.roofline_seconds(c["flops"], c["bytes"], run.peaks)[0]
                for c in costs)
    return 100.0 * least * t["steps"] / seconds
