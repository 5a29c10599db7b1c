"""The Kimi Delta Attention scan's share of its roofline, in %: the
least time the chip could take for the RECURRENCE — a layer's forward
and backward at the larger of FLOPs / peak FLOP/s and bytes / peak
bytes/s (benchmark/lib/flops_kimi_linear.py: kda_core_cost; 6 and 12
dk dv FLOPs a token and head, every operand and gradient once at the
scan's edge; memory-bound at this cell's shape) — over ALL device time
under the scope `kda_core`, XLA part and kernels alike, so that moving
work between them does not move the yardstick.  The chunked form's own
arithmetic and a recomputed forward count in the time and not in the
work."""

from benchmark.lib import flops, flops_kimi_linear, scopes


def read(run):
    if run.peaks is None:
        return None
    ms = scopes.ms_per_step(run, phase=("fwd", "bwd"),
                            path_regex=flops_kimi_linear.KDA_CORE)
    costs = [c for k, c in getattr(run.system, "kernels", {}).items()
             if k.startswith("kda_core_")]
    if not ms or not costs:
        return None
    least = sum(flops.roofline_seconds(c["flops"], c["bytes"], run.peaks)[0]
                for c in costs)
    return 100.0 * least * 1e3 / ms
