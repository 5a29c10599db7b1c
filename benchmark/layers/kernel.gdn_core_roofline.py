"""The Gated DeltaNet scan's share of its roofline, in %: the least
time the chip could take for the RECURRENCE — the GDN layers' forward
and backward at the larger of FLOPs / peak FLOP/s and bytes / peak
bytes/s (benchmark/lib/flops_qwen3_next.py: gdn_core_cost; 6 and 12
dk dv FLOPs a token and VALUE head, q and k once a KEY head, g and beta
one scalar a value head, every operand and gradient once at the scan's
edge; memory-bound at this cell's shape) — over ALL device time under
the scope `gdn_core`, so that moving work between XLA and the kernels
does not move the yardstick.  The chunked form's own arithmetic and a
recomputed forward count in the time and not in the work."""

from benchmark.lib import flops, flops_qwen3_next, scopes


def read(run):
    if run.peaks is None or "linear_num_value_heads" not in run.config:
        return None
    ms = scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=flops_qwen3_next.attn_pattern(run.config, "gdn",
                                                 "gdn_core"))
    costs = [c for k, c in getattr(run.system, "kernels", {}).items()
             if k.startswith("gdn_core_")]
    if not ms or not costs:
        return None
    least = sum(flops.roofline_seconds(c["flops"], c["bytes"], run.peaks)[0]
                for c in costs)
    return 100.0 * least * 1e3 / ms
