"""The expert layers' grouped matmuls' share of their roofline, in %:
the least time the chip could take for the visits that landed on held
experts (the mean of the steps fetched; gate, up, down forward, their
input and weight gradients backward —
benchmark/lib/flops_sdar_moe.py: grouped_matmul_cost) over the time of
the grouped-matmul kernels in the device trace.  The recomputation of
the three forward products in the backward walk counts in the time and
not in the work."""

from benchmark.lib import flops, flops_sdar_moe


def read(run):
    t = run.trace
    if not t or run.peaks is None:
        return None
    seconds = t["kernel_s"].get("grouped_matmul")
    visits = getattr(run.system, "held_visits_per_layer_step", None)
    if not seconds or not visits:
        return None
    cost = flops_sdar_moe.grouped_matmul_cost(run.config, visits)
    least = run.config["num_hidden_layers"] * sum(
        flops.roofline_seconds(c["flops"], c["bytes"], run.peaks)[0]
        for c in cost.values())
    return 100.0 * least * t["steps"] / seconds
