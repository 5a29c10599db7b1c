"""The full-attention layers' causal flash kernels' share of their
roofline, in %: the least time the chip could take for the causal pairs
(one sequence of 16,384: S (S + 1) / 2 a head) at 48 query heads in
groups of 6 over 8 key/value heads, each key/value head read once
(benchmark/lib/flops_laguna.py: full_flash_cost; compute-bound) — over
the full layers' kernel time in the device trace, told from the window
layers' by the layer whose scope the call carries
(benchmark/configs/laguna.py: _kernel_calls).  Dead tiles, the masked
halves of the diagonal tiles and a recomputed forward count in the time
and not in the work."""

from benchmark.lib import flops_laguna


def read(run):
    return flops_laguna.kernel_roofline(run, "full_flash_")
