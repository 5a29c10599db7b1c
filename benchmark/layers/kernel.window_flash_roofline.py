"""The sliding-window flash kernels' share of their roofline, in %: the
least time the chip could take for the pairs the band keeps (a sequence
of 16,384 and a window of 512: 8,257,792 a head, 3% of the square) — per
call the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s, q and o
at the 64 query heads' width, each of the 8 key/value heads read once
(benchmark/lib/flops_laguna.py: window_flash_cost) — over the window
layers' kernel time in the device trace.  The window instances are told
from the full ones by the layer whose scope the call carries
(benchmark/configs/laguna.py: _kernel_calls).  The masked parts of the
band's edge tiles, the dead steps of the first q tiles and a recomputed
forward count in the time and not in the work."""

from benchmark.lib import flops_laguna


def read(run):
    return flops_laguna.kernel_roofline(run, "window_flash_")
