"""Device milliseconds a traced step spends under the `moe` scope of the
four expert layers, forward and backward: the softmax router over 256,
the visit plan, the chunk walk with its grouped matmuls over the 16 held
experts of width 512, and the shared expert — one number for the whole
expert layer, beside the two attention kinds'."""

from benchmark.lib import scopes


def read(run):
    if "sliding_window" not in run.config:
        return None
    return scopes.ms_per_step(run, phase=("fwd", "bwd"),
                              path_regex=r"(^|/)moe(/|$)")
