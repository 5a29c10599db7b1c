"""Device milliseconds a traced step spends under the `moe` scope of the
expert layers, forward and backward: the sigmoid router with its
selection bias, the visit plan, the chunk walk with its grouped
matmuls, and the shared expert — one number for the whole expert layer,
beside the two attention kinds'."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, phase=("fwd", "bwd"),
                              path_regex=r"(^|/)moe(/|$)")
