"""Device milliseconds a traced step spends under the `self_attn` of the
sliding-window layers, forward and backward: the five projections (q,
k, v, the per-head gate's, o) at 64 query heads over 8 key/value heads,
the plain rotation of the whole head, the flash kernels whose grids
walk the band of 512 keys, and the gate's multiply.  The window layers
by the configuration's own list."""

from benchmark.lib import flops_laguna, scopes


def read(run):
    if "sliding_window" not in run.config:
        return None
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=flops_laguna.self_attn_pattern(run.config, "window"))
