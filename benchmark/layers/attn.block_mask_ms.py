"""Device milliseconds a traced step spends under a `self_attn` layer
of the block-diffusion model, forward and backward: the q/k/v/out
projections, the per-head norms, the rotation, and the flash kernels
with grouped key/value heads and the block-diffusion mask."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, phase=("fwd", "bwd"),
                              path_regex=r"(^|/)self_attn(/|$)")
