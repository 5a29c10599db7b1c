"""Device milliseconds a traced step spends under a `self_attn`
layer, forward and backward: projections, the flash kernels, and the
copies and dropout around them."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, phase=("fwd", "bwd"),
                              path_regex=r"(^|/)self_attn(/|$)")
