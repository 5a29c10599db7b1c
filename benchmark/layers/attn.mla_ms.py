"""Device milliseconds a traced step spends under a `self_attn` layer
of the latent-attention model, forward and backward: the six
projections, the two latent norms, the rotation, the assembly of the
192-wide q/k heads and the causal flash kernels."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, phase=("fwd", "bwd"),
                              path_regex=r"(^|/)self_attn(/|$)")
