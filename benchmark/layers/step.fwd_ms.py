"""Device milliseconds a traced step spends in the forward pass,
the loss included: the names of phase `fwd` and `loss`."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, phase=("fwd", "loss"))
