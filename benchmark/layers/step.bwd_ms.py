"""Device milliseconds a traced step spends in the backward pass:
the names of phase `bwd` (`transpose(` in `op_name`, or a `*_grad`
Program op)."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, phase="bwd")
