"""Device milliseconds a traced step spends applying the update:
the `optimizer` scope of a functional step, the optimizer op types
of a Program."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, phase="optimizer")
