"""Device milliseconds a traced step spends in the Program's
`conv2d*` ops, `conv2d_grad` included."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, path_regex=r"^conv2d")
