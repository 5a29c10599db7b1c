"""Device milliseconds a traced step spends in the Program's
`batch_norm*` ops, `batch_norm_grad` included."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, path_regex=r"^batch_norm")
