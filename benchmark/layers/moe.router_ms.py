"""Device milliseconds a traced step spends under the `router` scope
of the expert layers (`…/moe/router`), forward and backward: the
router matmul, the float32 softmax and the top-k."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, phase=("fwd", "bwd"),
                              path_regex=r"(^|/)moe/(.*/)?router(/|$)")
