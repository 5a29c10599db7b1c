"""Device milliseconds a traced step spends in the expert layers'
shared expert (`…/moe/shared_experts`: the gated FFN every row
passes), forward and backward."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=r"(^|/)moe/(.*/)?shared_experts(/|$)")
