"""Device milliseconds a traced step spends in the sigmoid router with
its selection bias: everything under the `router` scope of the expert
layers (`…/moe/router`: the router matmul, the float32 sigmoid, the
bias add, the top-k, the count over all router outputs), forward and
backward, and the step's update of the biases (`moe_bias_update`)."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(
        run, path_regex=r"(^|/)moe/(.*/)?router(/|$)|(^|/)moe_bias_update(/|$)")
