"""Device milliseconds a traced step spends under the `self_attn` of the
full-attention layers, forward and backward: the five projections at 48
query heads over 8 key/value heads (groups of 6), the YaRN rotation of
half of every head, the causal flash kernels over all 16,384 keys, and
the gate's multiply.  The full layers by the configuration's own
list."""

from benchmark.lib import flops_laguna, scopes


def read(run):
    if "sliding_window" not in run.config:
        return None
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=flops_laguna.self_attn_pattern(run.config, "full"))
