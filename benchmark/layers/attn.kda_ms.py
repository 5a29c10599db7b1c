"""Device milliseconds a traced step spends under the `self_attn` of
the Kimi Delta Attention layers, forward and backward: the eleven
projections, the three short convolutions, the norms and gates, and the
scan (`kda_core`).  The KDA layers by the configuration's own list."""

from benchmark.lib import flops_kimi_linear, scopes


def read(run):
    if "linear_attn_config" not in run.config:
        return None
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=flops_kimi_linear.self_attn_pattern(run.config, "kda"))
