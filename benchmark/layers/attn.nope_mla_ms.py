"""Device milliseconds a traced step spends under the `self_attn` of
the position-free latent-attention layers, forward and backward: the
four projections (no query latent), the one latent norm, the assembly
of the 192-wide q/k heads (no rotation) and the causal flash kernels.
The latent layers by the configuration's own list."""

from benchmark.lib import flops_kimi_linear, scopes


def read(run):
    if "linear_attn_config" not in run.config:
        return None
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=flops_kimi_linear.self_attn_pattern(run.config, "mla"))
