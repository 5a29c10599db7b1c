"""Device milliseconds a traced step spends under the `self_attn` of
the gated full-attention layers, forward and backward: W_q at twice the
query width (query and gate), k, v and o, the zero-centred QK norms, the
rotation of a quarter of each 256-wide head, the causal flash kernels
at 16 query heads over 2, and the element-wise gate.  The full layers by
the configuration's own `full_attention_interval`."""

from benchmark.lib import flops_qwen3_next, scopes


def read(run):
    if "linear_num_value_heads" not in run.config:
        return None
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=flops_qwen3_next.attn_pattern(run.config, "full"))
