"""Device milliseconds a traced step spends under the `linear_attn` of
the Gated DeltaNet layers, forward and backward: the projections
`in_proj_qkvz` and `in_proj_ba`, the work before the scan (`gdn_pre`),
the scan (`gdn_core`), the gated head norm (`gdn_post`) and
`out_proj`.  The GDN layers by the configuration's own
`full_attention_interval`."""

from benchmark.lib import flops_qwen3_next, scopes


def read(run):
    if "linear_num_value_heads" not in run.config:
        return None
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=flops_qwen3_next.attn_pattern(run.config, "gdn"))
