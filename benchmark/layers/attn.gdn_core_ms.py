"""Device milliseconds a traced step spends under the scope `gdn_core`
of the Gated DeltaNet layers, forward and backward: all of the scan —
the kernels `gdn_fwd` / `gdn_bwd` and the reshapes at their edge — and
nothing else of the layer."""

from benchmark.lib import flops_qwen3_next, scopes


def read(run):
    if "linear_num_value_heads" not in run.config:
        return None
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=flops_qwen3_next.attn_pattern(run.config, "gdn",
                                                 "gdn_core"))
