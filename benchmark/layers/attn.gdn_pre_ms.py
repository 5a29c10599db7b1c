"""Device milliseconds a traced step spends under the scope `gdn_pre`
of the Gated DeltaNet layers, forward and backward: the causal 4-tap
convolution over [q~ | k~ | v~], SiLU and the key heads' unit norms
(the kernels `gdn_pre_fwd` / `gdn_pre_bwd`), and beta and the decay g
in XLA — all a layer does between its projections and its scan."""

from benchmark.lib import flops_qwen3_next, scopes


def read(run):
    if "linear_num_value_heads" not in run.config:
        return None
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=flops_qwen3_next.attn_pattern(run.config, "gdn",
                                                 "gdn_pre"))
