"""Device milliseconds a traced step spends under the scope `kda_core`
of the Kimi Delta Attention layers, forward and backward: all of the
scan — its chunk-local XLA part (cumulated gates, the scores with the
decay inside the contraction, the triangular solve) and the kernels
`kda_fwd` / `kda_bwd` — and nothing else of the layer."""

from benchmark.lib import flops_kimi_linear, scopes


def read(run):
    return scopes.ms_per_step(run, phase=("fwd", "bwd"),
                              path_regex=flops_kimi_linear.KDA_CORE)
