"""Device milliseconds a traced step spends in the
multi-token-prediction module (scope `mtp`: its two norms, `eh_proj`,
its decoder block with latent attention and expert layer, its final
norm), forward and backward.  The shared embedding's second lookup and
the second head are under `embed_tokens` and `loss`."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(run, phase=("fwd", "bwd"),
                              path_regex=r"(^|/)mtp(/|$)")
