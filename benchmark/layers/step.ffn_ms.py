"""Device milliseconds a traced step spends in the encoder layers'
feed-forward blocks, forward and backward: the `ffn` scope of the
fused path, or `linear1` / `activation` / `dropout` / `linear2`."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd"),
        path_regex=r"/layers/\d+/"
                   r"(ffn|linear1|activation|dropout|linear2)(/|$)")
