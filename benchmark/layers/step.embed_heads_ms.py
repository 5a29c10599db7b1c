"""Device milliseconds a traced step spends outside the encoder
layers, forward and backward: `bert/embeddings`, `bert/pooler`, the
`cls` heads and the `loss`."""

from benchmark.lib import scopes


def read(run):
    return scopes.ms_per_step(
        run, phase=("fwd", "bwd", "loss"),
        path_regex=r"(^|/)(bert/embeddings|bert/pooler|cls|loss)(/|$)")
