"""Device milliseconds a traced step spends in the expert layers'
expert FFNs, forward and backward: everything under their `experts`
scope (masks, SiLU, the weighting) and the grouped-matmul kernels XLA
makes of `jax.lax.ragged_dot`, which `obs.opprof` names
`…/moe/…/ragged-dot-*` after the scope their neighbours share."""

from benchmark.lib import scopes

EXPERTS = r"(^|/)moe/(.*/)?(experts(/|$)|ragged-dot)"


def read(run):
    return scopes.ms_per_step(run, phase=("fwd", "bwd"), path_regex=EXPERTS)
