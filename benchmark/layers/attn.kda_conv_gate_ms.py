"""Device milliseconds a traced step spends under a KDA layer's
`self_attn` outside the four large projections (`q_proj`, `k_proj`,
`v_proj`, `o_proj`) and outside the scan (`kda_core`), forward and
backward: the three short convolutions with their SiLU, the L2 norms of
q and k, the two low-rank gate paths, beta, and the gated per-head norm
— what the layer pays around its matmuls and its recurrence."""

import re

from benchmark.lib import flops_kimi_linear, scopes

_REST = re.compile(r"(^|/)(q_proj|k_proj|v_proj|o_proj|kda_core)(/|$)")


def read(run):
    t = scopes.table(run)
    if t is None or "linear_attn_config" not in run.config:
        return None
    attn = re.compile(flops_kimi_linear.self_attn_pattern(run.config, "kda"))
    seconds = sum(s for (phase, path), s in t["by_name"].items()
                  if phase in ("fwd", "bwd") and attn.search(path)
                  and not _REST.search(path))
    return seconds / t["steps"] * 1e3
