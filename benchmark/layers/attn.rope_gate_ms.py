"""Device milliseconds a traced step spends under the `self_attn` of
both attention kinds outside the four large projections (`q_proj`,
`k_proj`, `v_proj`, `o_proj`) and outside the flash kernels, forward and
backward: both rotations (the plain one of the whole head, YaRN's of
half of it), the gate's narrow projection `g_proj`, its sigmoid and its
multiply, the reshapes between them and the row sums the backward
kernels are handed (delta) — what the layers pay around their matmuls
and their kernels.  The kernels' own time is the device trace's, by the
kinds benchmark/configs/laguna.py: _kernel_calls names."""

import re

from benchmark.lib import scopes

_ATTN = re.compile(r"(^|/)layers/\d+/self_attn(/|$)")
_PROJECTIONS = re.compile(r"(^|/)(q_proj|k_proj|v_proj|o_proj)(/|$)")


def read(run):
    t = scopes.table(run)
    if t is None or "sliding_window" not in run.config:
        return None
    seconds = sum(s for (phase, path), s in t["by_name"].items()
                  if phase in ("fwd", "bwd") and _ATTN.search(path)
                  and not _PROJECTIONS.search(path))
    kernels = sum(s for kind, s in run.trace["kernel_s"].items()
                  if "_flash_" in kind)
    return (seconds - kernels) / t["steps"] * 1e3
