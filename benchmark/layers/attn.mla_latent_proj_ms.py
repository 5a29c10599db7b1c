"""Device milliseconds a traced step spends under `self_attn` outside
the flash kernels, forward and backward: the six projections (`q_a_proj`,
`q_b_proj`, `kv_a_proj_with_mqa`, `kv_b_proj`, `o_proj`), the two
latent norms, the rotation of the 64-wide parts and the assembly of q,
k and v — what latent attention pays for its small cache."""

import re

from benchmark.lib import scopes

_ATTN = re.compile(r"(^|/)self_attn(/|$)")
_KERNEL = re.compile(r"(^|/)flash_(fwd|bwd_dkv|bwd_dq)(/|$)")


def read(run):
    t = scopes.table(run)
    if t is None:
        return None
    seconds = sum(s for (phase, path), s in t["by_name"].items()
                  if phase in ("fwd", "bwd") and _ATTN.search(path)
                  and not _KERNEL.search(path))
    return seconds / t["steps"] * 1e3
