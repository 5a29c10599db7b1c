"""Device milliseconds a traced step spends in the expert layers
outside the router and the experts, forward and backward: the sort of
the visits by expert, the gather of a chunk's rows (`dispatch`), the
scatter-add of the weighted results (`combine`), and the walk over the
chunks itself (the `while`, its `cond`, the carries)."""

import re

from benchmark.lib import scopes

_MOE = re.compile(r"(^|/)moe(/|$)")
_NOT = re.compile(r"(^|/)moe/(.*/)?(router(/|$)|experts(/|$)|ragged-dot)")


def read(run):
    t = scopes.table(run)
    if t is None:
        return None
    seconds = sum(s for (phase, path), s in t["by_name"].items()
                  if phase in ("fwd", "bwd") and _MOE.search(path)
                  and not _NOT.search(path))
    return seconds / t["steps"] * 1e3
