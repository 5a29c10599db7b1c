"""Programs that set-up took from a persistent cache instead of
compiling: hits of JAX's compilation cache plus hits of the program's
AOT executable cache.  0 in the first run of a checkout."""

from benchmark.lib import counters


def read(run):
    return (run.setup_delta(counters.PERSISTENT_HIT)
            + run.setup_delta("aot_cache_hits"))
