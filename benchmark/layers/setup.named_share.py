"""Share of `setup_s` that the nine `setup.*_s` times name: 1 less the
unnamed rest (the benchmark's batch pool, conditioning of weights,
gaps between phases) over `setup_s`."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.named_share")
