"""Seconds of Python tracing and lowering to StableHLO, which every
process pays whatever the caches hold: self time of the program's
`setup.trace`, `setup.lower`, `setup.transform` and `setup.verify`
phases (JAX's own events; the Executor's passes), outside the
reference check."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.trace_lower_s")
