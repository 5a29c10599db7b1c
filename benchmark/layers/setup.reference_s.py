"""Seconds of the benchmark's own reference check: its span
`setup.reference`, with everything the program did inside it."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.reference_s")
