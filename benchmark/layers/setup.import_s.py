"""Seconds importing the package itself: the program's `setup.import`
phase with its children `setup.import/<subpackage>`, less what other
phases cover inside it."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.import_s")
