"""Seconds of the benchmark's span `setup.warm_up` (the program's load
and the warm-up steps), less what the program's phases cover inside it
(the Executor's first call compiles or loads there)."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.warm_up_s")
