"""Seconds in the backend's compiler: the program's
`setup.backend_compile` phase (JAX's backend-compile events that no
persistent cache served), outside the reference check.  0 in a warm
run."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.compile_s")
