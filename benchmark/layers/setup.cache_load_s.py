"""Seconds loading compiled programs: the program's `setup.cache_load`
phase (JAX's persistent-cache retrievals and the compile requests they
served; the AOT executable cache's loads), outside the reference
check."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.cache_load_s")
