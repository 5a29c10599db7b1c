"""Seconds from process start to the first line of `paddle_tpu/__init__.py`
(start of the program's `setup.import` phase): the interpreter, the
benchmark's own imports, `import jax`, and reaching the chip in
`run.py: require_chip`."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.reach_s")
