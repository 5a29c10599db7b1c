"""Seconds making weights and optimizer state: self time of the program's
`setup.param_init` (`nn.Layer.create_parameter`: draw, cast, copy to the
device) and `setup.state_build` (master weights and moments in the
step builders) phases, outside the reference check."""

from benchmark.lib import setup_phases


def read(run):
    return setup_phases.of_run(run, "setup.param_init_s")
