"""Process start to the start of the measured window: imports,
building the Program or step, weights from the seed, compile or cache
load, the reference check and the warm-up steps."""


def read(run):
    return run.setup_s
