"""A per-layer metric no file of the harness knows."""


def read(run):
    return run.system.stepped
