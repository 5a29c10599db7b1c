"""Milliseconds a traced step's device sits idle while the Executor
is inside `pt.executor.dispatch` (seating state and launching the
compiled step)."""

from benchmark.lib import scopes


def read(run):
    return scopes.exposed_ms_per_step(run, "pt.executor.dispatch")
