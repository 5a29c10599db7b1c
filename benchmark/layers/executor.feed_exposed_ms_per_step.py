"""Milliseconds a traced step's device sits idle while the Executor
is inside `pt.executor.feed` (normalising the feed and enqueueing its
host-to-device copy)."""

from benchmark.lib import scopes


def read(run):
    return scopes.exposed_ms_per_step(run, "pt.executor.feed")
