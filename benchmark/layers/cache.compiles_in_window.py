"""Compile requests inside the measured window, from JAX's own compile
event and the Executor's `executor_compile_count`.  Must be 0: it is
part of `correct`."""

from benchmark.lib import counters


def read(run):
    return (run.window_delta(counters.COMPILE_REQUEST)
            + run.window_delta("executor_compile_count"))
