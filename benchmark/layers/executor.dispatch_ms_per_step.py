"""The Executor's own `dispatch_ms` timer (gathering state, calling
the executable, committing new state), per step of the window."""


def read(run):
    ms = run.window_delta("dispatch_ms")
    return ms / run.window.steps if ms else None
