"""The Executor's own `host_feed_ms` timer (normalising the feed and
enqueueing its host-to-device copy), per step of the window.  Nothing
for a system that does not run through the Executor."""


def read(run):
    ms = run.window_delta("host_feed_ms")
    return ms / run.window.steps if ms else None
