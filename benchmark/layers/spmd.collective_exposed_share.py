"""Collective time during which no compute ran on that device, over
the traced window: the share of a step lost to communication that the
schedule did not hide."""


def read(run):
    t = run.trace
    if not t or not t["collective_s"]:
        return None
    return t["collective_exposed_s"] / t["window_s"]
