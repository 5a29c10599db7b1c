"""1 - (union of the intervals in which an operation ran on the device
/ traced window), averaged over the chips used."""


def read(run):
    t = run.trace
    if not t or not t["window_s"]:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
