"""Device milliseconds a step spends in the flash-attention kernels,
forward and both backward calls, from the device trace."""


def read(run):
    t = run.trace
    if not t or not t["kernel_s"]:
        return None
    return sum(t["kernel_s"].values()) / t["steps"] * 1e3
