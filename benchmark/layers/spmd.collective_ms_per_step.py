"""Device milliseconds a step spends in collectives (all-reduce and
its kin; an asynchronous one from its start to its done), averaged
over the chips, from the device trace."""


def read(run):
    t = run.trace
    if not t or not t["collective_s"]:
        return None
    return t["collective_s"] / t["steps"] * 1e3
