"""Share of the traced window's summed device op time that
`obs.devprof.device_time` puts under a name the program gave it
(benchmark/lib/scopes.py); the rest is its `unattributed` bin."""

from benchmark.lib import scopes


def read(run):
    return scopes.named_share(run)
