"""The slowest chunk of the window, per step: what a stall costs."""


def read(run):
    w = run.window
    return max(w.chunk_s) / w.fetch_every * 1e3
