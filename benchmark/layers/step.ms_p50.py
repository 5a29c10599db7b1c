"""Median over the window's chunks of chunk seconds / steps a chunk,
on the host clock, each chunk ending when its loss reached the host."""

import statistics


def read(run):
    w = run.window
    return statistics.median(w.chunk_s) / w.fetch_every * 1e3
