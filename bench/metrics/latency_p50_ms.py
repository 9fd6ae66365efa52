"""Median client-side latency (ms), submit to answer, over every request
of the window (host clock)."""
from benchlib.stats import latency_percentile


def read(ctx):
    return latency_percentile(ctx.window, 50)
