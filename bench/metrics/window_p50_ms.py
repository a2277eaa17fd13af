"""Median latency of all windows, each from when it was due to its
answer."""
from benchkit.stats import latency_percentile


def read(run):
    return latency_percentile(run.requests, 50)
