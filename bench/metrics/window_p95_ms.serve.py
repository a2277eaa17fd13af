"""95th percentile latency over all windows of the run, each from when
it was due to its answer: the end-to-end tail, kept out of the
end-to-end metrics while stalls of the whole machine, seconds long in
some runs, spread it past any bound (PERF.md)."""
from benchkit.stats import latency_percentile


def read(run):
    return latency_percentile(run.requests, 95)
