"""Arithmetic shared by the metric readers."""
from __future__ import annotations

import math

import numpy as np


def latencies_ms(requests):
    """Due-to-answer latency of every window sent; a window never
    answered counts as infinitely late."""
    return [1e3 * (r.done - r.due) if r.answered else math.inf
            for r in requests]


def latency_percentile(requests, q: float):
    lat = latencies_ms(requests)
    if not lat:
        return None
    with np.errstate(invalid="ignore"):
        v = float(np.percentile(np.asarray(lat), q))
    return v if math.isfinite(v) else None


def mean_host_ms(run, span: str = "bench.run_lanes"):
    """Mean per dispatch of the harness span's time in which no device
    op ran."""
    if run.trace is None:
        return None
    host = run.trace.host_only_s(span)
    return 1e3 * float(np.mean(host)) if host else None


def idle_share(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    busy = run.trace.busy_s()
    return None if busy is None else \
        100.0 * (1.0 - busy / run.trace.window_s)
