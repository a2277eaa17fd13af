"""Mean time a window waited in the server's queue (submit to batch
assembly), from the program's own `ServeResult.queue_wait_ms`."""
import numpy as np


def read(run):
    waits = [r.result.queue_wait_ms for r in run.answered]
    return float(np.mean(waits)) if waits else None
