"""The whole lane step's share of its roofline, in %: the least time the
cell's chips need for the window's necessary bytes and ops
(`benchkit.work`) over the device time of the lane-step program in the
traced window, averaged over the chips."""
import sys

from benchkit import work

STEP_PROGRAM = "jit__run_lanes_impl"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    busy = run.trace.busy_s(module=STEP_PROGRAM)
    steps = sum(r.counts.shape[0] for r in run.answered)
    if not busy or not steps:
        return None
    n_bytes, n_ops = work.necessary(run.net.n_neurons, steps,
                                    sum(run.events.values()))
    t, bound = work.least_time(n_bytes, n_ops, run.peaks, run.chips)
    print(f"mfu_step.sim: bound by {bound}", file=sys.stderr)
    return 100.0 * t / busy
