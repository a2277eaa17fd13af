"""Device time of the lane step's phase-2 fan-in accumulate per
dispatched lane-step, in us: the traced window's ops that the program
runs under the named scope `accumulate`, with the ops XLA inserts
between two of them (`program_trace.Phases`), over the lane-steps of
the answered windows' dispatches (bucket lanes x steps). Logs the step
program's device time by phase, the share of it a phase names, the top
ops with their phase, and the spread of the `run_lanes` anchors' clock
offsets."""
import sys
from pathlib import Path

from benchkit import program_trace as pt

TRACES = Path(__file__).resolve().parents[1] / ".cache" / "trace"


def log(msg: str) -> None:
    print(f"accumulate_us: {msg}", file=sys.stderr, flush=True)


def read(run):
    if run.trace is None:
        return None
    events = pt.find(TRACES, (run.trace.w0, run.trace.w1))
    if events is None:
        return None
    ph = pt.Phases(events)
    by = ph.phase_s(pt.STEP_PROGRAM)
    shares = [ph.named_share(own=own) for own in (True, False)]
    offs = ph.offsets()
    log(f"{pt.STEP_PROGRAM} device s by phase: " + ", ".join(
        f"{p or 'none'} {s:.6f}" for p, s in sorted(
            by.items(), key=lambda kv: -kv[1]))
        + "; named by the ops' own scopes, and with the ops XLA put "
        "between two of one phase: " + ", ".join(
            "none" if x is None else f"{100 * x:.3f}%" for x in shares))
    named = ph.named_ops()
    log("top ops: " + ", ".join(
        f"{name}/{named.get(name, 'none')} {s:.6f}"
        for name, s in run.trace.top_ops()))
    if offs:
        log(f"{len(offs)} run_lanes anchors, offsets spread "
            f"{(max(offs) - min(offs)) / 1e3:.3f} us")
    steps = pt.lane_steps(run.requests)
    acc = by.get("accumulate")
    return 1e6 * acc / steps if acc and steps else None
