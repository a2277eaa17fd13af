"""Lane-timesteps answered per second: every step of every window
answered, over the whole window (first window sent to last answer)."""


def read(run):
    span = run.t_end - run.t0
    steps = sum(r.counts.shape[0] for r in run.answered)
    return steps / span if steps and span > 0 else None
