"""The measured window: the load a cell's traffic offers to the served
program, timed on the host's clock from when each window was due."""
from __future__ import annotations

import faulthandler
import threading
import time
from functools import partial
from typing import List

from benchkit.traffic import Plan, Request

ANSWER_GRACE_S = 60.0       # wait past the close for late answers
STALL_S = 0.25              # generator lateness that dumps every stack


def _finish(req: Request, fut) -> None:
    req.done = time.perf_counter()
    try:
        req.result = fut.result()
    except BaseException as e:          # noqa: BLE001 — recorded
        req.error = f"{type(e).__name__}: {e}"


def closed_loop(prog, plan: Plan, seconds: float) -> List[Request]:
    """`plan.sessions` sessions, each sending its next window when the
    last one returns. Whether round k + 1 is sent is decided once, by
    the first window of round k to return, so the sessions stay in step
    and every dispatch carries the same batch."""
    sids = prog.open_sessions(plan.sessions)
    lanes = [prog.lane_of(s) for s in sids]
    lock = threading.Lock()
    finished = threading.Event()
    reqs: List[Request] = []
    go = {}
    state = {"outstanding": 0}
    t0 = time.perf_counter()
    stop_at = t0 + seconds

    def submit(s: int, k: int) -> None:
        counts = plan.session_window(s, k)
        now = time.perf_counter()
        req = Request(index=0, counts=counts, due=now, session=s, k=k,
                      lane=lanes[s], submit=now)
        with lock:
            req.index = len(reqs)
            reqs.append(req)
            state["outstanding"] += 1
        try:
            fut = prog.submit(counts, session=sids[s])
        except BaseException as e:      # noqa: BLE001 — recorded
            req.error = f"{type(e).__name__}: {e}"
            done(req, None)
            return
        fut.add_done_callback(partial(done, req))

    def done(req: Request, fut) -> None:
        if fut is not None:
            _finish(req, fut)
        with lock:
            if req.k + 1 not in go:
                go[req.k + 1] = req.done < stop_at
            more = go[req.k + 1] and req.answered
        if more:
            submit(req.session, req.k + 1)
        with lock:
            state["outstanding"] -= 1
            if state["outstanding"] == 0:
                finished.set()

    for s in range(plan.sessions):
        submit(s, 0)
    prog.start()
    finished.wait(seconds + ANSWER_GRACE_S)
    return reqs


def open_loop(prog, plan: Plan, seconds: float,
              stall_file=None) -> List[Request]:
    """Submit each window at its due time, whatever the server does.
    With `stall_file` (an open file), a window submitted `STALL_S` or
    more after it was due has every thread's stack written there by
    `faulthandler`, whose timer thread runs without the GIL: the
    witness of what held the process while the generator was late."""
    cond = threading.Condition()
    state = {"sent": 0, "back": 0}

    def done(req: Request, fut) -> None:
        _finish(req, fut)
        with cond:
            state["back"] += 1
            cond.notify_all()

    prog.start()
    t0 = time.perf_counter()
    for req in plan.requests:
        req.due = t0 + req.due
        if stall_file is not None:
            faulthandler.dump_traceback_later(
                max(0.0, req.due - time.perf_counter()) + STALL_S,
                file=stall_file)
        while True:
            wait = req.due - time.perf_counter()
            if wait <= 0:
                break
            time.sleep(wait)
        req.submit = time.perf_counter()
        try:
            fut = prog.submit(req.counts, seed=req.seed)
        except BaseException as e:      # noqa: BLE001 — recorded
            req.error = f"{type(e).__name__}: {e}"
            continue
        with cond:
            state["sent"] += 1
        fut.add_done_callback(partial(done, req))
    if stall_file is not None:
        faulthandler.cancel_dump_traceback_later()
    deadline = t0 + seconds + ANSWER_GRACE_S
    with cond:
        cond.wait_for(lambda: state["back"] >= state["sent"],
                      timeout=max(0.0, deadline - time.perf_counter()))
    return plan.requests


LOOPS = {"closed": closed_loop, "open": open_loop}
