"""Whether what the timed path served is correct: every answered window
against the plain reference, bit for bit.

The numbers compared, each with its limit:
  spike_mismatch     spikes (window, step, neuron) that differ      <= 0
  membrane_mismatch  final membranes (window, neuron) that differ   <= 0
  unanswered         windows sent in the window and never answered  <= 0
  compared           windows compared                               >= 1
"""
from __future__ import annotations

import os
import resource
import time
from itertools import takewhile
from typing import Callable, Dict, List, Optional

import numpy as np

from benchkit import parallel
from benchkit.parallel import Answer
from benchkit.reference import Reference, lane_keys, scratch_keys
from benchkit.traffic import Request

CHUNK = 64                  # stateless windows per reference call

LIMITS = {"spike_mismatch": ("<=", 0), "membrane_mismatch": ("<=", 0),
          "unanswered": ("<=", 0), "compared": (">=", 1)}


def passes(name: str, value) -> bool:
    op, lim = LIMITS[name]
    return value <= lim if op == "<=" else value >= lim


def served(req: Request) -> Answer:
    """What the program sent back for a request."""
    return (np.asarray(req.result.spikes, bool),
            np.asarray(req.result.membrane, np.int32))


def replay(ref: Reference, requests: List[Request], seed: int, *,
           log: Optional[Callable[[str], None]] = None
           ) -> Dict[int, Answer]:
    """The reference's answer to each request, by request index.
    Stateless windows start from V = 0 on their own stream; each
    session's windows run in order from a fresh lane, and a session
    stops at its first request missing from `requests`. Sessions are
    replayed over worker processes where the network is large enough
    to pay for them (`parallel.worker_count`), with the same answers."""
    t0 = time.perf_counter()
    out: Dict[int, Answer] = {}
    stateless = [r for r in requests if r.session is None]
    for i in range(0, len(stateless), CHUNK):
        batch = stateless[i:i + CHUNK]
        V0 = np.zeros((len(batch), ref.n), np.int32)
        keys = scratch_keys(seed, [r.seed for r in batch])
        spikes, V, _ = ref.run(V0, keys, np.stack([r.counts
                                                   for r in batch]))
        for j, r in enumerate(batch):
            out[r.index] = (spikes[j], V[j])

    by_session: Dict[int, List[Request]] = {}
    for r in sorted((r for r in requests if r.session is not None),
                    key=lambda r: (r.session, r.k)):
        by_session.setdefault(r.session, []).append(r)
    # each session's windows up to its first missing one
    runs = {s: [r for _, r in takewhile(lambda p: p[1].k == p[0],
                                        enumerate(v))]
            for s, v in by_session.items()}
    sess = [s for s in sorted(runs) if runs[s]]
    workers, rss = 1, []
    if sess:
        keys = lane_keys(seed, [runs[s][0].lane for s in sess])
        window = max(r.counts.shape[0] for s in sess for r in runs[s])
        workers = parallel.worker_count(ref, len(sess), window)
        if workers > 1:
            answers, rss = parallel.replay_sessions(
                ref, [runs[s] for s in sess], keys, workers)
            out.update(answers)
        else:
            out.update(_replay_in_step(ref, [runs[s] for s in sess], keys))
    if log is not None:
        log(f"reference replay: {time.perf_counter() - t0:.2f} s, "
            f"{len(stateless)} stateless windows, {len(sess)} sessions "
            f"over {workers} workers (os.cpu_count() {os.cpu_count()})")
        own = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        parts = f"; {len(rss)} workers {sum(rss)}, the largest " \
            f"{max(rss)}, read after each round, the shared block in " \
            f"each" if rss else ""
        log(f"process tree peak RSS after the check: {own + sum(rss)} "
            f"KiB (this process {own}{parts})")
    return out


def _replay_in_step(ref: Reference, runs: List[List[Request]], keys
                    ) -> Dict[int, Answer]:
    """Every session's windows in this process, in lock step: round k
    runs the k-th window of each session that has one."""
    out: Dict[int, Answer] = {}
    V = np.zeros((len(runs), ref.n), np.int32)
    for k in range(max(len(run) for run in runs)):
        live = [j for j, run in enumerate(runs) if len(run) > k]
        batch = [runs[j][k] for j in live]
        spikes, Vj, kj = ref.run(V[live], keys[live],
                                 np.stack([r.counts for r in batch]))
        V[live], keys[live] = Vj, kj
        for j, r in enumerate(batch):
            out[r.index] = (spikes[j], Vj[j])
    return out


def count(requests: List[Request], want: Dict[int, Answer],
          answer: Callable[[Request], Answer]) -> Dict[str, int]:
    """The compared numbers for `answer` against `want`."""
    out = {"spike_mismatch": 0, "membrane_mismatch": 0,
           "unanswered": 0, "compared": 0}
    for r in requests:
        if r.index not in want:
            out["unanswered"] += 1
            continue
        s, v = answer(r)
        want_s, want_v = want[r.index]
        want_s = want_s[:s.shape[0]] if s.ndim == 2 else want_s
        if s.shape != want_s.shape or v.shape != want_v.shape:
            out["spike_mismatch"] += int(want_s.size)
            out["membrane_mismatch"] += int(want_v.size)
        else:
            out["spike_mismatch"] += int(np.sum(s != want_s))
            out["membrane_mismatch"] += int(np.sum(v != want_v))
        out["compared"] += 1
    return out


def compare(ref: Reference, requests: List[Request], seed: int, *,
            log: Optional[Callable[[str], None]] = None) -> Dict[str, int]:
    """The program's run: every answered request against the
    reference; requests never answered count as `unanswered`."""
    ok = [r for r in requests if r.answered]
    return count(requests, replay(ref, ok, seed, log=log), served)
