"""Whether what the timed path served is correct: every answered window
against the plain reference, bit for bit.

The numbers compared, each with its limit:
  spike_mismatch     spikes (window, step, neuron) that differ      <= 0
  membrane_mismatch  final membranes (window, neuron) that differ   <= 0
  unanswered         windows sent in the window and never answered  <= 0
  compared           windows compared                               >= 1
"""
from __future__ import annotations

from typing import Callable, Dict, List, Tuple

import numpy as np

from benchkit.reference import Reference, lane_keys, scratch_keys
from benchkit.traffic import Request

CHUNK = 64                  # stateless windows per reference call

LIMITS = {"spike_mismatch": ("<=", 0), "membrane_mismatch": ("<=", 0),
          "unanswered": ("<=", 0), "compared": (">=", 1)}

Answer = Tuple[np.ndarray, np.ndarray]      # (spikes (T, N), V (N,))


def passes(name: str, value) -> bool:
    op, lim = LIMITS[name]
    return value <= lim if op == "<=" else value >= lim


def served(req: Request) -> Answer:
    """What the program sent back for a request."""
    return (np.asarray(req.result.spikes, bool),
            np.asarray(req.result.membrane, np.int32))


def replay(ref: Reference, requests: List[Request], seed: int
           ) -> Dict[int, Answer]:
    """The reference's answer to each request, by request index.
    Stateless windows start from V = 0 on their own stream; each
    session's windows run in order from a fresh lane, and a session
    stops at its first request missing from `requests`."""
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
    if not by_session:
        return out
    sess = sorted(by_session)
    V = np.zeros((len(sess), ref.n), np.int32)
    keys = lane_keys(seed, [by_session[s][0].lane for s in sess])
    for k in range(max(len(v) for v in by_session.values())):
        live = [j for j, s in enumerate(sess)
                if [r.k for r in by_session[s][:k + 1]]
                == list(range(k + 1))]
        if not live:
            break
        batch = [by_session[sess[j]][k] for j in live]
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


def compare(ref: Reference, requests: List[Request], seed: int
            ) -> Dict[str, int]:
    """The program's run: every answered request against the
    reference; requests never answered count as `unanswered`."""
    ok = [r for r in requests if r.answered]
    return count(requests, replay(ref, ok, seed), served)
