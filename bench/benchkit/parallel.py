"""The reference's replay of sessions over worker processes.

Sessions are independent: each has its own lane, its own membranes and
its own PRNG stream. The parent draws each round's noise for every live
session (`reference.draws`, as the serial replay does) and writes it,
beside the reference's matrix and tables, into one block of shared
memory. Each worker integrates its own sessions' windows in order with
the reference's arithmetic (`Reference.integrate`), keeps their
membranes between rounds, and sends back each window's spikes and final
membranes. Workers are spawned, import numpy and scipy only, never JAX,
and read the block without copying it: the chip stays with the parent.

    out = replay_sessions(ref, runs, keys, worker_count(ref, 8, 16))
"""
from __future__ import annotations

import math
import mmap
import os
import traceback
from multiprocessing import connection, get_context, reduction
from typing import Dict, List, Sequence, Tuple

import numpy as np

from benchkit.reference import Reference, draws

Answer = Tuple[np.ndarray, np.ndarray]      # (spikes (T, N), V (N,))

# A worker takes about half a second to start; below this many synapses
# one process replays a run's sessions in less.
PARALLEL_ABOVE = 1 << 20
WORKER_BYTES = 256 << 20    # a worker's interpreter, numpy and scipy
LANE_BYTES = 128            # a session's membranes and temporaries a neuron
JOIN_S = 10.0               # a worker's wait to exit once told


def worker_count(ref: Reference, sessions: int, window: int) -> int:
    """Workers for replaying `sessions` sessions of `window` steps: one a
    session at most, no more than the host's CPUs, and as many as its
    free memory holds beside the shared block. 1 (the replay in this
    process) for a small network."""
    entries = ref.W.size if isinstance(ref.W, np.ndarray) else ref.W.nnz
    if sessions < 2 or entries < PARALLEL_ABOVE:
        return 1
    free = os.sysconf("SC_AVPHYS_PAGES") * os.sysconf("SC_PAGE_SIZE")
    shared = sum(v.nbytes for v in ref.parts().values()) \
        + sessions * window * ref.n * 4
    for n in range(min(sessions, os.cpu_count() or 1), 1, -1):
        lanes = math.ceil(sessions / n)
        per = WORKER_BYTES + lanes * ref.n * (8 * window + LANE_BYTES)
        if shared + n * per <= free:
            return n
    return 1


class Block:
    """Named arrays in one block of anonymous shared memory (a memfd: no
    name to leak, freed with its last mapping). A `Block` pickled into a
    spawned process passes its descriptor along."""

    def __init__(self, layout: Dict[str, Tuple[tuple, str]], fd: int = -1):
        self.layout = layout
        offsets, size = {}, 0
        for name, (shape, dtype) in layout.items():
            offsets[name] = size
            nbytes = math.prod(shape) * np.dtype(dtype).itemsize
            size += -(-nbytes // 64) * 64
        if fd < 0:
            fd = os.memfd_create("bench-reference", os.MFD_CLOEXEC)
            os.ftruncate(fd, max(size, 1))
        self.fd = fd
        self._map = mmap.mmap(fd, max(size, 1))
        self.arrays = {name: np.ndarray(shape, dtype, buffer=self._map,
                                        offset=offsets[name])
                       for name, (shape, dtype) in layout.items()}

    def __reduce__(self):
        return _attach, (self.layout, reduction.DupFd(self.fd))

    def close_fd(self) -> None:
        """Once every worker has its copy: the mapping stays."""
        if self.fd >= 0:
            os.close(self.fd)
            self.fd = -1


def _attach(layout, dupfd) -> Block:
    return Block(layout, dupfd.detach())


class Workers:
    """`n` spawned workers around one reference in shared memory. Session
    j goes to worker j % n, which keeps its membranes between rounds."""

    def __init__(self, ref: Reference, n: int, sessions: int, window: int):
        parts = ref.parts()
        layout = {k: (v.shape, v.dtype.str) for k, v in parts.items()}
        layout["u"] = ((sessions, window, ref.n), "<i4")
        self.block = Block(layout)
        for k, v in parts.items():
            self.block.arrays[k][...] = v
        self.u = self.block.arrays["u"]
        ctx = get_context("spawn")
        self.procs, self.conns = [], []
        self.rss = [0] * n          # each worker's largest RSS, KiB
        try:
            for _ in range(n):
                mine, theirs = ctx.Pipe()
                p = ctx.Process(target=_serve, args=(theirs, self.block),
                                daemon=True)
                p.start()
                theirs.close()
                self.procs.append(p)
                self.conns.append(mine)
        except BaseException:
            self.close(wait=False)
            raise
        finally:
            self.block.close_fd()

    def __enter__(self) -> "Workers":
        return self

    def __exit__(self, exc_type, *exc) -> None:
        self.close(wait=exc_type is None)

    def round(self, counts: Dict[int, np.ndarray]) -> Dict[int, Answer]:
        """One window of each session in `counts` (session -> (T, A)
        drive), its uniforms in `self.u[session, :T]`: session ->
        (spikes, V)."""
        rows: Dict[int, List[int]] = {}
        for j in sorted(counts):
            rows.setdefault(j % len(self.procs), []).append(j)
        for w, js in rows.items():
            try:
                self.conns[w].send((js, np.stack([counts[j] for j in js])))
            except OSError:
                raise self._died(w) from None
        out: Dict[int, Answer] = {}
        waiting = {self.conns[w]: w for w in rows}
        while waiting:
            for conn in connection.wait(list(waiting)):
                w = waiting.pop(conn)
                try:
                    tag, body = conn.recv()
                except EOFError:
                    raise self._died(w) from None
                if tag == "error":
                    raise RuntimeError(f"reference worker {w} failed:\n"
                                       f"{body}")
                spikes, V = body
                out.update((j, (spikes[i], V[i]))
                           for i, j in enumerate(rows[w]))
                # it holds this round's arrays until the next
                self.rss[w] = max(self.rss[w],
                              rss_kib(self.procs[w].pid))
        return out

    def _died(self, w: int) -> RuntimeError:
        self.procs[w].join(JOIN_S)
        return RuntimeError(f"reference worker {w} died (exit code "
                            f"{self.procs[w].exitcode})")

    def close(self, wait: bool = True) -> None:
        """Tell each worker to exit and wait for it; end it where it does
        not (`wait` False: at once)."""
        for conn in self.conns:
            try:
                conn.send(None)
            except OSError:
                pass
        for p in self.procs:
            p.join(JOIN_S if wait else 0)
            if p.is_alive():
                p.terminate()
                p.join()
        for conn in self.conns:
            conn.close()


def rss_kib(pid: int) -> int:
    """A process's resident set in KiB (VmRSS), 0 where /proc does not
    say. A spawned worker's `ru_maxrss` will not do: it starts at the
    peak of the process that spawned it."""
    try:
        with open(f"/proc/{pid}/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return 0


def _serve(conn, block: Block) -> None:
    """A worker: integrate each job (sessions, their drive) on the
    sessions' membranes until told to stop (None)."""
    try:
        parts = {k: v for k, v in block.arrays.items() if k != "u"}
        for v in parts.values():
            v.flags.writeable = False
        ref = Reference.from_parts(parts)
        V: Dict[int, np.ndarray] = {}
        while (job := conn.recv()) is not None:
            js, counts = job
            V0 = np.stack([V.get(j, np.zeros(ref.n, np.int32)) for j in js])
            spikes, Vn = ref.integrate(
                V0, block.arrays["u"][js, :counts.shape[1]], counts)
            V.update(zip(js, Vn))
            conn.send(("ok", (spikes, Vn)))
    except EOFError:
        pass                                # the parent is gone
    except Exception:
        conn.send(("error", traceback.format_exc()))


def replay_sessions(ref: Reference, runs: Sequence[Sequence], keys,
                    workers: int) -> Tuple[Dict[int, Answer], List[int]]:
    """Session j's windows `runs[j]` (requests, at least one), in order
    from a fresh lane whose stream starts at `keys[j]`, over `workers`
    workers: the serial replay's answers, by request index, and each
    worker's largest RSS in KiB, read after every round. The next
    round's noise is drawn while the workers integrate this one."""
    window = max(r.counts.shape[0] for run in runs for r in run)
    out: Dict[int, Answer] = {}
    with Workers(ref, workers, len(runs), window) as pool:
        live = list(range(len(runs)))
        pending = draws(np.asarray(keys), runs[0][0].counts.shape[0], ref.n)
        k = 0
        while live:
            u, keys_out = pending
            nxt = [i for i, j in enumerate(live) if len(runs[j]) > k + 1]
            if nxt:
                T = runs[live[nxt[0]]][k + 1].counts.shape[0]
                pending = draws(keys_out[np.asarray(nxt)], T, ref.n)
            u = np.asarray(u)
            pool.u[live, :u.shape[1]] = u
            got = pool.round({j: runs[j][k].counts for j in live})
            out.update((runs[j][k].index, a) for j, a in got.items())
            live = [live[i] for i in nxt]
            k += 1
    return out, pool.rss
