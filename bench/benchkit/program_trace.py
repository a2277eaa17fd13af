"""The program's own names for its time: the phase of each op of the
lane step and the `run_lanes` annotations on a device trace, which put
the program's spans on the trace's clock, and the dispatcher's span
totals.

The engine runs each phase of its lane step under a `jax.named_scope`
(`PHASES`), so a compiled op's scope path names its phase. The trace
carries the path in each op's metadata, as `tf_op`; `jax.profiler`
exposes it only in the trace viewer's export it writes beside the
`.xplane.pb` (`*.trace.json.gz`), keyed there by the op's HLO text
(`long_name`), the op event's name in the `.xplane.pb`.
`Deployment.run_lanes` opens a profiler annotation `run_lanes` whose
`mono_ns` stat is the monotonic start of its own `repro.obs.Tracer`
span: the annotation's start minus `mono_ns` is the offset from the
tracer's clock to the trace's.

  extract(xplane)  the `.xplane.pb` file -> a plain dict: the harness's
                   window, per device plane its ops as [name, phase,
                   start, end] and its programs, and the anchors
                   [[start, mono_ns], ...]. Times in ns on the trace's
                   clock.
  Phases(events)   the reductions: device seconds by phase in the
                   window, the share of a program's op time put down to
                   a phase, the anchor offsets, and the idle time named
                   by the innermost host span open in it.
  stage_mean_ms    mean per dispatch of named dispatcher spans, from
                   `SpikeServer.stats()["stages"]`.

A program without the scopes, the annotation or the stage totals gives
ops with no phase, no anchors and no stages; the readers then return
None.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
import statistics
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from benchkit import trace as tr

PHASES = ("noise", "fire", "gate", "accumulate", "integrate")
ANCHOR = "run_lanes"
STEP_PROGRAM = "jit__run_lanes_impl"
_PHASE = re.compile(r"(?:^|/)(" + "|".join(PHASES) + r")(?=[/:]|$)")


def phase_of(path: str) -> Optional[str]:
    """The innermost of `PHASES` named as a segment of a scope path:
    "jit(f)/while/body/closed_call/accumulate/gather" -> "accumulate"."""
    found = _PHASE.findall(path or "")
    return found[-1] if found else None


def op_phases(xplane: Path) -> Dict[str, str]:
    """{op's HLO text: phase} over the ops of the trace viewer's export
    beside `xplane` whose `tf_op` names a phase."""
    out: Dict[str, str] = {}
    for path in Path(xplane).parent.glob("*.trace.json.gz"):
        with gzip.open(path, "rt") as f:
            events = json.load(f).get("traceEvents", [])
        for e in events:
            args = e.get("args") or {}
            phase = phase_of(args.get("tf_op"))
            if phase is not None and "long_name" in args:
                out[args["long_name"]] = phase
    return out


def extract(xplane: Path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    phases = op_phases(xplane)
    devices, anchors, window = {}, [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [[tr.op_name(e.name), phases.get(e.name),
                            int(e.start_ns), int(e.end_ns)]
                           for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [[tr.module_name(e.name), int(e.start_ns),
                             int(e.end_ns)] for e in line.events]
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR:
                        mono = dict(e.stats).get("mono_ns")
                        if mono is not None:
                            anchors.append([int(e.start_ns), int(mono)])
                    elif e.name == tr.WINDOW_SPAN and window is None:
                        window = [int(e.start_ns), int(e.end_ns)]
    return {"window": window, "devices": devices,
            "anchors": sorted(anchors)}


def find(trace_root: Path, window: Sequence[int]) -> Optional[dict]:
    """The extracted trace under `trace_root` (one directory per cell)
    whose harness window is `window` [w0, w1]: the run's own, found
    among the newest first."""
    found = sorted(Path(trace_root).glob("*/plugins/profile/*/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime, reverse=True)
    for path in found:
        events = extract(path)
        if events["window"] == [int(window[0]), int(window[1])]:
            return events
    return None


class Phases:
    """Reductions over one extracted trace.

    An op XLA inserts (a copy, a transpose, a loop it builds) carries no
    scope path. One that runs, with no named op between, after and
    before ops of one phase in the same program run is put down to that
    phase: `own=True` reductions leave it unnamed."""

    def __init__(self, events: dict):
        if events.get("window") is None:
            raise ValueError(f"trace has no {tr.WINDOW_SPAN!r} span")
        self.w0, self.w1 = events["window"]
        self.devices = events["devices"]
        self.anchors = [tuple(a) for a in events["anchors"]]
        self._between = {d: between_phases(v) for d, v in
                         self.devices.items()}

    def _ops(self, module: Optional[str] = None, own: bool = False):
        """(device, name, phase, start, end) of every op in the window
        (in `module`'s runs only, if given), control flow left out."""
        for d, v in self.devices.items():
            runs = None if module is None else tr.union(
                (a, b) for m, a, b in v["modules"] if m == module)
            starts = [a for a, _ in runs or ()]
            for i, (name, phase, a, b) in enumerate(v["ops"]):
                if name.split(".")[0] in tr.CONTAINERS:
                    continue
                a, b = max(a, self.w0), min(b, self.w1)
                if b <= a:
                    continue
                if runs is not None:
                    k = bisect.bisect_right(starts, (a + b) // 2) - 1
                    if k < 0 or runs[k][1] <= (a + b) // 2:
                        continue
                if phase is None and not own:
                    phase = self._between[d].get(i)
                yield d, name, phase, a, b

    def phase_s(self, module: Optional[str] = None,
                own: bool = False) -> Dict[str, float]:
        """Device seconds by phase in the window (averaged over the
        devices); ops with no phase under None."""
        tot: Dict[Optional[str], int] = defaultdict(int)
        for _, _, phase, a, b in self._ops(module, own):
            tot[phase] += b - a
        k = max(len(self.devices), 1)
        return {p: t * 1e-9 / k for p, t in tot.items()}

    def named_share(self, module: str = STEP_PROGRAM,
                    own: bool = False) -> Optional[float]:
        """Share of `module`'s op time in the window that a phase
        names; None where no op of it ran or none carries a phase."""
        by = self.phase_s(module, own)
        total = sum(by.values())
        named = total - by.get(None, 0.0)
        return named / total if total > 0 and named > 0 else None

    def named_ops(self) -> Dict[str, str]:
        """{op name: phase} over the window's ops that carry one; the
        programs of several batch shapes may give one name to ops of
        different phases, which are then joined by "|"."""
        seen: Dict[str, set] = defaultdict(set)
        for _, name, phase, _, _ in self._ops():
            if phase is not None:
                seen[name].add(phase)
        return {name: "|".join(sorted(p)) for name, p in seen.items()}

    def offsets(self) -> List[int]:
        """Per anchor, the trace's clock minus the tracer's (ns)."""
        return [a - mono for a, mono in self.anchors]

    def offset(self) -> Optional[int]:
        offs = self.offsets()
        return int(statistics.median(offs)) if offs else None

    def program_spans(self, spans) -> List[Tuple[str, int, int]]:
        """`repro.obs` spans (or their dicts) on the trace's clock, by
        the median anchor offset."""
        off = self.offset()
        if off is None:
            return []
        out = []
        for s in spans:
            d = s if isinstance(s, dict) else s.to_dict()
            if d.get("end") is not None:
                out.append((d["name"], d["start"] + off, d["end"] + off))
        return out

    def _gaps(self) -> List[Tuple[int, int]]:
        """Stretches of the window with no op on any device."""
        busy = tr.union(tr.clip([(a, b) for v in self.devices.values()
                                 for _, _, a, b in v["ops"]],
                                self.w0, self.w1))
        gaps, t = [], self.w0
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.w1 > t:
            gaps.append((t, self.w1))
        return gaps

    def idle_gaps(self, host: Sequence[Tuple[str, int, int]],
                  n: int = 10) -> List[list]:
        """The `n` longest stretches of the window with no op on any
        device, each named by the innermost of the `host` spans
        (harness annotations and program spans on the trace's clock)
        open at its midpoint."""
        gaps = sorted(self._gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[innermost(host, (a + b) // 2), (b - a) * 1e-9]
                for a, b in gaps]

    def idle_by_span(self, host: Sequence[Tuple[str, int, int]]
                     ) -> Dict[str, float]:
        """All idle seconds of the window, each instant put down to the
        innermost of the `host` spans open then."""
        out: Dict[str, float] = defaultdict(float)
        for a, b in self._gaps():
            inside = [s for s in host if s[1] < b and s[2] > a]
            cuts = sorted({a, b} | {t for _, x, y in inside
                                    for t in (x, y) if a < t < b})
            for x, y in zip(cuts, cuts[1:]):
                out[innermost(inside, (x + y) // 2)] += (y - x) * 1e-9
        return dict(out)


def between_phases(device: dict) -> Dict[int, str]:
    """{index of an op with no phase: the phase of the named ops on both
    sides of it}, for the unnamed ops that run, with no named op
    between, after and before ops of one phase in one program run."""
    runs = sorted((a, b) for _, a, b in device["modules"])
    starts = [a for a, _ in runs]

    def run_of(t):
        return bisect.bisect_right(starts, t) - 1

    ops = device["ops"]
    out: Dict[int, str] = {}
    last, pending = None, []          # (run, phase) of the last named op
    for i in sorted(range(len(ops)), key=lambda j: ops[j][2]):
        name, phase, a, _ = ops[i]
        if name.split(".")[0] in tr.CONTAINERS:
            continue
        if phase is None:
            pending.append(i)
            continue
        here = (run_of(a), phase)
        if last == here:
            out.update((j, phase) for j in pending
                       if run_of(ops[j][2]) == here[0])
        last, pending = here, []
    return out


def innermost(spans: Sequence[Tuple[str, int, int]], t: int) -> str:
    """Name of the shortest span open at time t."""
    best = None
    for name, a, b in spans:
        if a <= t < b and (best is None or b - a < best[1]):
            best = (name, b - a)
    return best[0] if best else "none"


def lane_steps(requests) -> int:
    """Lane-steps the device ran for the answered windows: each
    dispatch runs its bucket's lanes (scratch rows included) for the
    window, shared by its `batch_size` requests."""
    total = 0.0
    for r in requests:
        res = r.result
        if r.answered and res.batch_size:
            total += res.bucket / res.batch_size * r.counts.shape[0]
    return round(total)


def stage_mean_ms(run, names: Sequence[str]) -> Optional[float]:
    """Mean ms per dispatch of the named dispatcher spans together, from
    the totals `SpikeServer.stats()` keeps under "stages" (None where
    the program keeps none, or a stage missed some dispatch)."""
    stages = (run.server_stats or {}).get("stages") or {}
    if not names or any(n not in stages for n in names):
        return None
    count = stages[names[0]]["dispatches"]
    if not count or any(stages[n]["dispatches"] != count for n in names):
        return None
    return sum(stages[n]["total_ms"] for n in names) / count
