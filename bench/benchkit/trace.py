"""From a `jax.profiler` trace to the numbers the per-layer metrics read.

Two stages, so that the second can be checked on a small recorded file:

  extract(path)  the `.xplane.pb` file -> a plain dict of events: per
                 device plane its ops ("XLA Ops" line) and programs
                 ("XLA Modules" line), and the harness's own host spans
                 (profiler annotations named "bench.*"). All times are
                 nanoseconds on the profiler's clock, which the device
                 and host planes share.
  Trace(events)  the reductions: busy time as the union of op
                 intervals, host time not covered by any device op, the
                 top ops and the longest idle gaps with the host span
                 open during each.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")
_OP_NAME = re.compile(r"%?([^\s=]+)")
_MODULE_NAME = re.compile(r"^(.*?)(\(\d+\))?$")


def start(log_dir: Path) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # no event per Python call
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find_xplane(log_dir: Path) -> Optional[Path]:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def op_name(hlo_text: str) -> str:
    """"%fusion.6 = (s32[8]...) fusion(...)" -> "fusion.6"."""
    m = _OP_NAME.match(hlo_text.strip())
    return m.group(1) if m else hlo_text


def module_name(name: str) -> str:
    """"jit__run_lanes_impl(1591766...)" -> "jit__run_lanes_impl"."""
    return _MODULE_NAME.match(name).group(1)


def extract(xplane: Path) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(xplane))
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(op_name(e.name), int(e.start_ns),
                            int(e.end_ns)) for e in line.events]
                elif line.name == "XLA Modules":
                    mods = [(module_name(e.name), int(e.start_ns),
                             int(e.end_ns)) for e in line.events]
            devices[plane.name] = {"ops": ops, "modules": mods}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, int(e.start_ns),
                                     int(e.end_ns)))
    return {"devices": devices, "host": sorted(host, key=lambda s: s[1])}


def save(events: dict, path: Path) -> None:
    path.write_text(json.dumps(events))


# ------------------------------------------------------------ reductions
def union(intervals) -> List[Tuple[int, int]]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[int]] = []
    for a, b in sorted((int(a), int(b)) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: List[Tuple[int, int]], a: int, b: int) -> int:
    """Length of [a, b] that the merged intervals cover."""
    return sum(max(0, min(b, y) - max(a, x)) for x, y in merged
               if y > a and x < b)


def clip(intervals, a: int, b: int) -> List[Tuple[int, int]]:
    return [(max(x, a), min(y, b)) for x, y in intervals
            if y > a and x < b]


class Trace:
    """Reductions over one extracted trace."""

    def __init__(self, events: dict):
        self.devices = events["devices"]
        self.host = [tuple(s) for s in events["host"]]
        windows = [s for s in self.host if s[0] == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        _, self.w0, self.w1 = windows[0]
        self._busy = {d: union(clip([(a, b) for _, a, b in v["ops"]],
                                    self.w0, self.w1))
                      for d, v in self.devices.items()}

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def busy_s(self, module: Optional[str] = None) -> Optional[float]:
        """Seconds in the window in which some op ran on the device
        (of `module` only, if given), averaged over the devices; None
        where the trace holds no device."""
        if not self.devices:
            return None
        total = 0
        for d, v in self.devices.items():
            if module is None:
                total += sum(b - a for a, b in self._busy[d])
                continue
            spans = union(clip([(a, b) for m, a, b in v["modules"]
                                if m == module], self.w0, self.w1))
            ops = union(clip([(a, b) for _, a, b in v["ops"]],
                             self.w0, self.w1))
            total += sum(covered(ops, a, b) for a, b in spans)
        return total * 1e-9 / len(self.devices)

    def spans(self, name: str) -> List[Tuple[int, int]]:
        return [(a, b) for n, a, b in self.host
                if n == name and a >= self.w0 and b <= self.w1]

    def host_only_s(self, name: str) -> List[float]:
        """For each host span `name` in the window: its seconds in which
        no device op ran (on any device)."""
        allbusy = union([iv for v in self._busy.values() for iv in v])
        return [((b - a) - covered(allbusy, a, b)) * 1e-9
                for a, b in self.spans(name)]

    def top_ops(self, n: int = 10) -> List[list]:
        """Device seconds in the window by op name, averaged over the
        devices; control flow (while, conditional, call), whose time is
        that of the ops it holds, is left out."""
        tot: Dict[str, int] = defaultdict(int)
        for v in self.devices.values():
            for name, a, b in v["ops"]:
                if name.split(".")[0] in CONTAINERS:
                    continue
                if b > self.w0 and a < self.w1:
                    tot[name] += min(b, self.w1) - max(a, self.w0)
        k = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t * 1e-9 / k] for name, t in top]

    def _host_at(self, t: int) -> str:
        """Innermost harness span open at time t."""
        best = None
        for name, a, b in self.host:
            if a <= t < b and (best is None or b - a < best[1]):
                best = (name, b - a)
        return best[0] if best else "none"

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Longest stretches of the window with no op on any device,
        each named by the harness span open at its midpoint."""
        allbusy = union([iv for v in self._busy.values() for iv in v])
        gaps, t = [], self.w0
        for a, b in allbusy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.w1 > t:
            gaps.append((t, self.w1))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self._host_at((a + b) // 2), (b - a) * 1e-9]
                for a, b in gaps[:n]]
