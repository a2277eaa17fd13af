"""Where the harness finds each part of a cell, by the names in
`BENCHMARK.json`."""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_FILE = "BENCHMARK.json"


def load_json(path: Path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import one file by path (the metric files carry dots in their
    names, so they are not importable by module name)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    name = "bench_" + "_".join(path.with_suffix("").parts[-2:]) \
        .replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Layout:
    """One checkout's benchmark: `BENCHMARK.json` at `root`, the
    benchmark's own files under `root/bench`."""

    def __init__(self, root: Path):
        self.root = Path(root)
        self.bench_dir = self.root / "bench"
        self.spec = load_json(self.root / BENCH_FILE)

    def cell(self, workload: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == workload:
                return w
        names = [w["name"] for w in self.spec["workloads"]]
        raise KeyError(f"no workload {workload!r} in {BENCH_FILE} "
                       f"(have {names})")

    def config_entry(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no configuration {name!r} in {BENCH_FILE}")

    def config(self, name: str) -> dict:
        return load_json(self.root / self.config_entry(name)["file"])

    def traffic(self, name: str) -> dict:
        return load_json(self.bench_dir / "traffic" / f"{name}.json")

    def network_builder(self, kind: str) -> ModuleType:
        return load_module(self.bench_dir / "networks" / f"{kind}.py")

    def input_maker(self, kind: str) -> ModuleType:
        return load_module(self.bench_dir / "traffic" / "inputs"
                           / f"{kind}.py")

    def metrics(self, workload: str, trace: bool) -> List[dict]:
        """The cell's end-to-end metrics (trace off) or its per-layer
        metrics (trace on): every entry that lists the cell, or lists
        no cells at all."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if workload in m.get("workloads", [workload])]

    def metric_reader(self, name: str) -> ModuleType:
        """`metrics/<name>.py`. A metric read alike in cells that report
        different end-to-end metrics is split by name, one part each
        (`device_idle.serve`, `device_idle.sim`), since a metric moves
        one end-to-end metric; such parts share `metrics/<quantity>.py`
        unless one has a file of its own."""
        path = self.bench_dir / "metrics" / f"{name}.py"
        if not path.is_file() and "." in name:
            path = path.with_name(f"{name.split('.')[0]}.py")
        return load_module(path)

    def peaks(self) -> Dict[str, dict]:
        return load_json(self.bench_dir / "peaks.json")
