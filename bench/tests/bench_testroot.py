"""A checkout-shaped directory for the benchmark's tests: a copy of
`bench/` and `BENCHMARK.json`, plus tiny configurations (one for each
deploy target), two tiny traffic mixes and cells that use them, all
added as new files and new entries only."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
for p in (str(REPO / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_LIF = {"kind": "random_lif", "axons": 32, "synapses_per_source": 40,
            "max_in_degree": 70,
            "neuron": {"model": "LIF", "threshold": 60000, "nu": 0,
                       "lam": 4}}
# four cores of 150 neurons, two FPGAs of two cores: a mesh over four
# devices exchanges spikes at both levels
TINY_HIERARCHY = {"cores": 4, "neurons_per_core": 150, "servers": 1,
                  "fpgas_per_server": 2, "cores_per_fpga": 2}

TINY_FILES = {
    "configs/tiny-core.json": {
        "name": "tiny-core", "target": "engine", "cores": 1,
        "neurons_per_core": 600, **TINY_LIF},
    "configs/tiny-hiaer.json": {
        "name": "tiny-hiaer", "target": "hiaer", **TINY_HIERARCHY,
        **TINY_LIF},
    "configs/tiny-mesh.json": {
        "name": "tiny-mesh", "target": "mesh", **TINY_HIERARCHY,
        **TINY_LIF},
    "configs/tiny-mlp.json": {
        "name": "tiny-mlp", "kind": "layered", "target": "engine",
        "inputs": 784,
        "layers": [40, 20, 10],
        "hidden_neuron": {"model": "ANN", "threshold": 0, "nu": -32},
        "output_neuron": {"model": "ANN", "threshold": 2 ** 30,
                          "nu": -32}},
    "traffic/tiny_sessions.json": {
        "loop": "closed", "sessions": 4, "window": 8, "max_batch": 4,
        "max_wait_ms": 2.0, "input": {"kind": "bernoulli", "rate": 0.1}},
    "traffic/tiny_poisson.json": {
        "loop": "open", "rate_per_s": 20.0, "arrival_seed": 3,
        "window": 8, "max_batch": 4, "max_wait_ms": 2.0,
        "input": {"kind": "digits", "on_rate": 0.5, "flip": 0.03}},
}
# new one-chip cell -> (config, traffic, the benchmark's cell whose
# metrics it takes)
TINY_CELLS = {"tiny.sim": ("tiny-core", "tiny_sessions", "core125k.sim"),
              "tiny.serve": ("tiny-mlp", "tiny_poisson", "mlp.serve"),
              "tiny.hiaer": ("tiny-hiaer", "tiny_sessions", "core125k.sim"),
              "tiny.mesh": ("tiny-mesh", "tiny_sessions", "core125k.sim")}
# the same for a cell on four chips, which runs in a process that has
# four devices
TINY_CELLS_4 = {"tiny.mesh4": ("tiny-mesh", "tiny_sessions",
                               "core125k.sim")}


def target_of(cell: str) -> str:
    """The deploy target a tiny cell's configuration names."""
    config = {**TINY_CELLS, **TINY_CELLS_4}[cell][0]
    return TINY_FILES[f"configs/{config}.json"]["target"]


def make_root(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for rel, body in TINY_FILES.items():
        path = root / "bench" / rel
        if path.exists():
            raise FileExistsError(path)
        path.write_text(json.dumps(body))
        if rel.startswith("configs/"):
            spec["configs"].append({
                "name": body["name"], "source": "tiny test network",
                "file": f"bench/{rel}", "reduced": [], "why": "tiny"})
    cells = [(cell, parts, 1) for cell, parts in TINY_CELLS.items()] \
        + [(cell, parts, 4) for cell, parts in TINY_CELLS_4.items()]
    for cell, (config, traffic, like), chips in cells:
        spec["workloads"].append({"name": cell, "config": config,
                                  "traffic": traffic, "chips": chips,
                                  "why": "tiny"})
        for group in ("end_to_end", "per_layer"):
            for m in spec[group]:
                if like in m.get("workloads", []):
                    m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
