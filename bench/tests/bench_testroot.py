"""A checkout-shaped directory for the benchmark's tests: a copy of
`bench/` and `BENCHMARK.json`, plus two tiny configurations, two tiny
traffic mixes and two cells that use them, all added as new files and
new entries only."""
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

TINY_FILES = {
    "configs/tiny-core.json": {
        "name": "tiny-core", "kind": "random_lif", "cores": 1,
        "neurons_per_core": 600, "axons": 32, "synapses_per_source": 40,
        "max_in_degree": 70,
        "neuron": {"model": "LIF", "threshold": 60000, "nu": 0,
                   "lam": 4}},
    "configs/tiny-mlp.json": {
        "name": "tiny-mlp", "kind": "layered", "inputs": 784,
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
# new cell -> (config, traffic, the benchmark's cell whose metrics it takes)
TINY_CELLS = {"tiny.sim": ("tiny-core", "tiny_sessions", "core125k.sim"),
              "tiny.serve": ("tiny-mlp", "tiny_poisson", "mlp.serve")}


def make_root(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__"))
    for rel, body in TINY_FILES.items():
        path = root / "bench" / rel
        if path.exists():
            raise FileExistsError(path)
        path.write_text(json.dumps(body))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    for name in ("tiny-core", "tiny-mlp"):
        spec["configs"].append({
            "name": name, "source": "tiny test network",
            "file": f"bench/configs/{name}.json", "reduced": [],
            "why": "tiny"})
    for cell, (config, traffic, like) in TINY_CELLS.items():
        spec["workloads"].append({"name": cell, "config": config,
                                  "traffic": traffic, "chips": 1,
                                  "why": "tiny"})
        for group in ("end_to_end", "per_layer"):
            for m in spec[group]:
                if like in m.get("workloads", []):
                    m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
