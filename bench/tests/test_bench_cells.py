"""Whole runs of the harness on the CPU at a tiny size, skipping only
its look for a chip: the served windows agree with the plain reference,
and a configuration and a traffic mix added as new files run without an
edit to any file already there."""
import filecmp
import json
from pathlib import Path

import pytest

from bench_testroot import BENCH, REPO, TINY_CELLS, make_root
from benchkit import parallel
from benchkit.harness import run_cell

SEED = 2 ** 31 + 977            # larger than 32 signed bits hold


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def test_added_cells_edit_no_file(root):
    for path in BENCH.rglob("*"):
        if path.is_file() and ".cache" not in path.parts \
                and "__pycache__" not in path.parts:
            copy = root / "bench" / path.relative_to(BENCH)
            assert filecmp.cmp(path, copy, shallow=False), path
    spec = json.loads((root / "BENCHMARK.json").read_text())
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    for key in ("configs", "workloads"):
        assert spec[key][:len(real[key])] == real[key]


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_tiny_cell_is_correct_against_the_reference(root, cell):
    res = run_cell(root, cell, SEED, 1.0, trace=False,
                   require_chip=False, log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["checks"]["compared"]["value"] == res["attempted"]
    assert list(res)[-1] == "checks"
    like = TINY_CELLS[cell][2]
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if like in m.get("workloads", [like])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())


def test_traced_run_reads_its_per_layer_metrics(root):
    res = run_cell(root, "tiny.serve", SEED, 1.0, trace=True,
                   require_chip=False, log=lambda m: None)
    assert res["correct"]
    # the CPU trace holds no device plane: the device metrics stay out
    assert {"queue_wait_ms.serve", "batch_fill.serve",
            "dispatch_host_ms.serve"} <= set(res["metrics"])
    assert "device_idle.serve" not in res["metrics"]
    assert Path(root / "bench" / ".cache" / "trace" / "tiny.serve"
                / "events.json").is_file()


def test_run_logs_the_reference_checks_parts(root, monkeypatch):
    monkeypatch.setattr(parallel, "worker_count", lambda *a: 2)
    lines = []
    res = run_cell(root, "tiny.sim", SEED, 1.0, trace=False,
                   require_chip=False, log=lines.append)
    assert res["correct"], res["checks"]
    text = "\n".join(lines)
    for part in ("reference build: ", "reference replay: ",
                 "sessions over 2 workers (os.cpu_count() ",
                 "process tree peak RSS after the check: "):
        assert part in text, part
