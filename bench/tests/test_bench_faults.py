"""The comparison that decides `correct` fails when the timed path is
broken underneath: a run of the harness at a tiny size, skipping its
look for a chip, with the lane step of the cell's deploy target broken
in each way a cell can break. (The exchange between chips left out is
planted on four devices, in `test_bench_targets.py`.)"""
import numpy as np
import pytest

from bench_testroot import TINY_CELLS, make_root, target_of
from benchkit.harness import run_cell
from repro.core.engine import EventEngine
from repro.core.hiaer import HiAERNetwork
from repro.core.mesh_runtime import MeshNetwork

BACKENDS = {"engine": EventEngine, "hiaer": HiAERNetwork,
            "mesh": MeshNetwork}


def state_unchanged(run_lanes):
    def broken(self, V0, keys, counts):
        _, _, spikes = run_lanes(self, V0, keys, counts)
        return V0, keys, spikes
    return broken


def half_batch(run_lanes):
    def broken(self, V0, keys, counts):
        V, k, spikes = run_lanes(self, V0, keys, counts)
        V = np.array(V)
        spikes = np.array(spikes)
        half = V.shape[0] // 2
        V[half:] = 0
        spikes[half:] = False
        return V, k, spikes
    return broken


def altered_answer(run_lanes):
    def broken(self, V0, keys, counts):
        V, k, spikes = run_lanes(self, V0, keys, counts)
        spikes = np.array(spikes)
        spikes[0, -1, 0] = ~spikes[0, -1, 0]
        return V, k, spikes
    return broken


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "altered_answer": altered_answer}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_lane_step_is_not_correct(root, monkeypatch, cell, fault):
    backend = BACKENDS[target_of(cell)]
    monkeypatch.setattr(backend, "run_lanes",
                        FAULTS[fault](backend.run_lanes))
    res = run_cell(root, cell, 4242, 1.0, trace=False,
                   require_chip=False, log=lambda m: None)
    assert res["correct"] is False
    c = res["checks"]
    assert c["spike_mismatch"]["value"] + c["membrane_mismatch"]["value"] \
        > 0
