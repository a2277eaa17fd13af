"""The comparison that decides `correct` fails when the timed path is
broken underneath: a run of the harness at a tiny size, skipping its
look for a chip, with the engine's lane step broken in each way a cell
can break. (One chip: no exchange between chips to leave out.)"""
import numpy as np
import pytest

from bench_testroot import TINY_CELLS, make_root
from benchkit.harness import run_cell
from repro.core.engine import EventEngine

ENGINE_RUN_LANES = EventEngine.run_lanes


def state_unchanged(self, V0, keys, counts):
    _, _, spikes = ENGINE_RUN_LANES(self, V0, keys, counts)
    return V0, keys, spikes


def half_batch(self, V0, keys, counts):
    V, k, spikes = ENGINE_RUN_LANES(self, V0, keys, counts)
    V = np.array(V)
    spikes = np.array(spikes)
    half = V.shape[0] // 2
    V[half:] = 0
    spikes[half:] = False
    return V, k, spikes


def altered_answer(self, V0, keys, counts):
    V, k, spikes = ENGINE_RUN_LANES(self, V0, keys, counts)
    spikes = np.array(spikes)
    spikes[0, -1, 0] = ~spikes[0, -1, 0]
    return V, k, spikes


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "altered_answer": altered_answer}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_lane_step_is_not_correct(root, monkeypatch, cell, fault):
    monkeypatch.setattr(EventEngine, "run_lanes", FAULTS[fault])
    res = run_cell(root, cell, 4242, 1.0, trace=False,
                   require_chip=False, log=lambda m: None)
    assert res["correct"] is False
    c = res["checks"]
    assert c["spike_mismatch"]["value"] + c["membrane_mismatch"]["value"] \
        > 0
