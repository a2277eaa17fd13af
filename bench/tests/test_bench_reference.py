"""The plain reference: its fixed-point semantics, its two ways of
summing synaptic input, and its lower-precision control, which the
comparison has to refuse."""
import numpy as np
import pytest
import scipy.sparse as sp

from bench_testroot import TINY_CELLS, make_root
from benchkit import check
from benchkit.layout import Layout
from benchkit.reference import Reference, lane_keys, leak, noise

CONTROL_SEEDS = (11, 12, 13)


def test_noise_follows_the_paper_formula():
    u = np.array([-65536, -5, -1, 0, 6, 65535], np.int32)
    assert noise(u, np.full(6, -32, np.int32)).tolist() == [0] * 6
    assert noise(u, np.zeros(6, np.int32)).tolist() == \
        [-65535, -5, -1, 1, 7, 65535]
    assert noise(u, np.full(6, 3, np.int32)).tolist() == \
        [-65535 * 8, -40, -8, 8, 56, 65535 * 8]
    # sign-magnitude right shift: truncates toward zero
    assert noise(u, np.full(6, -2, np.int32)).tolist() == \
        [-16383, -1, 0, 0, 1, 16383]


def test_leak_floors_toward_minus_infinity():
    V = np.array([-17, -16, -1, 0, 15, 33], np.int32)
    assert leak(V, np.full(6, 4, np.int32)).tolist() == \
        [-17 + 2, -16 + 1, -1 + 1, 0, 15, 33 - 2]
    assert leak(V, np.full(6, 63, np.int32)).tolist() == \
        [-16, -15, 0, 0, 15, 33]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def test_dense_and_sparse_sums_agree(root):
    layout = Layout(root)
    cfg = layout.config("tiny-core")
    net = layout.network_builder(cfg["kind"]).build(cfg, 5)
    sparse, dense = Reference(net), Reference(net)
    W = dense.W if isinstance(dense.W, np.ndarray) else dense.W.toarray()
    dense.W = np.asarray(W, np.float64)
    sparse.W = sp.csr_matrix(dense.W.astype(np.int64))
    rng = np.random.default_rng(0)
    counts = (rng.random((3, 8, net.n_axons)) < 0.2).astype(np.int32)
    V0 = np.zeros((3, net.n_neurons), np.int32)
    keys = lane_keys(9, [0, 1, 2])
    a, b = sparse.run(V0, keys, counts), dense.run(V0, keys, counts)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert 0 < a[0].mean() < 0.5


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_lower_precision_control_is_refused(root, cell):
    from control import control_readings

    for seed in CONTROL_SEEDS:
        got = control_readings(root, cell, seed, 1.0, rounds=3)
        assert got["compared"] > 0
        assert not all(check.passes(k, v) for k, v in got.items()), got
