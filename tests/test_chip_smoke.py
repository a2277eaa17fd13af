"""`chip_smoke.py` phases at a tiny size on the CPU backend.

The chip run drives the same functions at 1,000,000 neurons; here each
phase runs at a few hundred neurons (the fused Pallas step in interpret
mode) so a broken phase shows up before any chip time is spent. The
script's refusal to run without a TPU is checked in a child process.
"""
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

N = 512


@pytest.fixture(scope="module")
def small():
    spec = chip_smoke.build_spec(N, seed=0)
    return spec, chip_smoke.compile_for(spec, "engine")


def test_build_spec_shape_and_rate_inputs():
    spec = chip_smoke.build_spec(N, seed=0)
    assert spec.n_synapses == (N + chip_smoke.N_AXONS) * chip_smoke.FANOUT
    _, _, w = spec.columns()
    assert w.min() < -16000 and w.max() > 16000       # the int16 range
    counts = chip_smoke.windows(np.random.default_rng(0), 2)
    assert counts.shape == (2, chip_smoke.WINDOW, chip_smoke.N_AXONS)
    assert 0.03 < counts.mean() < 0.07


def test_phase_serve_bit_exact(small):
    _, compiled = small
    rate = chip_smoke.phase_serve(compiled, seed=0, n_clients=4,
                                  n_windows=2)
    assert 0.0 < rate <= 0.5


def test_phase_mesh_vs_engine(small):
    spec, compiled = small
    chip_smoke.phase_mesh_vs_engine(spec, compiled, seed=0, n_windows=1)


def test_phase_reference():
    chip_smoke.phase_reference(seed=1, n_neurons=256)


def test_phase_portal():
    chip_smoke.phase_portal(seed=2, n_neurons=256)


def test_phase_mesh_4_on_four_host_devices():
    """The `--chips 4` phase on four forced host devices (the flag must
    precede the child's first jax import)."""
    code = ("import chip_smoke; "
            "chip_smoke.phase_mesh_4(chip_smoke.build_spec(512, 0), 0, "
            "n_windows=1)")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "synapse shards on devices [0, 1, 2, 3]" in out.stdout
    assert "mesh on 4 devices == 1 device" in out.stdout


def test_check_equal_names_the_mismatch():
    chip_smoke.check_equal("same", np.arange(3), np.arange(3))
    with pytest.raises(AssertionError, match="x: mismatch \\(1 entries\\)"):
        chip_smoke.check_equal("x", np.array([1, 2]), np.array([1, 3]))


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout
