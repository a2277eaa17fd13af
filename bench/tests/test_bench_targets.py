"""A configuration names its deploy target and a cell its chips: the
harness compiles an engine configuration exactly as a user does, gives
hiaer and mesh the configuration's hierarchy, spreads a mesh over the
cell's chips and no others, and refuses a cell whose chips cannot hold
its target before anything is built.

The four-device half runs in a subprocess with
`XLA_FLAGS=--xla_force_host_platform_device_count=4` (JAX fixes its
device count when it starts); this file is also that child's script:
`python bench/tests/test_bench_targets.py --child <dir>`."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench_testroot import REPO, make_root
from benchkit import program
from benchkit.harness import run_cell
from benchkit.layout import Layout

SEED = 2 ** 31 + 4099


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


class Spy:
    """Records each call of `compile_spec` and passes it on."""

    def __init__(self, inner):
        self.inner, self.calls = inner, []

    def __call__(self, *args, **kw):
        self.calls.append((args, kw))
        return self.inner(*args, **kw)


@pytest.fixture
def compile_spy(monkeypatch):
    import repro.core.compile as compile_mod

    spy = Spy(compile_mod.compile_spec)
    monkeypatch.setattr(compile_mod, "compile_spec", spy)
    return spy


def _tiny_net(root, config):
    layout = Layout(root)
    cfg = layout.config(config)
    return cfg, layout.network_builder(cfg["kind"]).build(cfg, 5)


@pytest.mark.parametrize("config", ["hiaer-core-125k",
                                    "mlp-784-2000-1000-10"])
def test_engine_configuration_compiles_exactly_as_before(root, compile_spy,
                                                         config):
    cfg = Layout(REPO).config(config)
    assert program.deploy_target(cfg, 1) == "engine"
    _, net = _tiny_net(root, "tiny-core")
    compiled = program.compile_network(net, cfg, lambda m: None)
    ((args, kw),) = compile_spy.calls
    assert len(args) == 1 and kw == {"target": "engine"}
    assert compiled.target == "engine" and compiled.hierarchy is None


@pytest.mark.parametrize("config", ["tiny-hiaer", "tiny-mesh"])
def test_hiaer_and_mesh_get_the_configurations_hierarchy(root, compile_spy,
                                                         config):
    from repro.core.partition import Hierarchy

    cfg, net = _tiny_net(root, config)
    compiled = program.compile_network(net, cfg, lambda m: None)
    ((_, kw),) = compile_spy.calls
    assert kw["target"] == cfg["target"] == compiled.target
    assert kw["hierarchy"] == Hierarchy(1, 2, 2, 150)
    assert list(kw["placement"]) == list(range(net.n_neurons))
    np.testing.assert_array_equal(compiled.neuron_core,
                                  np.arange(net.n_neurons) // 150)


# a cell that the harness has to refuse: (config changes, chips)
REFUSED = {
    "unknown_target": ({"target": "gpu"}, 1),
    "simulator_target": ({"target": "simulator"}, 1),
    "no_target": ({"target": None}, 1),
    "engine_on_four_chips": ({"target": "engine"}, 4),
    "hiaer_on_four_chips": ({"target": "hiaer"}, 4),
    "mesh_chips_not_dividing_its_cores": ({"target": "mesh",
                                           "cores_per_fpga": 1}, 4),
    "mesh_without_its_hierarchy": ({"target": "mesh",
                                    "fpgas_per_server": None}, 1),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_cell_refused_before_anything_is_built(root, monkeypatch, case):
    changes, chips = REFUSED[case]
    cfg = json.loads((root / "bench/configs/tiny-mesh.json").read_text())
    cfg.update(changes)
    cfg = {k: v for k, v in cfg.items() if v is not None}
    (root / "bench/configs" / f"refused-{case}.json").write_text(
        json.dumps(cfg))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": f"refused-{case}", "source": "test",
                            "file": f"bench/configs/refused-{case}.json",
                            "reduced": [], "why": "refused"})
    spec["workloads"].append({"name": f"refused.{case}",
                              "config": f"refused-{case}",
                              "traffic": "tiny_sessions", "chips": chips,
                              "why": "refused"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    def never(*a, **kw):
        raise AssertionError("a refused cell built its network")

    monkeypatch.setattr(Layout, "network_builder", never)
    # refused before the look for a chip, too: no NoChip on the CPU
    with pytest.raises(program.Refused):
        run_cell(root, f"refused.{case}", SEED, 1.0, trace=False,
                 log=lambda m: None)
    if case == "engine_on_four_chips":
        p = subprocess.run(
            [sys.executable, str(root / "bench" / "run.py"), "--workload",
             f"refused.{case}", "--seed", "1", "--seconds", "1",
             "--trace", "0"],
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=120, cwd=root)
        assert p.returncode == 2 and p.stdout.strip() == ""
        assert "refused" in p.stderr and "one chip" in p.stderr


def test_mesh_on_four_forced_devices_subprocess(tmp_path):
    proc = subprocess.run(
        [sys.executable, str(Path(__file__).resolve()), "--child",
         str(tmp_path)],
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        capture_output=True, text=True, timeout=600, cwd=str(REPO))
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "BENCH-MESH-4DEV-OK" in proc.stdout


def exchange_left_out(x_local, stages, axis_name, axis=0):
    """No spike crosses a chip: at every hop a device takes its own block
    in place of each peer's."""
    import jax.numpy as jnp

    for groups in stages:
        x_local = jnp.concatenate([x_local] * len(groups[0]), axis=axis)
    return x_local


def _child(tmp: Path) -> int:
    import jax

    import repro.kernels.exchange as exch_k
    from repro.core.mesh_runtime import MeshNetwork

    assert len(jax.devices()) == 4, jax.devices()
    root = make_root(tmp)
    spread = []
    init = MeshNetwork.__init__

    def recording_init(self, *a, **kw):
        init(self, *a, **kw)
        spread.append(self.n_devices)

    MeshNetwork.__init__ = recording_init
    for cell, chips in (("tiny.mesh4", 4), ("tiny.mesh", 1)):
        res = run_cell(root, cell, SEED, 1.0, trace=False,
                       require_chip=False, log=lambda m: None)
        assert res["correct"], (cell, res["checks"])
        assert res["failed"] == 0 and res["attempted"] > 0
        assert res["device"]["count"] == chips and spread[-1] == chips, \
            (cell, res["device"], spread)
    exch_k.hierarchical_gather_collective = exchange_left_out
    res = run_cell(root, "tiny.mesh4", SEED, 1.0, trace=False,
                   require_chip=False, log=lambda m: None)
    assert res["correct"] is False, res["checks"]
    assert res["checks"]["spike_mismatch"]["value"] > 0
    print("BENCH-MESH-4DEV-OK")
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["--child"]:
        raise SystemExit(_child(Path(sys.argv[2])))
