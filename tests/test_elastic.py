"""Elastic scaling: a checkpoint written under one mesh restores onto a
DIFFERENT mesh (node-failure recovery path) with identical values and valid
shardings. Runs in a subprocess with 8 virtual devices."""
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, r"%(src)s")
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_reduced
from repro.checkpoint import save_tree, restore_tree
from repro.distributed.context import mesh_context
from repro.distributed.elastic import reshard_tree
from repro.launch.sharding import ShardingRules, to_named
from repro.models import lm

cfg = get_reduced("gemma_7b")
auto = (jax.sharding.AxisType.Auto,) * 2
mesh_a = jax.make_mesh((2, 4), ("data", "model"), axis_types=auto)
mesh_b = jax.make_mesh((4, 2), ("data", "model"), axis_types=auto)

with mesh_context(mesh_a):
    params = lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rules = ShardingRules(cfg, mesh_a, "heads")
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          params)
    sh = to_named(rules.params_specs(shapes), mesh_a)
    params = jax.tree.map(lambda a, s: jax.device_put(a, s), params, sh)
    batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
    loss_a, _ = lm.loss_fn(params, cfg, batch)
    save_tree(r"%(ckpt)s", params)

# 'failure': rebuild on the reshaped mesh and restore
with mesh_context(mesh_b):
    restored, _ = restore_tree(r"%(ckpt)s", params)
    resharded = reshard_tree(restored, cfg, mesh_b, kind="params",
                             layout="heads")
    # values identical
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(resharded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and usable: same loss on the new mesh
    loss_b, _ = lm.loss_fn(resharded, cfg, batch)
    assert abs(float(loss_a) - float(loss_b)) < 1e-4, (loss_a, loss_b)
print("ELASTIC_OK")
"""


def test_elastic_remesh_roundtrip(tmp_path):
    script = SCRIPT % {"src": str(ROOT / "src"),
                       "ckpt": str(tmp_path / "ck")}
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=500)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    assert "ELASTIC_OK" in proc.stdout
