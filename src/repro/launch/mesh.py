"""Production meshes.

Functions (not module-level constants) so importing never touches jax device
state. The production system is a TPU v5e pod of 16x16 = 256 chips
('data' x 'model'); multi-pod doubles it with a leading 'pod' axis over DCN
(2 pods = 512 chips). Mapping to the paper's hierarchy: 'model' = cores
within an FPGA (NoC), 'data' = FPGAs within a server (FireFly), 'pod' =
servers (Ethernet).
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh


def _auto_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh() -> Mesh:
    """Whatever devices exist, as a (1, n) ('data','model') mesh — used by
    smoke tests and the single-host examples."""
    n = len(jax.devices())
    return _auto_mesh((1, n), ("data", "model"))
