"""Mesh-scale SNN execution with hierarchical HiAER spike routing.

This is the paper's scaling story mapped to the TPU pod: 160M neurons /
40B synapses sharded over the production mesh, with spike bit-vectors
multicast level-by-level (Fig. 1b):

  'model' axis = 32 cores within an FPGA  -> NoC        (fastest, first)
  'data'  axis = 8 FPGA boards per server -> FireFly
  'pod'   axis = servers                  -> Ethernet   (slowest, last)

Postsynaptic neurons are sharded over ('data','model') [+pod]; each device
owns a (neurons_global x neurons_local) stripe of synapses stored as dense
int8-occupancy-tagged 128x128 blocks (block-CSR in spirit; block-dense in
the XLA dry-run — the event-gated skipping is the Pallas kernel's job on
real TPUs, kernels/spike_matmul.py).

The spike exchange is a hierarchical all-gather of 1-bit spike vectors:
exactly the paper's "keep most event traffic on fast local links" — the
slow cross-pod hop carries only the pod-boundary summary once. The wire
format is the shared bit-packed representation of `kernels.exchange`
(`pack_events`/`unpack_events`, uint32 presence words): this module no
longer hand-rolls its own 1-bit packing — it is a thin consumer of the
same primitives the production mesh tier (core.mesh_runtime) exchanges
with, and `small_reference_step` remains the dense single-device oracle
the packed path is tested against (tests/test_system.py). Shards whose
local bit count is not word-aligned (n_loc % 32 != 0, impossible at the
paper's scale) fall back to the dense bool gather.

`step` is pjit-compatible; `hiaer_snn_40b` dry-runs it at full scale
(160e6 neurons, 40e9 synapses => 2.4e5 synapses/neuron avg fan-in 250,
int16 weights: 80 GB sharded, 312 MB/device at 256 devices).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import neuron as nrn
from repro.distributed.context import batch_axes, get_mesh, tp_axis
from repro.kernels import exchange as exch_k


@dataclass(frozen=True)
class SNNShardConfig:
    n_neurons: int = 160_000_000
    avg_fan_in: int = 250            # 40e9 / 160e6
    block: int = 128
    # synapses stored as (n_blocks_in, block, n_loc) int16 stripes where
    # n_blocks_in = ceil(fan_in_window / block): each neuron's inputs come
    # from a bounded window of presynaptic blocks (sparse 'grey matter'
    # locality the paper's partitioner [10] optimizes for).
    fan_window_blocks: int = 4       # 4*128 = 512-wide presynaptic window

    @property
    def n_synapses(self) -> int:
        return self.n_neurons * self.avg_fan_in


def snn_state_shapes(cfg: SNNShardConfig, mesh):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    n_axes = [a for a in ("data", "model") if a in mesh.axis_names]
    shard = 1
    for a in n_axes:
        shard *= mesh.shape[a]
    if "pod" in mesh.axis_names:
        shard *= mesh.shape["pod"]
    n_loc = cfg.n_neurons // shard
    W = cfg.fan_window_blocks * cfg.block
    spec = {
        "V": jax.ShapeDtypeStruct((cfg.n_neurons,), jnp.int32),
        "theta": jax.ShapeDtypeStruct((cfg.n_neurons,), jnp.int32),
        "lam": jax.ShapeDtypeStruct((cfg.n_neurons,), jnp.int32),
        # per-device synapse stripe: (window_pre, n_loc) int16, stored
        # globally as (n_neurons_global_window..., n) — represented as the
        # full sharded matrix (W, n_neurons) with W the presyn window
        "weights": jax.ShapeDtypeStruct((W, cfg.n_neurons), jnp.int16),
        "spikes": jax.ShapeDtypeStruct((cfg.n_neurons,), jnp.bool_),
    }
    return spec


def snn_shardings(cfg: SNNShardConfig, mesh):
    all_axes = tuple(a for a in ("pod", "data", "model")
                     if a in mesh.axis_names)
    vec = NamedSharding(mesh, P(all_axes))
    return {
        "V": vec, "theta": vec, "lam": vec, "spikes": vec,
        "weights": NamedSharding(mesh, P(None, all_axes)),
    }


def make_snn_step(cfg: SNNShardConfig, mesh):
    """One simulation timestep at pod scale.

    state: dict of sharded arrays (see snn_state_shapes). The windowed
    synapse model: neuron i's presynaptic sources are spikes[w(i) : w(i)+W]
    where w(i) is its window start — here fixed strided windows so the
    gather is a reshape (the partitioner's locality assumption)."""
    all_axes = tuple(a for a in ("pod", "data", "model")
                     if a in mesh.axis_names)

    def step(state, key):
        V, theta, lam = state["V"], state["theta"], state["lam"]
        spikes_prev = state["spikes"]
        W = cfg.fan_window_blocks * cfg.block

        def local(V, theta, lam, spikes_prev, weights, key):
            # --- phase 1 (fire): local neuron update
            n_loc = V.shape[0]
            V_mid, spikes = nrn.fire_phase(
                V, theta, jnp.full_like(theta, -32), lam,
                jnp.ones((n_loc,), bool), key)
            # --- HiAER multicast: hierarchical all-gather of spike bits,
            # fast axis first (NoC -> FireFly -> Ethernet), over the
            # shared packed wire format: each shard's bool vector packs
            # to uint32 presence words (kernels.exchange.pack_events),
            # the hops gather WORDS (32x fewer bytes per link), and the
            # global vector unpacks once at the destination. Word
            # packing commutes with concatenation only when every
            # shard's bit count is word-aligned; otherwise fall back to
            # the dense bool gather (same values, wide wire).
            if spikes_prev.shape[0] % exch_k.PACK_BITS == 0:
                words = exch_k.pack_events(spikes_prev)
                for ax in reversed(all_axes):  # model, data, pod
                    words = jax.lax.all_gather(words, ax, tiled=True)
                bits = exch_k.unpack_events(
                    words, words.shape[0] * exch_k.PACK_BITS)
            else:
                bits = spikes_prev
                for ax in reversed(all_axes):  # model, data, pod
                    bits = jax.lax.all_gather(bits, ax, tiled=True)
            # --- phase 2 (integrate): windowed event-driven synaptic sum.
            # Local connectivity ("grey matter"): this device's neurons see
            # the presynaptic window anchored at their own global offset —
            # the locality the partitioning algorithm [10] optimizes for.
            n_glob = bits.shape[0]
            lin = jnp.int32(0)
            for ax in all_axes:
                lin = lin * get_mesh().shape[ax] + jax.lax.axis_index(ax)
            base = jnp.minimum(lin * n_loc, n_glob - W)
            win = jax.lax.dynamic_slice_in_dim(bits, base, W)
            syn = jnp.einsum("w,wn->n", win.astype(jnp.int32),
                             weights.astype(jnp.int32))
            V_next = nrn.integrate_phase(V_mid, syn)
            return V_next, spikes

        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(all_axes), P(all_axes), P(all_axes), P(all_axes),
                      P(None, all_axes), P()),
            out_specs=(P(all_axes), P(all_axes)),
            check_vma=False)
        V_next, spikes = fn(V, theta, lam, spikes_prev, state["weights"],
                            key)
        return {**state, "V": V_next, "spikes": spikes}

    return step


def small_reference_step(V, theta, lam, spikes_prev, weights, key):
    """Single-device oracle for tests: same windowed semantics."""
    V_mid, spikes = nrn.fire_phase(V, theta, jnp.full_like(theta, -32), lam,
                                   jnp.ones(V.shape, bool), key)
    W = weights.shape[0]
    win = spikes_prev[:W]
    syn = jnp.einsum("w,wn->n", win.astype(jnp.int32),
                     weights.astype(jnp.int32))
    return nrn.integrate_phase(V_mid, syn), spikes
