"""Event-gated block-sparse spike SpMV — the TPU-native adaptation of the
paper's two-phase HBM synapse routing (DESIGN.md §2).

FPGA mechanism: for each fired neuron, fetch its synapse rows from HBM and
scatter-accumulate into membrane registers. TPUs have no efficient per-event
scatter, so the event-driven insight is lifted to BLOCK granularity:
synapses live in (BP x BN) int16 tiles (128-aligned, the MXU/VPU native
shape — the analogue of the 16-slot segment alignment); a scalar-prefetched
per-block spike count gates the whole tile with @pl.when, so presynaptic
blocks that carry no events are never multiplied — and with the block-count
vector in SMEM before the grid runs, a silent block's index map repeats the
previous live block, so the DMA pipeline skips its HBM read, which is
precisely the paper's "energy ∝ HBM accesses touched by events".

Accumulation is int32 (exact, matches the fixed-point engine bit-for-bit).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_interpret

BP = 128     # presynaptic block
BN = 128     # postsynaptic block


def _kernel(counts_ref, fetch_ref, spikes_ref, w_ref, out_ref):
    ip = pl.program_id(1)        # presynaptic block index (inner)

    @pl.when(ip == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(counts_ref[ip] > 0)
    def _accum():
        s = spikes_ref[...]                            # (BP, 1) int32
        w = w_ref[...].astype(jnp.int32)               # (BP, BN)
        out_ref[...] += jnp.sum(s * w, axis=0, keepdims=True)


def spike_matmul(spikes, weights, *, interpret=None):
    """spikes: (Npre,) bool; weights: (Npre, Npost) int16.
    Returns (Npost,) int32. Npre/Npost must be multiples of 128
    (pad to segment boundaries — the compiler's alignment job)."""
    npre, npost = weights.shape
    assert npre % BP == 0 and npost % BN == 0, (npre, npost)
    s32 = spikes.astype(jnp.int32)
    counts = jnp.sum(s32.reshape(npre // BP, BP), axis=1)
    # block to fetch at each presynaptic step: the last live block at or
    # before it, so a run of silent blocks re-uses the resident tile
    fetch = jax.lax.cummax(jnp.where(counts > 0,
                                     jnp.arange(npre // BP), 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(npost // BN, npre // BP),
        in_specs=[
            pl.BlockSpec((BP, 1), lambda j, i, c, f: (f[i], 0)),
            pl.BlockSpec((BP, BN), lambda j, i, c, f: (f[i], j)),
        ],
        out_specs=pl.BlockSpec((1, BN), lambda j, i, c, f: (0, j)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, npost), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(counts, fetch.astype(jnp.int32), s32.reshape(npre, 1), weights)
    return out.reshape(npost)
