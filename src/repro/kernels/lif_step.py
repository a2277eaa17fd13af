"""Fused LIF/ANN membrane-update kernel — the VMEM-resident analogue of the
paper's URAM membrane registers (DESIGN.md §2).

One pass over the neuron state vector does noise-shift, threshold/reset,
leak, and synaptic integration — V never round-trips to HBM between the
sub-steps (on the FPGA it never leaves URAM within a timestep). All math is
int32 and bit-exact against core.neuron (ref.lif_step_ref).

Noise bits are pre-generated 17-bit draws (uniform, from the host PRNG) so
the kernel is deterministic and byte-for-byte testable; on TPU the same
kernel can seed pltpu.prng_random_bits instead.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import neuron as nrn
from repro.kernels.backend import resolve_interpret

# XLA tiles a 1-D int32 array in 1024-element tiles on TPU; a block of
# any other length is refused by Mosaic as a layout mismatch
BLOCK = 1024


def _kernel(V_ref, syn_ref, u_ref, theta_ref, nu_ref, lam_ref, lif_ref,
            Vout_ref, spike_ref):
    V, spikes = nrn.fire_phase_from_u(
        V_ref[...], theta_ref[...], nu_ref[...], lam_ref[...],
        lif_ref[...] != 0, u_ref[...])
    Vout_ref[...] = V + syn_ref[...]
    spike_ref[...] = spikes.astype(jnp.int32)


def lif_step(V, syn_in, noise_u, theta, nu, lam, is_lif, *, interpret=None):
    """All inputs (N,) int32 (is_lif: bool). Returns (V_next, spikes_bool).
    N must be a multiple of BLOCK (pad the membrane file)."""
    n = V.shape[0]
    assert n % BLOCK == 0, n
    spec = pl.BlockSpec((BLOCK,), lambda i: (i,))
    V_next, spikes = pl.pallas_call(
        _kernel,
        grid=(n // BLOCK,),
        in_specs=[spec] * 7,
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n,), jnp.int32),
                   jax.ShapeDtypeStruct((n,), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(V, syn_in, noise_u, theta, nu, lam, is_lif.astype(jnp.int32))
    return V_next, spikes.astype(bool)
