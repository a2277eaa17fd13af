"""Public jit'd wrappers for the Pallas kernels.

`interpret=None` is resolved by `kernels.backend.resolve_interpret`:
compiled Mosaic on TPU, the Pallas interpreter on the CPU backend the tests
use, an error anywhere else. Padding to hardware tile boundaries (128-lane,
the segment-alignment analogue) happens here so callers keep natural sizes.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.lif_step import BLOCK as _LIF_BLOCK
from repro.kernels.lif_step import lif_step as _lif
from repro.kernels.spike_matmul import BN, BP
from repro.kernels.spike_matmul import spike_matmul as _spmv


def _pad_to(x, mult, axis=0, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@partial(jax.jit, static_argnames=("interpret",))
def spike_matmul(spikes, weights, interpret=None):
    """Event-gated synaptic accumulation. spikes (Npre,) bool,
    weights (Npre, Npost) int16 -> (Npost,) int32."""
    npre, npost = weights.shape
    s = _pad_to(spikes.astype(jnp.int32), BP)
    w = _pad_to(_pad_to(weights, BP, 0), BN, 1)
    out = _spmv(s.astype(bool), w, interpret=interpret)
    return out[:npost]


@partial(jax.jit, static_argnames=("interpret",))
def lif_step(V, syn_in, noise_u, theta, nu, lam, is_lif, interpret=None):
    n = V.shape[0]
    args = [_pad_to(a.astype(jnp.int32), _LIF_BLOCK)
            for a in (V, syn_in, noise_u, theta, nu, lam)]
    lif = _pad_to(is_lif.astype(jnp.int32), _LIF_BLOCK).astype(bool)
    V2, s2 = _lif(*args, lif, interpret=interpret)
    return V2[:n], s2[:n]


@partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal=True, bq=128, bk=128, interpret=None):
    """(B, H, S, D) flash forward; S padded to block multiple (padded keys
    are masked out by causality when causal=True)."""
    S = q.shape[2]
    if S % bq or S % bk:
        qp = _pad_to(q, max(bq, bk), axis=2)
        kp = _pad_to(k, max(bq, bk), axis=2)
        vp = _pad_to(v, max(bq, bk), axis=2)
        out = _flash(qp, kp, vp, causal=True, bq=bq, bk=bk,
                     interpret=interpret)
        return out[:, :, :S]
    return _flash(q, k, v, causal=causal, bq=bq, bk=bk, interpret=interpret)
