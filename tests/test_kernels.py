"""Per-kernel allclose vs ref.py oracles, with hypothesis shape/dtype
sweeps (interpret=True executes kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.kernels import ops, ref
from repro.kernels.backend import resolve_interpret


# ------------------------------------------------------------ spike matmul
@settings(max_examples=12, deadline=None)
@given(st.integers(1, 5), st.integers(1, 4), st.integers(0, 100),
       st.floats(0.0, 0.5))
def test_spike_matmul_sweep(npre_blocks, npost_blocks, seed, density):
    key = jax.random.PRNGKey(seed)
    npre, npost = npre_blocks * 128, npost_blocks * 128
    spikes = jax.random.bernoulli(key, density, (npre,))
    w = jax.random.randint(jax.random.fold_in(key, 1), (npre, npost),
                           -32768, 32767, jnp.int16)
    got = ops.spike_matmul(spikes, w)
    want = ref.spike_matmul_ref(spikes, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_spike_matmul_unaligned_padding():
    key = jax.random.PRNGKey(7)
    spikes = jax.random.bernoulli(key, 0.2, (300,))
    w = jax.random.randint(key, (300, 77), -100, 100, jnp.int16)
    got = ops.spike_matmul(spikes, w)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.spike_matmul_ref(spikes, w)))


def test_spike_matmul_skips_silent_blocks_exactly():
    """Live presynaptic blocks after silent runs: the silent blocks'
    index maps repeat the last live tile, and only live ones add."""
    key = jax.random.PRNGKey(11)
    spikes = jnp.zeros((128 * 6,), bool)
    spikes = spikes.at[128 * 2 + 5].set(True).at[128 * 5:].set(True)
    w = jax.random.randint(key, (128 * 6, 256), -3000, 3000, jnp.int16)
    np.testing.assert_array_equal(
        np.asarray(ops.spike_matmul(spikes, w)),
        np.asarray(ref.spike_matmul_ref(spikes, w)))


def test_spike_matmul_all_silent_is_zero():
    w = jnp.ones((256, 128), jnp.int16)
    out = ops.spike_matmul(jnp.zeros((256,), bool), w)
    assert int(jnp.abs(out).max()) == 0


# ------------------------------------------------------ interpret choice
@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False)])
def test_interpret_follows_the_platform(monkeypatch, platform, want):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert resolve_interpret() is want
    assert resolve_interpret(not want) is (not want)


def test_interpret_refuses_other_platforms(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        resolve_interpret()


# ---------------------------------------------------------------- lif step
@settings(max_examples=12, deadline=None)
@given(st.integers(1, 4), st.integers(0, 1000))
def test_lif_step_sweep(blocks, seed):
    key = jax.random.PRNGKey(seed)
    n = blocks * 256
    ks = [jax.random.fold_in(key, i) for i in range(7)]
    V = jax.random.randint(ks[0], (n,), -(2**20), 2**20, jnp.int32)
    syn = jax.random.randint(ks[1], (n,), -5000, 5000, jnp.int32)
    u = jax.random.randint(ks[2], (n,), -(2**16), 2**16, jnp.int32)
    theta = jax.random.randint(ks[3], (n,), 0, 2**16, jnp.int32)
    nu = jax.random.randint(ks[4], (n,), -32, 32, jnp.int32)
    lam = jax.random.randint(ks[5], (n,), 0, 64, jnp.int32)
    is_lif = jax.random.bernoulli(ks[6], 0.5, (n,))
    V2, s2 = ops.lif_step(V, syn, u, theta, nu, lam, is_lif)
    Vr, sr = ref.lif_step_ref(V, syn, u, theta, nu, lam, is_lif)
    np.testing.assert_array_equal(np.asarray(V2), np.asarray(Vr))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(sr))


def test_lif_step_unaligned():
    n = 100
    V = jnp.arange(n, dtype=jnp.int32) * 37 - 1000
    syn = jnp.ones((n,), jnp.int32)
    u = jnp.zeros((n,), jnp.int32)
    theta = jnp.full((n,), 500, jnp.int32)
    nu = jnp.full((n,), -32, jnp.int32)
    lam = jnp.full((n,), 2, jnp.int32)
    is_lif = jnp.ones((n,), bool)
    V2, s2 = ops.lif_step(V, syn, u, theta, nu, lam, is_lif)
    Vr, sr = ref.lif_step_ref(V, syn, u, theta, nu, lam, is_lif)
    np.testing.assert_array_equal(np.asarray(V2), np.asarray(Vr))


# --------------------------------------------------------- flash attention
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(1, 1, 128, 64), (2, 3, 256, 64),
                                   (1, 2, 512, 128)])
def test_flash_attention_shapes_dtypes(shape, dtype):
    key = jax.random.PRNGKey(0)
    B, H, S, D = shape
    q = jax.random.normal(key, shape, jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), shape,
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), shape,
                          jnp.float32).astype(dtype)
    got = ops.flash_attention(q, k, v, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    assert err < tol, err


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 2), st.integers(1, 3), st.sampled_from([128, 256]),
       st.sampled_from([32, 64]), st.integers(0, 50))
def test_flash_attention_sweep(B, H, S, D, seed):
    key = jax.random.PRNGKey(seed)
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i),
                                     (B, H, S, D), jnp.float32)
    q, k, v = mk(0), mk(1), mk(2)
    got = ops.flash_attention(q, k, v, bq=64, bk=64)
    want = ref.flash_attention_ref(q, k, v)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


def test_flash_attention_causality():
    """Perturbing a future key must not change earlier outputs."""
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 1, 128, 32))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 128, 32))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 1, 128, 32))
    o1 = ops.flash_attention(q, k, v, bq=64, bk=64)
    k2 = k.at[:, :, 100:].add(7.0)
    o2 = ops.flash_attention(q, k2, v, bq=64, bk=64)
    np.testing.assert_allclose(np.asarray(o1[:, :, :100]),
                               np.asarray(o2[:, :, :100]), atol=1e-6)


# ----------------------------------------------- flash attention backward
@settings(max_examples=6, deadline=None)
@given(st.integers(1, 2), st.sampled_from([128, 256]),
       st.sampled_from([32, 64]), st.integers(0, 30))
def test_flash_attention_trainable_grads(H, S, D, seed):
    """Pallas fwd+bwd kernels match jax.grad of the pure-jnp oracle."""
    from repro.kernels.flash_attention import flash_attention_trainable
    key = jax.random.PRNGKey(seed)
    mk = lambda i: jax.random.normal(jax.random.fold_in(key, i),
                                     (1, H, S, D))
    q, k, v = mk(0), mk(1), mk(2)

    def loss_kernel(q, k, v):
        return jnp.sum(jnp.tanh(
            flash_attention_trainable(q, k, v, True, 64, 64, True)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.tanh(ref.flash_attention_ref(q, k, v)))

    g1 = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-4
