"""STE backward pass of the engine's float GEMM (noise-aware), and the
noise seed-determinism contract of the `ref` backend's integer datapath."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dpu import DPUConfig
from repro.noise import build_channel_model
from repro.photonic import engine_for


def _matmul(x, w, cfg, prng_key=None):
    """Float GEMM through the `ref` backend (STE-differentiable)."""
    return engine_for(cfg, "ref").matmul_float(x, w, prng_key=prng_key)


def _int_gemm(xq, wq, cfg, prng_key=None):
    """Integer GEMM through the `ref` backend's datapath."""
    return engine_for(cfg, "ref").int_gemm(xq, wq, prng_key=prng_key)


def _data(seed=0, b=4, s=8, k=32, c=16):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(b, s, k)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(k, c)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(b, s, c)), jnp.float32)
    return x, w, g


# ---------------------------------------------------------------------------
# STE backward == dense-matmul gradients (exactly, for a linear loss)
# ---------------------------------------------------------------------------
def test_ste_backward_matches_dense_matmul_grad():
    x, w, g = _data()
    cfg = DPUConfig(dpe_size=16)

    def loss_ste(x, w):
        return (_matmul(x, w, cfg) * g).sum()

    def loss_dense(x, w):
        return ((x @ w) * g).sum()

    gx, gw = jax.grad(loss_ste, argnums=(0, 1))(x, w)
    ex, ew = jax.grad(loss_dense, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ex), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(ew), rtol=1e-6)


def test_ste_backward_unchanged_by_noise():
    """The straight-through gradient ignores forward perturbations: a noisy
    channel changes the forward value but not the backward pass."""
    x, w, g = _data(1)
    ch = build_channel_model("ASMW", n=16)
    cfg_ideal = DPUConfig(organization="ASMW", dpe_size=16)
    cfg_noisy = dataclasses.replace(cfg_ideal, channel=ch)
    key = jax.random.PRNGKey(7)

    y_ideal = _matmul(x, w, cfg_ideal)
    y_noisy = _matmul(x, w, cfg_noisy, key)
    assert (np.asarray(y_ideal) != np.asarray(y_noisy)).any()

    def gset(cfg, key=None):
        gx, gw = jax.grad(
            lambda x, w: (_matmul(x, w, cfg, key) * g).sum(),
            argnums=(0, 1),
        )(x, w)
        return np.asarray(gx), np.asarray(gw)

    gx_i, gw_i = gset(cfg_ideal)
    gx_n, gw_n = gset(cfg_noisy, key)
    np.testing.assert_array_equal(gx_i, gx_n)
    np.testing.assert_array_equal(gw_i, gw_n)
    assert np.isfinite(gx_n).all() and np.isfinite(gw_n).all()


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_kernel_entrypoint_ste_noise_aware(backend):
    """`matmul_float` on either datapath backend takes a prng_key and keeps
    its STE gradients exact while the forward carries channel noise."""
    x, w, g = _data(2)
    ch = build_channel_model("MASW", n=16)
    cfg = DPUConfig(organization="MASW", dpe_size=16, channel=ch)
    key = jax.random.PRNGKey(3)

    eng = engine_for(cfg, backend)
    y = eng.matmul_float(x, w, prng_key=key)
    assert np.isfinite(np.asarray(y)).all()
    gx = jax.grad(lambda x: (eng.matmul_float(x, w, prng_key=key) * g).sum())(x)
    ex = jax.grad(lambda x: ((x @ w) * g).sum())(x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(ex), rtol=1e-6)


def test_ste_jit_and_value_and_grad():
    x, w, g = _data(3)
    ch = build_channel_model("SMWA", n=16)
    cfg = DPUConfig(dpe_size=16, channel=ch, noise_seed=5)

    @jax.jit
    def vg(x, w):
        return jax.value_and_grad(
            lambda x, w: (_matmul(x, w, cfg) * g).sum(),
            argnums=(0, 1),
        )(x, w)

    (v1, (gx, gw)) = vg(x, w)
    (v2, _) = vg(x, w)
    assert v1 == v2  # noise_seed path: bitwise-deterministic forward
    assert np.isfinite(np.asarray(gx)).all()


# ---------------------------------------------------------------------------
# Seed-determinism contract (regression for the prng_key=None path)
# ---------------------------------------------------------------------------
def test_same_key_bitwise_equal_under_noise():
    rng = np.random.default_rng(4)
    xq = jnp.asarray(rng.integers(-127, 128, (16, 96), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-127, 128, (96, 24), dtype=np.int8))
    cfg = DPUConfig(dpe_size=24, noise_sigma_lsb=4.0)
    key = jax.random.PRNGKey(0)
    a = _int_gemm(xq, wq, cfg, prng_key=key)
    b = _int_gemm(xq, wq, cfg, prng_key=key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = _int_gemm(xq, wq, cfg, prng_key=jax.random.PRNGKey(1))
    assert (np.asarray(a) != np.asarray(c)).any()


def test_noise_without_seed_is_explicit_error():
    rng = np.random.default_rng(5)
    xq = jnp.asarray(rng.integers(-127, 128, (4, 32), dtype=np.int8))
    wq = jnp.asarray(rng.integers(-127, 128, (32, 8), dtype=np.int8))
    with pytest.raises(ValueError, match="randomness source"):
        _int_gemm(xq, wq, DPUConfig(dpe_size=16, noise_sigma_lsb=2.0))
    ch = build_channel_model("ASMW", n=16)
    with pytest.raises(ValueError, match="randomness source"):
        _int_gemm(xq, wq, DPUConfig(organization="ASMW", dpe_size=16, channel=ch))
    # Crosstalk-only channels are deterministic — no seed needed.
    out = _int_gemm(
        xq,
        wq,
        DPUConfig(
            organization="ASMW", dpe_size=16, channel=ch.disable("detector")
        ),
    )
    assert out.shape == (4, 8)


def test_same_seed_distinct_operands_decorrelated():
    """Two same-shaped GEMMs sharing one noise_seed must not reuse the same
    noise array (operand-content tweak): otherwise every same-shaped layer
    of a model would see coherent, correlated analog errors."""
    rng = np.random.default_rng(7)
    xq = jnp.asarray(rng.integers(-127, 128, (8, 64), dtype=np.int8))
    w1 = jnp.asarray(rng.integers(-127, 128, (64, 16), dtype=np.int8))
    w2 = jnp.asarray(rng.integers(-127, 128, (64, 16), dtype=np.int8))
    ch = build_channel_model("SMWA", n=16).disable("crosstalk")
    cfg = DPUConfig(dpe_size=16, channel=ch, noise_seed=0)
    from repro.kernels.photonic_gemm.ref import exact_int_gemm

    n1 = np.asarray(_int_gemm(xq, w1, cfg)) - np.asarray(exact_int_gemm(xq, w1))
    n2 = np.asarray(_int_gemm(xq, w2, cfg)) - np.asarray(exact_int_gemm(xq, w2))
    assert (n1 != n2).any()
    corr = np.corrcoef(n1.ravel().astype(float), n2.ravel().astype(float))[0, 1]
    assert abs(corr) < 0.3, corr


def test_noise_seed_documented_deterministic_path():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(8, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    ch = build_channel_model("MASW", n=21)
    cfg = DPUConfig(organization="MASW", dpe_size=21, channel=ch, noise_seed=42)
    a = _matmul(x, w, cfg)
    b = _matmul(x, w, cfg)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # An explicit key overrides the config seed.
    c = _matmul(x, w, cfg, prng_key=jax.random.PRNGKey(9))
    assert (np.asarray(a) != np.asarray(c)).any()
