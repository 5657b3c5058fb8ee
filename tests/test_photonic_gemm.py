"""Kernel sweep: Pallas photonic GEMM vs the pure-jnp oracle, plus DPU
datapath invariants (property-based)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core.dpu import DPUConfig, bit_slices, quantize_symmetric
from repro.kernels.photonic_gemm.ref import (
    exact_int_gemm,
    slice_decompose,
)
from repro.photonic import engine_for


def _rand_int8(rng, shape):
    return jnp.asarray(rng.integers(-127, 128, shape, dtype=np.int8))


# ---------------------------------------------------------------------------
# Shape / precision sweep of the Pallas kernel vs the oracle
# ---------------------------------------------------------------------------
SHAPES = [
    (8, 64, 32),
    (16, 200, 96),     # K not a multiple of the chunk
    (1, 128, 128),     # decode-like single row
    (64, 83, 83),      # K = exactly one SMWA DPE
    (128, 512, 256),
    (33, 1000, 17),    # ragged everything
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("bits,operand_bits", [(4, 8), (2, 8), (8, 8), (4, 4)])
def test_pallas_matches_oracle(shape, bits, operand_bits):
    r, k, c = shape
    rng = np.random.default_rng(hash((shape, bits)) % 2**32)
    xq = _rand_int8(rng, (r, k))
    wq = _rand_int8(rng, (k, c))
    if operand_bits < 8:
        lim = 2 ** (operand_bits - 1) - 1
        xq = jnp.clip(xq, -lim, lim)
        wq = jnp.clip(wq, -lim, lim)
    cfg = DPUConfig(bits=bits, operand_bits=operand_bits, dpe_size=83)
    gold = exact_int_gemm(xq, wq)
    ref = engine_for(cfg, "ref").int_gemm(xq, wq)
    pal = engine_for(cfg, "pallas").int_gemm(xq, wq)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(gold))
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(gold))


@pytest.mark.parametrize("adc_bits", [10, 12, 16])
def test_pallas_adc_saturation_matches_ref(adc_bits):
    rng = np.random.default_rng(7)
    xq = _rand_int8(rng, (32, 256))
    wq = _rand_int8(rng, (256, 64))
    cfg = DPUConfig(dpe_size=42, adc_bits=adc_bits)
    ref = engine_for(cfg, "ref").int_gemm(xq, wq)
    pal = engine_for(cfg, "pallas").int_gemm(xq, wq)
    np.testing.assert_array_equal(np.asarray(pal), np.asarray(ref))


def test_adc_saturation_bounds_error():
    """Saturated psums bias the result, but never past the clip bound."""
    rng = np.random.default_rng(11)
    xq = _rand_int8(rng, (16, 512))
    wq = _rand_int8(rng, (512, 32))
    gold = np.asarray(exact_int_gemm(xq, wq))
    cfg = DPUConfig(dpe_size=64, adc_bits=8)
    sat = np.asarray(engine_for(cfg, "ref").int_gemm(xq, wq))
    assert np.abs(sat).max() <= np.abs(gold).max()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_float_roundtrip_error_small(dtype):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(8, 96)), dtype)
    w = jnp.asarray(rng.normal(size=(96, 48)), dtype)
    y = engine_for(DPUConfig(dpe_size=48), "pallas").matmul_float(x, w)
    ye = (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(dtype)
    rel = float(
        jnp.linalg.norm((y - ye).astype(jnp.float32))
        / jnp.linalg.norm(ye.astype(jnp.float32))
    )
    assert rel < 0.03, rel


# ---------------------------------------------------------------------------
# Property tests — DPU datapath invariants
# ---------------------------------------------------------------------------
@given(
    r=st.integers(1, 16),
    k=st.integers(1, 96),
    c=st.integers(1, 24),
    bits=st.sampled_from([2, 4, 8]),
    n=st.integers(1, 64),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=40, deadline=None)
def test_dpu_gemm_exact_property(r, k, c, bits, n, seed):
    """Ideal DPU (no noise, no ADC clip) == exact integer GEMM, for any
    chunking N, slicing B, and shape."""
    rng = np.random.default_rng(seed)
    xq = _rand_int8(rng, (r, k))
    wq = _rand_int8(rng, (k, c))
    cfg = DPUConfig(bits=bits, dpe_size=n)
    out = engine_for(cfg, "ref").int_gemm(xq, wq)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(exact_int_gemm(xq, wq)))


@given(
    bits=st.sampled_from([1, 2, 3, 4, 6, 8]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=30, deadline=None)
def test_bit_slice_recompose(bits, seed):
    """sum_s slice_s * 2^(B s) reconstructs the operand exactly."""
    rng = np.random.default_rng(seed)
    q = _rand_int8(rng, (5, 7))
    num = -(-8 // bits)
    sl = bit_slices(q, bits, num)
    recomposed = sum(sl[s].astype(jnp.int32) << (bits * s) for s in range(num))
    np.testing.assert_array_equal(np.asarray(recomposed), np.asarray(q, dtype=np.int32))
    # and the ref decomposition agrees
    sl2 = slice_decompose(q, bits, num)
    for a, b in zip(sl, sl2):
        np.testing.assert_array_equal(np.asarray(a, dtype=np.int32), np.asarray(b))


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_quantization_error_bound(seed):
    """Symmetric quantization error is bounded by scale/2 elementwise."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(32, 32)), jnp.float32)
    q, scale = quantize_symmetric(x, 8)
    err = jnp.abs(q.astype(jnp.float32) * scale - x)
    assert float(err.max()) <= float(scale) * 0.5 + 1e-7


@given(
    b_lo=st.sampled_from([2, 4]),
    seed=st.integers(0, 2**31 - 1),
)
@settings(max_examples=15, deadline=None)
def test_more_noise_worse_error_monotonicity(b_lo, seed):
    """Noisier analog path -> larger expected GEMM error (paper Fig. 3
    narrative: precision costs power; here: noise costs accuracy)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(16, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(128, 16)), jnp.float32)
    key = jax.random.PRNGKey(seed)
    exact = x @ w

    def err(sigma):
        cfg = DPUConfig(bits=b_lo, dpe_size=32, noise_sigma_lsb=sigma)
        y = engine_for(cfg, "ref").matmul_float(x, w, prng_key=key)
        return float(jnp.linalg.norm(y - exact))

    e0, e1, e2 = err(0.0), err(2.0), err(16.0)
    assert e0 <= e1 + 1e-5
    assert e1 < e2


def test_ste_gradients_match_dense_path():
    """STE backward == gradients of the exact float matmul."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(4, 8, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    eng = engine_for(DPUConfig(dpe_size=16), "ref")

    gx, gw = jax.grad(
        lambda x, w: (eng.matmul_float(x, w) ** 2).sum(), argnums=(0, 1)
    )(x, w)
    # Compare direction against the dense-path gradient of the same loss
    # evaluated at the quantized output (STE: identity through quantizer).
    y = eng.matmul_float(x, w)
    gx_e = jnp.einsum("bsc,kc->bsk", 2 * y, w)
    gw_e = jnp.einsum("bsk,bsc->kc", x, 2 * y)
    assert float(jnp.linalg.norm(gx - gx_e) / jnp.linalg.norm(gx_e)) < 1e-5
    assert float(jnp.linalg.norm(gw - gw_e) / jnp.linalg.norm(gw_e)) < 1e-5


def test_dpu_config_from_scalability():
    """DPUConfig with no explicit N pulls the calibrated Table V value."""
    cfg = DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0)
    assert cfg.n == 42  # Table V
    assert cfg.m == 42
    cfg = DPUConfig(organization="ASMW", bits=4, datarate_gs=10.0)
    assert cfg.n == 12
    assert DPUConfig(bits=4).num_slices == 2
    assert DPUConfig(bits=4).passes == 4
    assert DPUConfig(bits=4, dpe_size=83).num_chunks(4096) == 50
