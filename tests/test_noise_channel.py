"""The `repro.noise` channel model: Table II/III structure, backend
equivalence (bit-identical ideal path, statistical noise agreement),
determinism, and differentiability."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import scalability
from repro.core.dpu import DPUConfig
from repro.core.organizations import ORGANIZATIONS, through_device_count
from repro.kernels.photonic_gemm.ref import exact_int_gemm, photonic_gemm_ref
from repro.noise import (
    apply_channel_psum,
    build_channel_model,
    fold_seed,
    gaussian_from_counter,
    neighbor_sum,
    round_ste,
)
from repro.photonic import engine_for


def _int_gemm(xq, wq, cfg, *, backend, prng_key=None, **kw):
    """Integer GEMM of ``cfg``'s DPU through one engine backend."""
    return engine_for(cfg, backend).int_gemm(xq, wq, prng_key=prng_key, **kw)


def _rand_int8(rng, shape):
    return jnp.asarray(rng.integers(-127, 128, shape, dtype=np.int8))


# ---------------------------------------------------------------------------
# Table II — crosstalk presence/absence per organization
# ---------------------------------------------------------------------------
def test_table2_crosstalk_structure():
    asmw = build_channel_model("ASMW", n=16)
    masw = build_channel_model("MASW", n=16)
    smwa = build_channel_model("SMWA", n=16)
    # ASMW: inter-modulation + cross-weight, no filter truncation.
    assert asmw.intermod_eps > 0 and asmw.crossweight_eps > 0
    assert asmw.filter_alpha == 0.0
    # MASW: cross-weight + filter truncation, no inter-modulation.
    assert masw.intermod_eps == 0.0
    assert masw.crossweight_eps > 0 and masw.filter_alpha > 0
    # SMWA ("hitless"): only filter truncation.
    assert smwa.intermod_eps == 0.0 and smwa.crossweight_eps == 0.0
    assert smwa.filter_alpha > 0
    # Budget ordering (paper §IV-C): cross-weight (3 dB) > inter-mod (1 dB)
    # > filter (0.5 dB).
    assert asmw.crossweight_eps > asmw.intermod_eps > smwa.filter_alpha / 2


# ---------------------------------------------------------------------------
# Table III — loss-chain structure
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [4, 17, 42, 83])
def test_table3_through_loss_formulas(n):
    p = scalability.CALIBRATED
    for org, count in (("ASMW", 2 * (n - 1)), ("MASW", n), ("SMWA", 2)):
        ch = build_channel_model(org, n=n)
        assert count == through_device_count(org, n)
        np.testing.assert_allclose(
            ch.through_loss_db, count * p.p_mrm_obl_db, rtol=1e-12
        )


def test_through_loss_growth():
    """ASMW through loss grows ~2N; SMWA's is constant in N (Table III)."""
    a8, a64 = (build_channel_model("ASMW", n=n).through_loss_db for n in (8, 64))
    s8, s64 = (build_channel_model("SMWA", n=n).through_loss_db for n in (8, 64))
    assert a64 / a8 == pytest.approx((2 * 63) / (2 * 7))
    assert s64 == s8  # constant: 2 devices regardless of N
    # MASW sits between.
    m8, m64 = (build_channel_model("MASW", n=n).through_loss_db for n in (8, 64))
    assert a64 > m64 > s64
    assert m64 / m8 == pytest.approx(8.0)


def test_detector_sigma_ordering_and_monotonicity():
    """Penalty + loss ordering (SMWA best) shows up as noise sigma; sigma
    grows with N for every organization (less power per channel)."""
    for n in (8, 17, 42):
        sig = {o: build_channel_model(o, n=n).detector_sigma_lsb for o in ORGANIZATIONS}
        assert sig["ASMW"] > sig["MASW"] > sig["SMWA"], (n, sig)
    for org in ORGANIZATIONS:
        sigs = [
            build_channel_model(org, n=n).detector_sigma_lsb for n in (8, 16, 32, 64)
        ]
        assert sigs == sorted(sigs)


def test_snr_consistent_with_scalability_solver():
    """At the calibrated achievable N the delivered-power SNR meets the
    B-bit ENOB requirement; one step past it, it no longer does."""
    margin = scalability.calibration().snr_margin_db
    need_db = 6.02 * 4 + 1.76 + margin
    for org in ORGANIZATIONS:
        n_max = scalability.calibrated_max_n(org, 4, 5.0)
        ch = build_channel_model(org, n=n_max, bits=4, datarate_gs=5.0)
        assert ch.snr_db >= need_db - 1e-6, (org, ch.snr_db, need_db)
        beyond = build_channel_model(org, n=n_max + 1, bits=4, datarate_gs=5.0)
        assert beyond.snr_db < need_db
        assert beyond.detector_sigma_lsb > ch.detector_sigma_lsb


# ---------------------------------------------------------------------------
# Ideal channel == exact integer path, bit-identical, both backends
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("org", ORGANIZATIONS)
def test_disabled_channel_bit_identical(org):
    rng = np.random.default_rng(0)
    xq = _rand_int8(rng, (16, 200))
    wq = _rand_int8(rng, (200, 96))
    ch = build_channel_model(org, n=21).disable("all")
    assert ch.is_ideal
    cfg = DPUConfig(organization=org, dpe_size=21, channel=ch)
    gold = np.asarray(exact_int_gemm(xq, wq))
    for backend in ("ref", "pallas"):
        out = _int_gemm(xq, wq, cfg, backend=backend)
        np.testing.assert_array_equal(np.asarray(out), gold)
    # ...and the compiled oracle too.
    eng = engine_for(cfg, "ref")
    out = jax.jit(lambda a, b: eng.int_gemm(a, b))(xq, wq)
    np.testing.assert_array_equal(np.asarray(out), gold)


def test_builder_enable_flags_disable_stages():
    ch = build_channel_model(
        "MASW",
        n=16,
        enable_crosstalk=False,
        enable_detector_noise=False,
    )
    assert ch.is_ideal
    full = build_channel_model("MASW", n=16)
    assert not full.is_ideal
    assert full.disable("crosstalk").crossweight_eps == 0.0
    assert full.disable("detector").analog  # crosstalk still on


# ---------------------------------------------------------------------------
# Deterministic stages: ref / pallas agree bitwise, whatever the tiling
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("org,n", [("ASMW", 17), ("MASW", 21), ("SMWA", 42)])
def test_crosstalk_stages_bitwise_across_backends(org, n):
    rng = np.random.default_rng(2)
    xq = _rand_int8(rng, (32, 200))
    wq = _rand_int8(rng, (200, 64))
    ch = build_channel_model(org, n=n).disable("detector")
    cfg = DPUConfig(organization=org, dpe_size=n, channel=ch)
    ref = np.asarray(_int_gemm(xq, wq, cfg, backend="ref"))
    pal = np.asarray(_int_gemm(xq, wq, cfg, backend="pallas"))
    pal_tiled = np.asarray(
        _int_gemm(xq, wq, cfg, backend="pallas", tile_r=8, tile_c=256)
    )
    np.testing.assert_array_equal(ref, pal)
    np.testing.assert_array_equal(ref, pal_tiled)


def test_crosstalk_perturbs_but_smwa_unbiased_by_neighbors():
    """Crosstalk-on changes results for ASMW/MASW; SMWA's only Table II
    effect is filter truncation (a pure amplitude compression)."""
    rng = np.random.default_rng(3)
    xq = _rand_int8(rng, (8, 84))
    wq = _rand_int8(rng, (84, 16))
    gold = np.asarray(exact_int_gemm(xq, wq))
    for org in ("ASMW", "MASW"):
        ch = build_channel_model(org, n=21).disable("detector", "filter")
        cfg = DPUConfig(organization=org, dpe_size=21, channel=ch)
        out = np.asarray(_int_gemm(xq, wq, cfg, backend="ref"))
        assert (out != gold).any(), org
    ch = build_channel_model("SMWA", n=21).disable("detector", "filter")
    cfg = DPUConfig(organization="SMWA", dpe_size=21, channel=ch)
    out = np.asarray(_int_gemm(xq, wq, cfg, backend="ref"))
    np.testing.assert_array_equal(out, gold)  # nothing left to perturb


# ---------------------------------------------------------------------------
# Noise: statistical pallas/oracle agreement, bitwise ref==oracle
# ---------------------------------------------------------------------------
def test_pallas_noise_statistics_match_oracle():
    rng = np.random.default_rng(4)
    xq = _rand_int8(rng, (128, 256))
    wq = _rand_int8(rng, (256, 128))
    ch = build_channel_model("SMWA", n=64).disable("crosstalk")
    cfg = DPUConfig(dpe_size=64, channel=ch, noise_seed=3)
    gold = np.asarray(exact_int_gemm(xq, wq), np.float64)
    e_pal = (
        np.asarray(_int_gemm(xq, wq, cfg, backend="pallas"), np.float64)
        - gold
    )
    e_ref = np.asarray(_int_gemm(xq, wq, cfg, backend="ref"), np.float64) - gold
    assert abs(e_pal.std() / e_ref.std() - 1.0) < 0.1, (e_pal.std(), e_ref.std())
    # Means consistent with zero (std over sqrt(n_samples) scale).
    tol = 4 * e_ref.std() / np.sqrt(e_ref.size)
    assert abs(e_pal.mean()) < tol and abs(e_ref.mean()) < tol


def test_pallas_noise_statistics_ragged_k():
    """K-padding chunks must not receive noise (variance would inflate)."""
    rng = np.random.default_rng(5)
    xq = _rand_int8(rng, (64, 200))   # 200 = 2 full + 1 partial chunk of 83
    wq = _rand_int8(rng, (200, 128))
    ch = build_channel_model("SMWA", n=83).disable("crosstalk")
    cfg = DPUConfig(dpe_size=83, channel=ch, noise_seed=9)
    gold = np.asarray(exact_int_gemm(xq, wq), np.float64)
    e_pal = (
        np.asarray(_int_gemm(xq, wq, cfg, backend="pallas"), np.float64)
        - gold
    )
    e_ref = np.asarray(_int_gemm(xq, wq, cfg, backend="ref"), np.float64) - gold
    assert abs(e_pal.std() / e_ref.std() - 1.0) < 0.1, (e_pal.std(), e_ref.std())


def test_noisy_ref_bitwise_equals_dpu_oracle():
    """The ``ref`` backend under noise is the jnp oracle fed the engine's
    documented stream seed (``site=None``: the bare key), bit for bit."""
    rng = np.random.default_rng(6)
    xq = _rand_int8(rng, (16, 100))
    wq = _rand_int8(rng, (100, 24))
    ch = build_channel_model("ASMW", n=17)
    cfg = DPUConfig(organization="ASMW", dpe_size=17, channel=ch)
    key = jax.random.PRNGKey(11)
    eng = engine_for(cfg, "ref")
    a = photonic_gemm_ref(
        xq,
        wq,
        slice_bits=cfg.bits,
        num_slices=cfg.num_slices,
        n_chunk=cfg.n,
        channel=ch,
        seed=eng.stream_seed(None, None, key, xq, wq),
    )
    b = _int_gemm(xq, wq, cfg, backend="ref", prng_key=key)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    c = _int_gemm(xq, wq, cfg, backend="ref", prng_key=jax.random.PRNGKey(12))
    assert (np.asarray(b) != np.asarray(c)).any()


def test_pallas_noise_seed_determinism():
    rng = np.random.default_rng(7)
    xq = _rand_int8(rng, (32, 128))
    wq = _rand_int8(rng, (128, 32))
    ch = build_channel_model("MASW", n=32)
    c1 = DPUConfig(organization="MASW", dpe_size=32, channel=ch, noise_seed=1)
    c2 = DPUConfig(organization="MASW", dpe_size=32, channel=ch, noise_seed=2)
    a = _int_gemm(xq, wq, c1, backend="pallas")
    b = _int_gemm(xq, wq, c1, backend="pallas")
    c = _int_gemm(xq, wq, c2, backend="pallas")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (np.asarray(a) != np.asarray(c)).any()


# ---------------------------------------------------------------------------
# Stage primitives
# ---------------------------------------------------------------------------
def test_neighbor_sum_zero_edges():
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0]])
    out = np.asarray(neighbor_sum(x, axis=1))
    np.testing.assert_allclose(out, [[2.0, 4.0, 6.0, 3.0]])


def test_gaussian_from_counter_moments():
    z = np.asarray(gaussian_from_counter(fold_seed(jnp.uint32(42), 0), (256, 256)))
    assert abs(z.mean()) < 0.02
    assert abs(z.std() - 1.0) < 0.02
    # Distinct streams are decorrelated.
    z2 = np.asarray(gaussian_from_counter(fold_seed(jnp.uint32(42), 1), (256, 256)))
    assert abs(np.corrcoef(z.ravel(), z2.ravel())[0, 1]) < 0.02


def test_round_ste_identity_gradient():
    g = jax.grad(lambda x: round_ste(3.0 * x).sum())(jnp.ones(5))
    np.testing.assert_allclose(np.asarray(g), 3.0)


def test_apply_channel_psum_differentiable():
    """filter -> noise -> ADC chain passes gradients (STE through round,
    zero grad only where the ADC saturates)."""
    ch = build_channel_model("SMWA", n=16, adc_bits=8)
    a = jnp.asarray([0.4, 10.0, 1e6, -1e6])  # last two saturate
    seed = fold_seed(jnp.uint32(0), 0)

    def f(a):
        return apply_channel_psum(a, ch, seed).sum()

    g = np.asarray(jax.grad(f)(a))
    scale = 1.0 - ch.filter_alpha
    np.testing.assert_allclose(g[:2], scale, rtol=1e-6)
    np.testing.assert_allclose(g[2:], 0.0)


def test_channel_model_hashable_jit_static():
    ch = build_channel_model("SMWA", n=16)
    assert hash(ch) == hash(dataclasses.replace(ch))

    @jax.jit
    def f(a):
        return apply_channel_psum(a, ch, fold_seed(jnp.uint32(1), 0))

    out = f(jnp.ones((4, 4)) * 100.0)
    assert out.shape == (4, 4)
    # vmap over inputs with the channel closed over.
    outs = jax.vmap(lambda a: apply_channel_psum(a, ch, fold_seed(jnp.uint32(1), 0)))(
        jnp.ones((3, 5)) * 50.0
    )
    assert outs.shape == (3, 5)


def test_adc_saturation_under_channel():
    rng = np.random.default_rng(8)
    xq = _rand_int8(rng, (8, 128))
    wq = _rand_int8(rng, (128, 8))
    ch = build_channel_model("SMWA", n=32, adc_bits=8).disable("detector", "filter")
    cfg = DPUConfig(dpe_size=32, channel=ch)
    gold = np.asarray(exact_int_gemm(xq, wq))
    sat = np.asarray(_int_gemm(xq, wq, cfg, backend="ref"))
    assert np.abs(sat).max() <= np.abs(gold).max()
    assert (sat != gold).any()
    # Same semantics on the Pallas path.
    np.testing.assert_array_equal(
        sat, np.asarray(_int_gemm(xq, wq, cfg, backend="pallas"))
    )
