"""Differentiable signal-chain stages for the analog channel model.

Each stage is a small, jit/vmap-compatible array transform; the composition
order (paper §IV, DESIGN.md §8) is

    crosstalk (operand level) -> analog accumulate -> filter truncation
    -> detector noise -> ADC (round + saturate)

Non-smooth stages use straight-through estimators (:func:`round_ste`) so the
whole chain is differentiable; smooth stages are plain jnp and get exact
gradients.  The gaussian generator is *counter-based* (murmur3-style integer
mixing + Box-Muller) rather than ``jax.random`` so the exact same code runs
inside the Pallas TPU kernel (where ``jax.random`` / ``pltpu.prng_*`` are
unavailable or backend-specific) and in interpret mode on CPU — bitwise
deterministic for a fixed seed and layout.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Straight-through rounding (the ADC quantizer)
# ---------------------------------------------------------------------------
@jax.custom_vjp
def round_ste(x: jax.Array) -> jax.Array:
    """``jnp.round`` with an identity (straight-through) gradient."""
    return jnp.round(x)


def _round_ste_fwd(x):
    return jnp.round(x), None


def _round_ste_bwd(_, g):
    return (g,)


round_ste.defvjp(_round_ste_fwd, _round_ste_bwd)


def adc_quantize(
    a: jax.Array, adc_bits: Optional[int], *, differentiable: bool = False
) -> jax.Array:
    """ADC stage: round to integer psum LSBs, saturate to ``adc_bits``.

    ``differentiable=True`` keeps the output float and routes rounding
    through :func:`round_ste` (clipping already has the usual subgradient);
    the default integer path is used by the int-level DPU datapath.
    """
    q = round_ste(a) if differentiable else jnp.round(a)
    if adc_bits is not None:
        lim = 2 ** (adc_bits - 1) - 1
        q = jnp.clip(q, -lim, lim)
    return q if differentiable else q.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Crosstalk perturbations (operand level, within one DPE chunk)
# ---------------------------------------------------------------------------
def neighbor_sum(x: jax.Array, axis: int) -> jax.Array:
    """Sum of the two spectrally-adjacent channels, zero at chunk edges.

    ``axis`` indexes the wavelength (fan-in) dimension of one DPE chunk; a
    chunk boundary is a physical DPE boundary, so leakage never crosses it.
    Implemented with concatenate+slice (not ``roll``) so edges see zeros and
    the same code lowers inside Pallas kernels.
    """
    axis = axis % x.ndim
    zshape = list(x.shape)
    zshape[axis] = 1
    zero = jnp.zeros(zshape, x.dtype)
    idx_lo = [slice(None)] * x.ndim
    idx_hi = [slice(None)] * x.ndim
    idx_lo[axis] = slice(1, None)     # left-shift: neighbor at +1
    idx_hi[axis] = slice(None, -1)    # right-shift: neighbor at -1
    up = jnp.concatenate([x[tuple(idx_lo)], zero], axis=axis)
    dn = jnp.concatenate([zero, x[tuple(idx_hi)]], axis=axis)
    return up + dn


def filter_truncation(a: jax.Array, alpha: float) -> jax.Array:
    """Aggregation-filter truncation: amplitude compression ``(1 - alpha)``.

    The partial-drop filter truncates the modulated symbol's spectrum
    (paper Table II, "filter truncation"); the surviving fraction of the
    amplitude is ``1 - alpha``.  Linear, hence exactly differentiable.
    """
    return a * (1.0 - alpha)


# ---------------------------------------------------------------------------
# Counter-based gaussian noise (shared between oracle and Pallas kernel)
# ---------------------------------------------------------------------------
_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLDEN = 0x9E3779B9
_S1 = 0x27D4EB2F
_S2 = 0x165667B1
_S3 = 0x5BF03635


def hash_mix32(x: jax.Array) -> jax.Array:
    """murmur3 fmix32 finalizer — a full-avalanche 32-bit mixer."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(_M2)
    x = x ^ (x >> jnp.uint32(16))
    return x


def fold_seed(seed: jax.Array, *ids) -> jax.Array:
    """Fold integer stream ids (pass / chunk / grid indices) into a seed."""
    s = seed.astype(jnp.uint32)
    for i, v in enumerate(ids):
        v = jnp.asarray(v).astype(jnp.uint32)
        s = hash_mix32(s ^ (v + jnp.uint32(1)) * jnp.uint32(_GOLDEN) ^ jnp.uint32(i))
    return s


def gaussian_from_counter(base: jax.Array, shape) -> jax.Array:
    """Standard-normal draws of ``shape`` from a mixed ``base`` stream seed.

    Element counters are hashed into two independent uniform streams and
    combined with Box-Muller.  Pure jnp (iota / integer ops / transcendental
    VPU ops), so it lowers identically inside Pallas TPU kernels and in
    interpret mode.
    """
    if len(shape) == 1:
        ctr = jax.lax.iota(jnp.uint32, shape[0])
    else:
        ctr = jnp.zeros(shape, jnp.uint32)
        stride = jnp.uint32(1)
        for ax in range(len(shape) - 1, -1, -1):
            ctr = ctr + jax.lax.broadcasted_iota(jnp.uint32, shape, ax) * stride
            stride = stride * jnp.uint32(shape[ax])
    u1 = hash_mix32(base ^ (ctr * jnp.uint32(_S1)))
    u2 = hash_mix32(base ^ (ctr * jnp.uint32(_S2)) ^ jnp.uint32(_S3))
    # 24-bit mantissa uniforms; u1 offset keeps log() finite.  The 24-bit
    # words go through int32 (same value) because the TPU kernel compiler
    # has no uint32 -> float32 conversion.
    def unit(u):
        return (u >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) * (
            1.0 / (1 << 24)
        )

    f1 = unit(u1) + (0.5 / (1 << 24))
    f2 = unit(u2)
    return jnp.sqrt(-2.0 * jnp.log(f1)) * jnp.cos((2.0 * jnp.pi) * f2)


def data_tweak(seed: jax.Array, *arrays: jax.Array) -> jax.Array:
    """Fold a cheap content hash of the operands into a stream seed.

    Two GEMMs that share a ``noise_seed`` and a psum shape (e.g. the
    same-shaped projections of every transformer layer, or successive QAT
    steps) would otherwise draw bitwise-identical noise arrays and their
    analog errors would add coherently instead of averaging out.  Folding
    an operand-dependent word keeps full determinism (same seed + same
    inputs => same noise) while decorrelating distinct layers/steps.
    Zero-padding is hash-neutral (zeros contribute nothing to the sum), so
    callers may tweak before or after padding.
    """
    s = seed.astype(jnp.uint32)
    for a in arrays:
        word = (a.astype(jnp.uint32) * jnp.uint32(_S1)).sum(dtype=jnp.uint32)
        s = hash_mix32(s ^ word)
    return s


def key_zero_cotangent(prng_key: Optional[jax.Array]):
    """The zero cotangent custom-VJP rules must return for a PRNG-key
    argument: ``None`` for an absent key, a symbolic-zero ``float0`` array
    for an integer-typed one."""
    if prng_key is None:
        return None
    import numpy as np

    return np.zeros(prng_key.shape, dtype=jax.dtypes.float0)


def seed_from_key(prng_key: Optional[jax.Array]) -> Optional[jax.Array]:
    """Collapse a JAX PRNG key (typed or raw uint32) to a uint32 seed.

    Lets the counter-based generator honour the ``jax.random`` key
    discipline of the callers: same key -> bitwise-identical noise,
    ``fold_in``-style independence comes from :func:`fold_seed`.
    """
    if prng_key is None:
        return None
    key = prng_key
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    data = key.astype(jnp.uint32).reshape(-1)
    seed = jnp.uint32(0)
    for i in range(data.shape[0]):
        seed = hash_mix32(seed ^ data[i] ^ jnp.uint32(i * _GOLDEN & 0xFFFFFFFF))
    return seed


def detector_noise(a: jax.Array, sigma: float, base: jax.Array) -> jax.Array:
    """Additive shot/thermal/RIN noise at the balanced photodetector.

    ``sigma`` is the per-psum standard deviation in psum LSBs (set by the
    delivered-power SNR, see ``channel.build_channel_model``); ``base`` is a
    uint32 stream seed from :func:`fold_seed`.  The draw does not depend on
    ``a`` so gradients pass through exactly.
    """
    if sigma <= 0.0:
        return a
    return a + sigma * jax.lax.stop_gradient(gaussian_from_counter(base, a.shape))
