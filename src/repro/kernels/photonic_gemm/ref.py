"""Pure-jnp oracle for the photonic GEMM kernel — the repo's one jnp
implementation of the DPU datapath.

Implements exactly the kernel's semantics — signed-magnitude bit-slicing,
DPE-size (N) psum chunking with optional ADC saturation, shift-add recombine —
with no Pallas, no tiling.  It is the ``ref`` backend of
:class:`repro.photonic.PhotonicEngine` (``engine_for(cfg, "ref")``), the
gold reference of the tests and the portable fallback of the models.

With a :class:`repro.noise.ChannelModel` the oracle applies the full analog
signal chain per slice-pair pass, seeded by the engine's stream seed
(:meth:`repro.photonic.PhotonicEngine.stream_seed`); the Pallas kernel draws
its noise from tile-local streams and agrees with the oracle
*statistically* (mean/variance), not bitwise.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.noise.channel import ChannelModel, analog_pass_psums
from repro.noise.stages import fold_seed


def slice_decompose(q: jax.Array, slice_bits: int, num_slices: int) -> list:
    """Signed-magnitude slices: sum_s out[s] * 2**(slice_bits*s) == q."""
    qi = q.astype(jnp.int32)
    sgn = jnp.sign(qi)
    mag = jnp.abs(qi)
    mask = (1 << slice_bits) - 1
    return [sgn * ((mag >> (slice_bits * s)) & mask) for s in range(num_slices)]


def photonic_gemm_ref(
    xq: jax.Array,  # (R, K) int8
    wq: jax.Array,  # (K, C) int8
    *,
    slice_bits: int = 4,
    num_slices: int = 2,
    n_chunk: int = 128,
    adc_bits: Optional[int] = None,
    channel: Optional[ChannelModel] = None,
    seed: Optional[jax.Array] = None,  # uint32; required if channel has noise
) -> jax.Array:
    """Reference int32 GEMM through the DPU datapath."""
    r, k = xq.shape
    _, c = wq.shape
    analog = channel is not None and channel.analog
    if analog and channel.detector_sigma_lsb > 0.0 and seed is None:
        raise ValueError("channel with detector noise requires a seed")
    if channel is not None and channel.adc_bits is not None:
        adc_bits = channel.adc_bits
    pad = (-k) % n_chunk
    if pad:
        xq = jnp.pad(xq, ((0, 0), (0, pad)))
        wq = jnp.pad(wq, ((0, pad), (0, 0)))
    kp = k + pad
    chunks = kp // n_chunk

    x_sl = slice_decompose(xq, slice_bits, num_slices)
    w_sl = slice_decompose(wq, slice_bits, num_slices)

    out = jnp.zeros((r, c), jnp.int32)
    for si in range(num_slices):
        xs = x_sl[si].reshape(r, chunks, n_chunk)
        for ti in range(num_slices):
            ws = w_sl[ti].reshape(chunks, n_chunk, c)
            shift = slice_bits * (si + ti)
            if analog:
                pass_seed = fold_seed(
                    seed if seed is not None else jnp.uint32(0),
                    si * num_slices + ti,
                )
                psum = analog_pass_psums(xs, ws, channel, pass_seed)
            else:
                psum = jnp.einsum(
                    "rgn,gnc->rgc", xs, ws, preferred_element_type=jnp.int32
                )
                if adc_bits is not None:
                    lim = 2 ** (adc_bits - 1) - 1
                    psum = jnp.clip(psum, -lim, lim)
            out = out + (psum.sum(axis=1) << shift)
    return out


def exact_int_gemm(xq: jax.Array, wq: jax.Array) -> jax.Array:
    """The ideal integer GEMM (what the DPU must equal when ideal)."""
    return jnp.matmul(
        xq.astype(jnp.int32), wq.astype(jnp.int32), preferred_element_type=jnp.int32
    )
