"""Functional model of the photonic DPU datapath (paper §III-A / §V).

A DPU executes a GEMM by:

1. **Quantizing** operands to the digital precision (``operand_bits``, int8
   for the paper's CNNs).
2. **Bit-slicing** each operand into ``ceil(operand_bits / B)`` slices of the
   analog precision ``B`` (paper §III: "If the supported value of B is less
   than the precision requirement ... bit-slicing is applied").  Incoherent
   photonics carries magnitudes; signs ride on the balanced-photodetector
   differential rails — numerically we carry a signed magnitude slice.
3. **Chunking** the dot-product (contraction) dimension into chunks of the
   achievable DPE size ``N`` (from the scalability solver).  Each chunk's
   analog summation produces a *psum* that is digitized by the ADC and
   accumulated by the electronic reduction network.
4. **Shift-adding** slice-pair passes (2^{B(s+t)} weights) and
   **dequantizing**.

With no noise/saturation enabled the model is *numerically exact*: it equals
the integer GEMM of the quantized operands (tested).  Analog non-idealities
are modeled by an organization-aware :class:`repro.noise.ChannelModel`
(crosstalk per Table II, loss-chain-derived detector noise per Tables
III–IV, filter truncation, ADC quantization/saturation — see DESIGN.md §8);
the legacy scalar ``noise_sigma_lsb`` is kept as a shorthand for a
detector-noise-only channel.

Noise determinism: every noisy call needs an explicit randomness source —
either ``prng_key`` (same key => bitwise-identical result) or the
``DPUConfig.noise_seed`` field (the documented deterministic path used when
no key can be threaded, e.g. the model serving stack).  A noisy call with
neither raises ``ValueError`` rather than silently drawing fresh noise.

This module holds the DPU's configuration and its digital shoulders
(quantization, bit-slicing, the straight-through gradient).  The datapath
itself runs through :class:`repro.photonic.PhotonicEngine`: the pure-jnp
oracle is its ``ref`` backend (``repro.kernels.photonic_gemm.ref``) and the
TPU Pallas kernel its ``pallas`` backend, with identical semantics (fused
slicing + chunked accumulation in VMEM)::

    engine_for(cfg, "ref").int_gemm(xq, wq)     # int32 (R, C)
    engine_for(cfg, "ref").matmul_float(x, w)   # float, STE-differentiable
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import platforms as _platforms
from repro.core import scalability
from repro.core.params import PhotonicParams
from repro.noise.channel import ChannelModel, shard_local_channel
from repro.noise.stages import seed_from_key
from repro.orgs import OrgSpec, resolve


@dataclasses.dataclass(frozen=True)
class DPUConfig:
    """Operating point of a photonic DPU (organization + precision + rate).

    ``organization`` accepts a name ("SMWA", case-insensitive), a
    four-letter block-order string ("MWAS"), or a typed
    :class:`repro.orgs.OrgSpec`; it is validated eagerly and stored as
    the canonical order name (unknown orders raise ``ValueError`` naming
    the valid choices instead of a late ``KeyError``).  ``platform``
    follows the same pattern through :func:`repro.platforms.resolve`
    (canonical name stored, eager ``ValueError`` on unknown names) and
    selects the material platform the calibrated DPE size — and any
    channel built from this config — is derived on.
    """

    organization: "str | OrgSpec" = "SMWA"
    bits: int = 4              # analog precision B per pass
    operand_bits: int = 8      # digital operand precision (paper: int8 CNNs)
    datarate_gs: float = 5.0   # symbol rate [GS/s]
    dpe_size: Optional[int] = None   # N; None -> calibrated scalability solver
    dpu_fanout: Optional[int] = None  # M; None -> = N (paper assumption)
    noise_sigma_lsb: float = 0.0     # legacy: detector-noise-only channel
    adc_bits: Optional[int] = None   # ADC saturation range; None = ideal
    # Structural analog channel (repro.noise); overrides noise_sigma_lsb.
    channel: Optional[ChannelModel] = None
    # Deterministic noise seed used when no prng_key is threaded to a call
    # (the documented deterministic path; see module docstring).
    noise_seed: Optional[int] = None
    # Material platform (repro.platforms): canonical name after resolve.
    platform: "str | _platforms.PlatformSpec" = "SOI"

    def __post_init__(self):
        # One resolution point (repro.orgs.resolve): eager validation, one
        # normalization.  Storing the canonical name keeps the config's
        # repr/equality/hash identical to the historical string form.
        object.__setattr__(self, "organization", resolve(self.organization).name)
        # Same pattern for the platform (repro.platforms.resolve).
        object.__setattr__(self, "platform", _platforms.resolve(self.platform).name)

    @property
    def org_spec(self) -> OrgSpec:
        """The typed organization spec this config runs (repro.orgs)."""
        return resolve(self.organization)

    @property
    def platform_spec(self) -> _platforms.PlatformSpec:
        """The typed platform spec this config runs on (repro.platforms)."""
        return _platforms.resolve(self.platform)

    @property
    def n(self) -> int:
        if self.dpe_size is not None:
            return self.dpe_size
        n = scalability.calibrated_max_n(
            self.organization, self.bits, self.datarate_gs, platform=self.platform
        )
        if n <= 0:
            raise ValueError(
                f"infeasible operating point: {self.organization} B={self.bits} "
                f"DR={self.datarate_gs} GS/s"
            )
        return n

    @property
    def m(self) -> int:
        return self.dpu_fanout if self.dpu_fanout is not None else self.n

    @property
    def num_slices(self) -> int:
        return -(-self.operand_bits // self.bits)  # ceil

    @property
    def passes(self) -> int:
        """Slice-pair passes per GEMM element (inputs x weights)."""
        return self.num_slices * self.num_slices

    def num_chunks(self, k: int) -> int:
        """psum chunks for a contraction of length k."""
        return -(-k // self.n)

    def effective_channel(self) -> Optional[ChannelModel]:
        """The channel model this config implies (None = ideal datapath).

        ``channel`` wins when set (inheriting ``adc_bits`` from the config
        if the channel leaves it unset); a bare ``noise_sigma_lsb`` maps to
        a detector-noise-only channel; ADC-only configs return None and keep
        the exact-integer path with saturation (bit-compatible with the
        pre-channel behavior).
        """
        if self.channel is not None:
            ch = self.channel
            if ch.adc_bits is None and self.adc_bits is not None:
                ch = dataclasses.replace(ch, adc_bits=self.adc_bits)
            return ch
        if self.noise_sigma_lsb > 0.0:
            return ChannelModel(
                organization=self.organization,
                bits=self.bits,
                datarate_gs=self.datarate_gs,
                detector_sigma_lsb=self.noise_sigma_lsb,
                adc_bits=self.adc_bits,
                platform=self.platform,
            )
        return None

    def shard_local(self, k_local: int) -> "DPUConfig":
        """The per-shard operating point of a K-sharded GEMM.

        The paper's Summation manipulation accumulates per-DPE partials in
        the digital domain; sharding the contraction axis over a device
        mesh is the same semantics at system scale, and it changes the
        physics: each shard's DPE fan-in is ``N_local = min(N, K_local)``,
        and the Table II/III channel must be evaluated there rather than
        at the global ``N`` (:func:`repro.noise.shard_local_channel`).
        Ideal configs only clamp the chunk size, which is numerically
        inert — sharded and unsharded ideal GEMMs stay bitwise equal.
        """
        n_local = min(self.n, max(int(k_local), 1))
        updates: dict = {}
        if self.dpe_size != n_local:
            updates["dpe_size"] = n_local
        if self.channel is not None:
            ch = shard_local_channel(self.channel, n_local)
            if ch is not self.channel:
                updates["channel"] = ch
        return dataclasses.replace(self, **updates) if updates else self

    def noise_seed_array(
        self, prng_key: Optional[jax.Array], *, what: str = "noise"
    ) -> jax.Array:
        """uint32 stream seed from ``prng_key`` or ``noise_seed`` (in that
        order), raising the documented error when neither is given."""
        if prng_key is not None:
            return seed_from_key(prng_key)
        if self.noise_seed is not None:
            return jnp.uint32(self.noise_seed & 0xFFFFFFFF)
        raise ValueError(
            f"{what} requires a randomness source: pass prng_key or set "
            "DPUConfig.noise_seed (deterministic; same seed => bitwise-equal "
            "results)"
        )


# ---------------------------------------------------------------------------
# Quantization
# ---------------------------------------------------------------------------
def quant_scale(
    x: jax.Array,
    bits: int,
    axis: Optional[int] = None,
    *,
    amax: Optional[jax.Array] = None,
) -> jax.Array:
    """The symmetric quantization scale alone (f32), no rounding.

    Exactly the scale half of :func:`quantize_symmetric` — the fused
    Pallas hot path computes it outside the kernel (XLA fuses the abs-max
    reduction into the producer) and ships it into the kernel as an SMEM
    scalar for the in-kernel rounding prologue.
    """
    qmax = float(2 ** (bits - 1) - 1)
    if amax is None:
        amax = jnp.max(jnp.abs(x)) if axis is None else jnp.max(
            jnp.abs(x), axis=axis, keepdims=True
        )
    # Explicit reciprocal multiply: XLA's algebraic simplifier rewrites
    # divide-by-constant to exactly this inside compiled contexts (jit /
    # scan bodies), so spelling it out keeps the scale BITWISE identical
    # between eager calls and compiled ones — the invariant the prepacked
    # weight path (repro.photonic.packing) relies on.
    scale = jnp.maximum(amax, 1e-12) * (1.0 / qmax)
    return scale.astype(jnp.float32)


def quantize_with_scale(x: jax.Array, scale: jax.Array, bits: int) -> jax.Array:
    """Round/clip ``x`` against a precomputed symmetric ``scale``.

    The rounding half of :func:`quantize_symmetric` (``scale`` is traced,
    so the division is the blessed reciprocal-multiply idiom's second
    half); for f32 inputs, composing it with :func:`quant_scale` is the
    bitwise-identical op sequence of the one-shot call — which is why the
    fused hot path only fuses f32 activations (the one-shot call divides
    by the *raw-dtype* scale, so lower-precision inputs would round
    differently against the f32 SMEM scalar).
    """
    qmax = float(2 ** (bits - 1) - 1)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    dtype = jnp.int8 if bits <= 8 else jnp.int32
    return q.astype(dtype)


def quantize_symmetric(
    x: jax.Array,
    bits: int,
    axis: Optional[int] = None,
    *,
    amax: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Symmetric linear quantization to signed ``bits`` integers.

    Returns ``(q, scale)`` with ``x ~= q * scale``; ``q`` in
    ``[-(2^{bits-1}-1), 2^{bits-1}-1]`` (int8 storage for bits<=8, int32
    otherwise).  ``amax`` overrides the local abs-max reduction — the
    K-sharded engine passes the ``pmax``-reduced global abs-max so every
    shard quantizes with the bitwise-identical scale the unsharded path
    would use (max is exact under any reduction order).
    """
    qmax = float(2 ** (bits - 1) - 1)
    if amax is None:
        amax = jnp.max(jnp.abs(x)) if axis is None else jnp.max(
            jnp.abs(x), axis=axis, keepdims=True
        )
    # Same reciprocal-multiply scale as quant_scale (see the comment
    # there); kept inline so the historical raw-dtype division below is
    # byte-for-byte unchanged for non-f32 inputs.
    scale = jnp.maximum(amax, 1e-12) * (1.0 / qmax)
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    dtype = jnp.int8 if bits <= 8 else jnp.int32
    return q.astype(dtype), scale.astype(jnp.float32)


def bit_slices(q: jax.Array, slice_bits: int, num_slices: int) -> jax.Array:
    """Signed-magnitude bit-slice decomposition.

    ``q == sum_s slices[s] * 2**(slice_bits * s)`` exactly, with
    ``slices[s]`` in ``[-(2^slice_bits - 1), 2^slice_bits - 1]``.
    Stacked on a new leading axis.
    """
    sgn = jnp.sign(q).astype(jnp.int32)
    mag = jnp.abs(q.astype(jnp.int32))
    mask = (1 << slice_bits) - 1
    slices = [
        (sgn * ((mag >> (slice_bits * s)) & mask)).astype(jnp.int8)
        for s in range(num_slices)
    ]
    return jnp.stack(slices, axis=0)


# ---------------------------------------------------------------------------
# Straight-through gradient of the engine's float entry points
# ---------------------------------------------------------------------------
def dense_vjp(x: jax.Array, w: jax.Array, g: jax.Array):
    """``(dx, dw)`` of ``x @ w`` at cotangent ``g``: the straight-through
    gradient, computed as the dense matmul's own VJP in ``g``'s dtype and
    at ``x``'s rank, so it matches ``jax.grad`` of the dense product
    bit-for-bit on every backend."""
    _, vjp = jax.vjp(lambda a, b: a @ b, x.astype(g.dtype), w.astype(g.dtype))
    dx, dw = vjp(g)
    return dx.astype(x.dtype), dw.astype(w.dtype)


# ---------------------------------------------------------------------------
# Noise sigma derived from the scalability analysis (for accuracy studies)
# ---------------------------------------------------------------------------
def noise_sigma_from_snr(
    cfg: DPUConfig, params: Optional[PhotonicParams] = None
) -> float:
    """Analog noise std (in psum LSBs) implied by operating at ENOB = B.

    The DPU is sized so the *per-symbol* SNR supports B bits; the psum of a
    chunk spans ~N * (2^B-1)^2 levels, so half-LSB noise at B bits maps to a
    psum-level sigma of ``sqrt(N) / 2`` quantization-equivalent steps spread
    across the chunk (independent symbol noise accumulates in quadrature).
    """
    n = cfg.n
    return math.sqrt(n) * 0.5
