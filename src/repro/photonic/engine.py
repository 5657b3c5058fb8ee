"""The photonic execution engine: one dispatcher for every DPU GEMM.

The paper's DPUs are *weight-stationary*: weight MRRs are programmed once
per tile, then inputs stream through at the symbol rate (crossbar MRR
accelerators program weights into ring banks, arXiv:2401.16072; the
bit-sliced integer representation that makes the weight operand
prepackable is the byte-size integer GEMM decomposition of
arXiv:2407.06134).  :class:`PhotonicEngine` is the software image of that
operating point:

* a :class:`~repro.core.dpu.DPUConfig` (organization — any
  ``str | OrgSpec`` the :func:`repro.orgs.resolve` point accepts,
  including orderings the paper never studied — precision, rate, analog
  channel),
* a backend (``ref`` oracle / ``pallas`` TPU kernel / ``exact`` upper
  bound),
* a :class:`SitePolicy` deciding which *named GEMM sites* ("attn.wq",
  "ffn.wi", "lm_head", ...) execute photonically — expert-routing
  projections ("router") stay digital by default,
* deterministic site-folded seed derivation, so same-shaped GEMMs at
  different sites (or different layers of a scanned stack) draw
  decorrelated noise from one ``noise_seed``/``prng_key``.

Contracts (DESIGN.md §8/§9): with an ideal channel every backend is
bit-identical to :func:`~repro.kernels.photonic_gemm.ref.exact_int_gemm`;
deterministic analog stages are bitwise across backends; noisy calls need
``prng_key`` or ``DPUConfig.noise_seed`` (same source + same site/fold =>
bitwise-equal).  ``site=None, fold=None`` is the unfolded stream: the
seed of the bare ``noise_seed``/``prng_key``.
"""

from __future__ import annotations

import dataclasses
import fnmatch
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

# Moved to the Level-2 contract passes in PR-6; re-exported for existing
# call sites (tests, benchmarks) that import it from the engine.
from repro.analysis.contracts import count_weight_round_ops  # noqa: F401
from repro.core.dpu import (
    DPUConfig,
    bit_slices,
    dense_vjp,
    quant_scale,
    quantize_symmetric,
    quantize_with_scale,
)
from repro.kernels import pallas_interpret
from repro.kernels.photonic_gemm.epilogue import (
    ACTIVATIONS,
    EpilogueArgs,
    EpilogueSpec,
    apply_epilogue,
    as_epilogue,
)
from repro.kernels.photonic_gemm.kernel import (
    photonic_gemm_fused_pallas,
    photonic_gemm_grouped_pallas,
    photonic_gemm_pallas,
)
from repro.kernels.photonic_gemm.ref import exact_int_gemm, photonic_gemm_ref
from repro.noise.channel import sliced_channel
from repro.noise.stages import (
    data_tweak,
    fold_seed,
    key_zero_cotangent,
    seed_from_key,
)
from repro.photonic.slicing import SlicingSpec, resolve_slicing

BACKENDS = ("ref", "pallas", "exact")

# Stream-domain tag folded in ahead of a shard index, so the (site, fold=i)
# and (site, shard=i) streams never coincide (repro.photonic.sharded folds
# the mesh-axis index of each K-shard through this).
SHARD_STREAM_TAG = 0x5348

# Stream-domain tag for the bit-plane index of a sliced GEMM (DESIGN.md
# §15): each plane-pair pass folds (tag, plane) behind the site/fold/shard
# scheme, so plane streams decorrelate from each other and never collide
# with a layer-fold or shard stream.
PLANE_STREAM_TAG = 0x504C


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# The epilogue as its own compilation unit.  The Pallas kernel's fused
# epilogue always runs compiled (the kernel entry is jitted), so the
# ref/exact backends apply theirs through this jitted twin — same op
# sequence, same compilation regime — which keeps the backends
# bitwise-aligned in every calling context even for the FMA-contractable
# bias/activation stages (see the epilogue module docstring).  Under an
# outer ``jit`` this inlines, exactly as the interpret-mode kernel body
# does; the rescale-only default is contraction-free either way.
_jit_apply_epilogue = functools.partial(jax.jit, static_argnames="spec")(
    apply_epilogue
)


def _digital_reference(x, wf, bias, spec: EpilogueSpec) -> jax.Array:
    """Non-routed fallback: the exact digital op order the models used
    before epilogue fusion existed — matmul in ``x.dtype``, bias added in
    the *output* dtype, activation from the shared table — so excluded
    sites stay bitwise-stable against the pre-fusion path."""
    y = x @ wf
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if spec.activation is not None:
        y = ACTIVATIONS[spec.activation](y)
    return y


def site_hash(site: str) -> int:
    """Stable 32-bit FNV-1a of a site name (process-independent)."""
    h = 2166136261
    for ch in site.encode():
        h = (h ^ ch) * 16777619 & 0xFFFFFFFF
    return h


def pallas_tiling(cfg: DPUConfig, k: int, c: int) -> Tuple[int, int, int]:
    """Static Pallas tiling ``(n_chunk, tile_k, tile_c)`` for a (K, C) weight.

    ``n_chunk`` is the psum chunk (the DPE size ``N`` when ADC/analog
    fidelity is on), ``tile_k`` the K extent of one kernel block in the
    chunk-slot layout (:func:`to_chunk_slots`), ``tile_c`` the column
    block.  Depends only on the config and the weight shape — never on the
    activations — which is what makes the padded weight layout prepackable
    (:mod:`repro.photonic.packing`).
    """
    channel = cfg.effective_channel()
    analog = channel is not None and channel.analog
    adc_bits = channel.adc_bits if channel is not None else cfg.adc_bits
    if adc_bits is None and not analog:
        # Chunking numerically irrelevant -> MXU-aligned tiles.
        tile_k = 512 if k >= 512 else _round_up(max(k, 128), 128)
        n_chunk = min(128, tile_k)
    else:
        # DPU-faithful chunking at the achievable DPE size N: 512 // N
        # chunks per block, each in its own lane-aligned slot.
        n_chunk = cfg.n
        tile_k = chunk_lanes(n_chunk) * max(1, 512 // n_chunk)
    tile_c = min(128, _round_up(c, 128))
    return n_chunk, tile_k, tile_c


def chunk_lanes(n_chunk: int) -> int:
    """Lane width of one chunk slot: ``n_chunk`` rounded up to the 128
    lanes of a TPU vector register."""
    return _round_up(n_chunk, 128)


def stored_k(k: int, tiling: Tuple[int, int, int]) -> int:
    """Stored K extent of a logical K under ``tiling``: one slot per
    chunk, padded to whole ``tile_k`` blocks."""
    n_chunk, tile_k, _ = tiling
    return _round_up(-(-k // n_chunk) * chunk_lanes(n_chunk), tile_k)


def to_chunk_slots(a: jax.Array, k: int, tiling, axis: int) -> jax.Array:
    """Lay the logical K axis of ``a`` out as the Pallas kernel reads it.

    Chunk ``g`` (logical rows ``[g*N, (g+1)*N)``) lands at the start of
    slot ``[g*L, (g+1)*L)`` with ``L = chunk_lanes(N)``; the rest of each
    slot and the tail up to :func:`stored_k` are zero.  Zeros add nothing
    to an integer psum, hash neutrally into noise seeds, and never leak
    crosstalk into a chunk.  With ``N`` a multiple of 128 this is plain
    zero padding.
    """
    n_chunk = tiling[0]
    lanes = chunk_lanes(n_chunk)
    axis %= a.ndim
    g = stored_k(k, tiling) // lanes
    pad = [(0, 0)] * a.ndim
    pad[axis] = (0, g * n_chunk - k)
    a = jnp.pad(a, pad)
    if lanes != n_chunk:
        shape = a.shape
        a = a.reshape(shape[:axis] + (g, n_chunk) + shape[axis + 1 :])
        pad = [(0, 0)] * a.ndim
        pad[axis + 1] = (0, lanes - n_chunk)
        a = jnp.pad(a, pad).reshape(shape[:axis] + (g * lanes,) + shape[axis + 1 :])
    return a


def logical_weight(wq: jax.Array, k: int, c: int, tiling) -> jax.Array:
    """The logical ``(..., K, C)`` rows of a weight stored raw
    (``tiling=None``) or in the kernel layout of ``tiling`` — the inverse
    of :func:`to_chunk_slots`."""
    wq = wq[..., :c]
    if tiling is not None:
        n_chunk, lanes = tiling[0], chunk_lanes(tiling[0])
        lead, g = wq.shape[:-2], wq.shape[-2] // lanes
        wq = wq.reshape(*lead, g, lanes, c)[..., :n_chunk, :]
        wq = wq.reshape(*lead, g * n_chunk, c)
    return wq[..., :k, :]


@dataclasses.dataclass(frozen=True)
class SitePolicy:
    """Which named GEMM sites execute on the photonic DPU.

    Patterns are matched (``fnmatch``-style) against the full dotted site
    name ("ffn.router") *and* its final component ("router"), so
    leaf-level patterns compose across models.  A ``None`` site (caller
    did not name the GEMM) always routes — backward compatible with the
    pre-engine behavior.

    The default excludes ``router``: MoE expert-routing decisions are
    control flow, not bulk compute, and a noisy analog channel would
    perturb top-k selection; opt it in with ``exclude=()`` (or
    ``ModelConfig.photonic_exclude=()``).
    """

    include: Tuple[str, ...] = ("*",)
    exclude: Tuple[str, ...] = ("router",)

    def routes(self, site: Optional[str]) -> bool:
        if site is None:
            return True
        return self._match(self.include, site) and not self._match(self.exclude, site)

    @staticmethod
    def _match(patterns: Tuple[str, ...], site: str) -> bool:
        leaf = site.rsplit(".", 1)[-1]
        return any(
            fnmatch.fnmatchcase(site, p) or fnmatch.fnmatchcase(leaf, p)
            for p in patterns
        )


@dataclasses.dataclass(frozen=True)
class EngineInfo:
    """Structured ``PhotonicEngine.describe()`` result (PR-9 API redesign).

    A frozen snapshot of the engine's operating point — org, platform,
    backend, slicing mode, channel provenance — consumable as data
    (:meth:`to_dict`, e.g. for the dry-run manifest) while ``str(info)``
    renders the exact human-readable line ``describe()`` historically
    returned, so f-string/logging call sites are unchanged.
    """

    backend: str
    organization: str
    platform: str
    blocks: Tuple[str, ...]
    through_devices: str
    bits: int
    n: int
    datarate_gs: float
    channel: str  # "analog" | "ideal"
    slicing: Optional[int]  # plane bits, or None (unsliced)
    include: Tuple[str, ...]
    exclude: Tuple[str, ...]

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        # Byte-identical to the historical describe() text at the SOI /
        # unsliced defaults; non-default platform or slicing is inserted
        # between the channel and sites fields.
        extra = "" if self.platform == "SOI" else f"platform={self.platform}, "
        if self.slicing is not None:
            extra += f"slicing={self.slicing}b planes, "
        return (
            f"{self.backend} backend, {self.organization} "
            f"(blocks {'->'.join(self.blocks)}, through {self.through_devices}) "
            f"B={self.bits} N={self.n} @ {self.datarate_gs} GS/s, "
            f"channel={self.channel}, {extra}"
            f"sites include={list(self.include)} "
            f"exclude={list(self.exclude)}"
        )


@dataclasses.dataclass(frozen=True)
class PhotonicEngine:
    """Frozen photonic operating point + routing policy (hashable, so it
    can ride through ``jit`` closures and ``custom_vjp`` static args).

    ``slicing`` selects the bit-sliced execution mode (DESIGN.md §15):
    when set, every routed GEMM decomposes its int operands into
    ``plane_bits``-wide signed-magnitude planes, runs each plane pair
    through the analog channel re-referred to the plane full-scale, and
    recombines with exact digital shifts.  Under an ideal channel the
    result is bit-identical to the unsliced exact GEMM; under a noisy
    channel each plane pass draws a decorrelated stream (the plane index
    folds behind the site/fold/shard scheme).
    """

    dpu: DPUConfig = DPUConfig()
    backend: str = "ref"
    policy: SitePolicy = SitePolicy()
    slicing: Optional[SlicingSpec] = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"unknown photonic backend {self.backend!r}; expected one of "
                f"{BACKENDS}"
            )
        # Normalize through THE slicing resolution point (None | int |
        # str | SlicingSpec -> Optional[SlicingSpec], eager ValueError).
        object.__setattr__(self, "slicing", resolve_slicing(self.slicing))

    # -- policy --------------------------------------------------------------
    def routes(self, site: Optional[str]) -> bool:
        return self.policy.routes(site)

    def with_slicing(self, slicing) -> "PhotonicEngine":
        """This engine with a different slicing mode (frozen-replace)."""
        spec = resolve_slicing(slicing)
        if spec == self.slicing:
            return self
        return dataclasses.replace(self, slicing=spec)

    def describe(self) -> EngineInfo:
        d = self.dpu
        ch = d.effective_channel()
        spec = d.org_spec
        return EngineInfo(
            backend=self.backend,
            organization=d.organization,
            platform=ch.platform if ch is not None else d.platform,
            blocks=tuple(spec.blocks),
            through_devices=spec.through_devices,
            bits=d.bits,
            n=d.n,
            datarate_gs=d.datarate_gs,
            channel="analog" if ch is not None and ch.analog else "ideal",
            slicing=None if self.slicing is None else self.slicing.plane_bits,
            include=tuple(self.policy.include),
            exclude=tuple(self.policy.exclude),
        )

    # -- seed derivation -----------------------------------------------------
    def stream_seed(
        self,
        site: Optional[str],
        fold,
        prng_key: Optional[jax.Array],
        xq: jax.Array,
        wq: jax.Array,
        shard=None,
        plane=None,
    ) -> jax.Array:
        """uint32 noise-stream seed for one GEMM call.

        Precedence matches :meth:`DPUConfig.noise_seed_array` (explicit
        ``prng_key`` wins over ``noise_seed``; neither => the documented
        ``ValueError``).  The site name and an optional traced ``fold``
        index (e.g. the layer counter of a ``lax.scan`` stack) are folded
        in *before* the operand-content tweak, so same-shaped, same-seed
        GEMMs at different sites/layers decorrelate even when their
        operand contents coincide.  ``shard`` is the (traced) mesh-axis
        index of a K-sharded call, folded behind a tag so shards draw
        decorrelated noise and the shard stream never collides with a
        layer-fold stream; ``plane`` is the plane-pair index of a
        bit-sliced call, folded behind its own tag the same way.
        ``site=None, fold=None, shard=None, plane=None`` folds nothing:
        the bare key or ``noise_seed``, then the content tweak.
        """
        if prng_key is not None:
            key = prng_key
            if site is not None:
                key = jax.random.fold_in(key, site_hash(site) & 0x7FFFFFFF)
            if fold is not None:
                key = jax.random.fold_in(key, fold)
            if shard is not None:
                key = jax.random.fold_in(key, SHARD_STREAM_TAG)
                key = jax.random.fold_in(key, shard)
            if plane is not None:
                key = jax.random.fold_in(key, PLANE_STREAM_TAG)
                key = jax.random.fold_in(key, plane)
            seed = seed_from_key(key)
        else:
            seed = self.dpu.noise_seed_array(None)
            if site is not None:
                seed = fold_seed(seed, jnp.uint32(site_hash(site)))
            if fold is not None:
                seed = fold_seed(seed, fold)
            if shard is not None:
                seed = fold_seed(seed, jnp.uint32(SHARD_STREAM_TAG), shard)
            if plane is not None:
                seed = fold_seed(
                    seed, jnp.uint32(PLANE_STREAM_TAG), jnp.uint32(plane)
                )
        # Operand-content tweak (zero-padding is hash-neutral, so padded
        # prepacked weights derive the same stream as per-call operands).
        return data_tweak(seed, xq, wq)

    # -- integer datapath (single implementation for every caller) -----------
    def int_gemm(
        self,
        xq: jax.Array,  # (R, K) int — quantized inputs
        wq: jax.Array,  # (K, C) int, or (Kp, Cp) prepacked tile-padded
        *,
        site: Optional[str] = None,
        fold=None,
        shard=None,
        plane=None,
        prng_key: Optional[jax.Array] = None,
        logical_kc: Optional[Tuple[int, int]] = None,
        tiling: Optional[Tuple[int, int, int]] = None,
        tile_r: int = 128,
        tile_c: int = 128,
        epilogue: Optional[EpilogueArgs] = None,
        slicing=None,
    ) -> jax.Array:
        """Integer GEMM through the DPU datapath; int32 (R, C).

        ``logical_kc``/``tiling`` describe a prepacked, tile-padded weight
        (see :class:`repro.photonic.packing.PackedDense`); without them
        the weight is taken at face value and padded per call.  ``shard``
        is the mesh-axis index of a K-sharded call and ``plane`` the
        plane-pair index of a bit-sliced one (see :meth:`stream_seed`);
        both only perturb the noise stream.

        ``slicing`` overrides the engine's bit-slicing mode for this call
        (``None`` inherits ``self.slicing``; pass ``"none"`` to force the
        unsliced datapath).  The ``exact`` backend ignores slicing — the
        plane decomposition is exact, so sliced-exact == exact.

        With ``epilogue`` this is the *fused hot path* (DESIGN.md §14):
        ``xq`` may be a float activation — quantized against
        ``epilogue.x_scale`` in-kernel on the Pallas backend, digitally
        (same op sequence) elsewhere — and the int32 accumulator is
        rescaled / biased / activated before it ever materializes,
        returning f32 ``(R, C)``.  Without it the historical integer
        contract is unchanged: int in, int32 out.
        """
        mode = self.slicing if slicing is None else resolve_slicing(slicing)
        if mode is not None and self.backend != "exact":
            return self._sliced_int_gemm(
                mode,
                xq,
                wq,
                site=site,
                fold=fold,
                shard=shard,
                prng_key=prng_key,
                logical_kc=logical_kc,
                tiling=tiling,
                tile_r=tile_r,
                tile_c=tile_c,
                epilogue=epilogue,
            )
        k, c = logical_kc if logical_kc is not None else wq.shape[-2:]
        cfg = self.dpu
        channel = cfg.effective_channel()
        analog = channel is not None and channel.analog
        adc_bits = channel.adc_bits if channel is not None else cfg.adc_bits
        noisy = analog and channel.detector_sigma_lsb > 0.0

        if jnp.issubdtype(xq.dtype, jnp.floating):
            if epilogue is None:
                raise TypeError(
                    "int_gemm got float activations without an EpilogueArgs; "
                    "quantize explicitly or pass epilogue= (fused hot path)"
                )
            if noisy or self.backend != "pallas":
                # The noise-stream seed hashes the *integer* activation
                # image, and only the Pallas kernel has an in-kernel
                # prologue — everywhere else quantize digitally (the same
                # op sequence as the in-kernel ``quantize_tile``).
                xq = quantize_with_scale(xq, epilogue.x_scale, cfg.operand_bits)

        if self.backend == "exact":
            acc = exact_int_gemm(xq, wq[:k, :c])
            return acc if epilogue is None else _finish(acc, epilogue)

        seed = (
            self.stream_seed(site, fold, prng_key, xq, wq, shard=shard, plane=plane)
            if noisy
            else None
        )

        if self.backend == "ref":
            acc = photonic_gemm_ref(
                xq,
                wq[:k, :c],
                slice_bits=cfg.bits,
                num_slices=cfg.num_slices,
                n_chunk=cfg.n,
                adc_bits=adc_bits,
                channel=channel,
                seed=seed,
            )
            return acc if epilogue is None else _finish(acc, epilogue)

        assert self.backend == "pallas", self.backend
        r = xq.shape[0]
        if tiling is not None:
            n_chunk, tile_k, tc = tiling  # prepacked layout is authoritative
        else:
            n_chunk, tile_k, _ = pallas_tiling(cfg, k, c)
            # Honour the caller's tile_c bound (values above 128 are legal).
            tc = min(tile_c, _round_up(c, 128))
            cp = _round_up(c, tc)
            wq = to_chunk_slots(wq[:k, :c], k, (n_chunk, tile_k, tc), axis=0)
            wq = jnp.pad(wq, ((0, 0), (0, cp - c)))
        tr = min(tile_r, _round_up(r, 8))
        rp, cp = _round_up(r, tr), wq.shape[1]
        xp = to_chunk_slots(
            jnp.pad(xq[:, :k], ((0, rp - r), (0, 0))), k, (n_chunk, tile_k, tc), 1
        )
        ch = channel
        seed_arr = None if seed is None else seed.astype(jnp.int32).reshape(1)
        stages = dict(
            slice_bits=cfg.bits,
            num_slices=cfg.num_slices,
            chunk_lanes=chunk_lanes(n_chunk),
            adc_bits=adc_bits,
            noise_sigma=ch.detector_sigma_lsb if analog else 0.0,
            filter_alpha=ch.filter_alpha if analog else 0.0,
            intermod_eps=ch.intermod_eps if analog else 0.0,
            crossweight_eps=ch.crossweight_eps if analog else 0.0,
            valid_chunks=-(-k // n_chunk) if noisy else None,
            tile_r=tr,
            tile_c=tc,
            tile_k=tile_k,
            interpret=pallas_interpret(),
        )
        if epilogue is None:
            out = photonic_gemm_pallas(xp, wq, seed_arr, **stages)
            return out[:r, :c]
        ws = epilogue.w_scale.astype(jnp.float32).reshape(-1)
        bias = epilogue.bias
        out = photonic_gemm_fused_pallas(
            xp,
            wq,
            epilogue.x_scale,
            jnp.pad(ws, (0, cp - c)),
            None if bias is None else jnp.pad(bias.astype(jnp.float32), (0, cp - c)),
            seed_arr,
            operand_bits=cfg.operand_bits,
            activation=epilogue.spec.activation,
            out_dtype=jnp.float32,
            **stages,
        )
        return out[:r, :c]

    def _sliced_int_gemm(
        self,
        mode: SlicingSpec,
        xq: jax.Array,
        wq: jax.Array,
        *,
        site,
        fold,
        shard,
        prng_key,
        logical_kc,
        tiling,
        tile_r,
        tile_c,
        epilogue: Optional[EpilogueArgs],
    ) -> jax.Array:
        """Bit-sliced execution (DESIGN.md §15): decompose both operands
        into ``mode.plane_bits``-wide signed-magnitude planes, run every
        plane pair through the analog channel re-referred to the plane
        full-scale (:func:`repro.noise.sliced_channel`), recombine with
        exact digital shifts.  Each pass folds its plane-pair index into
        the noise stream, so plane passes decorrelate; under an ideal
        channel the shift-add recombination is bit-identical to
        :func:`exact_int_gemm`.

        Prepacked tilings are dropped — plane passes run at the plane
        engine's own tiling over the logical ``(K, C)`` region (the plane
        operands are re-materialized per call anyway).
        """
        cfg = self.dpu
        k, c = logical_kc if logical_kc is not None else wq.shape[-2:]
        if jnp.issubdtype(xq.dtype, jnp.floating):
            if epilogue is None:
                raise TypeError(
                    "int_gemm got float activations without an EpilogueArgs; "
                    "quantize explicitly or pass epilogue= (fused hot path)"
                )
            # Planes are precomputed digitally, so the activation is
            # always quantized up front (same op sequence as in-kernel).
            xq = quantize_with_scale(xq, epilogue.x_scale, cfg.operand_bits)
        plane_eng = _plane_engine(self, mode)
        p = mode.plane_bits
        planes = mode.num_planes(cfg.operand_bits)
        x_pl = bit_slices(xq, p, planes)  # (P, R, K) int8
        w_pl = bit_slices(logical_weight(wq, k, c, tiling), p, planes)  # (P, K, C)
        acc = jnp.zeros((xq.shape[0], c), jnp.int32)
        for si in range(planes):
            for ti in range(planes):
                part = plane_eng.int_gemm(
                    x_pl[si],
                    w_pl[ti],
                    site=site,
                    fold=fold,
                    shard=shard,
                    plane=si * planes + ti,
                    prng_key=prng_key,
                    tile_r=tile_r,
                    tile_c=tile_c,
                )
                # Exact digital recombination: q = sum_s plane_s * 2^(p*s)
                # per operand => plane-pair products shift by p*(si+ti).
                acc = acc + part * (1 << (p * (si + ti)))
        return acc if epilogue is None else _finish(acc, epilogue)

    # -- float entry points (STE-differentiable) -----------------------------
    def matmul_float(
        self,
        x: jax.Array,
        w: jax.Array,
        *,
        site: Optional[str] = None,
        fold=None,
        prng_key: Optional[jax.Array] = None,
        epilogue=None,
        slicing=None,
    ) -> jax.Array:
        """Float GEMM, quantizing *both* operands per call (QAT/train path).

        ``epilogue=`` (an :class:`EpilogueSpec` or :class:`Epilogue`) is
        the one spelling of the fused epilogue request (DESIGN.md §14;
        :func:`as_epilogue` is the single normalization point).
        ``slicing`` overrides the engine's bit-slicing mode for this call.
        Non-routed sites fall back to the exact digital op order.  The
        gradient is straight-through: the dense product's own VJP.
        """
        spec, bias = as_epilogue(epilogue)
        eng = self if slicing is None else self.with_slicing(slicing)
        if not eng.routes(site):
            return _digital_reference(x, w.astype(x.dtype), bias, spec)
        fold = None if fold is None else jnp.asarray(fold, jnp.int32)
        return _float_matmul((eng, site, spec), x, w, bias, fold, prng_key)

    def matmul(
        self,
        x: jax.Array,
        packed,  # PackedDense
        *,
        site: Optional[str] = None,
        fold=None,
        prng_key: Optional[jax.Array] = None,
        epilogue=None,
        slicing=None,
    ) -> jax.Array:
        """Float GEMM against a prepacked weight — the weight-stationary
        hot path: only the activation is quantized per call, and with a
        float32 activation the quantization itself is deferred into the
        Pallas kernel prologue.

        Accepts the ``epilogue=``/``slicing=`` surface exactly as
        :meth:`matmul_float`.  Non-routed sites execute the dequantized
        digital matmul.
        """
        spec, bias = as_epilogue(epilogue)
        eng = self if slicing is None else self.with_slicing(slicing)
        if not eng.routes(site):
            return _digital_reference(x, packed.dequant().astype(x.dtype), bias, spec)
        fold = None if fold is None else jnp.asarray(fold, jnp.int32)
        meta = (eng, site, packed.k, packed.c, packed.tiling, spec)
        return _packed_matmul(meta, x, packed.wq, packed.w_scale, bias, fold, prng_key)

    def grouped_matmul(
        self,
        x: jax.Array,  # (M, K) float rows, grouped by bank in tile_r-row tiles
        w,  # PackedDense with a leading bank axis, or float (E, K, C)
        tile_expert: jax.Array,  # (M // tile_r,) int32: each row tile's bank
        n_tiles: jax.Array,  # () int32: row tiles in use
        *,
        tile_r: int,
        site: Optional[str] = None,
    ) -> jax.Array:
        """Float GEMM of each row tile against its own weight bank: the
        held experts of a mixture-of-experts layer in one call (a grouped
        GEMM).  Row tile ``i`` runs the DPU datapath against bank
        ``tile_expert[i]``; the activation is quantized per tensor, each
        bank per column, as in :meth:`matmul`.  Only the first ``n_tiles``
        row tiles are defined in the result; the caller masks the rest.

        Float weights quantize per call, bitwise as their prepack would,
        with the straight-through gradient of the digital product.
        Non-routed sites run the digital product.  Noisy channels and
        bit-sliced execution have no grouped path (``NotImplementedError``).
        """
        from repro.photonic.packing import PackedDense

        if not self.routes(site):
            wf = w.dequant() if isinstance(w, PackedDense) else w
            return grouped_digital(x, wf, tile_expert, n_tiles, tile_r)
        ch = self.dpu.effective_channel()
        if self.slicing is not None or (
            ch is not None and ch.analog and ch.detector_sigma_lsb > 0.0
        ):
            raise NotImplementedError(
                "grouped GEMMs run unsliced, on channels without detector noise"
            )
        if isinstance(w, PackedDense):
            return self._grouped_int(x, w, tile_expert, n_tiles, tile_r)
        return _grouped_float((self, tile_r), x, w, tile_expert, n_tiles)

    def _grouped_int(self, x, packed, tile_expert, n_tiles, tile_r):
        """The grouped forward over a packed bank stack, f32 (M, C)."""
        cfg = self.dpu
        k, c = packed.k, packed.c
        xr = x.astype(jnp.float32)
        sx = quant_scale(xr, cfg.operand_bits)
        if self.backend == "pallas":
            ch = cfg.effective_channel()
            analog = ch is not None and ch.analog
            tiling = packed.tiling or pallas_tiling(cfg, k, c)
            wq = packed.wq
            if packed.tiling is None:
                from repro.photonic.packing import _kernel_layout

                wq = _kernel_layout(wq, k, tiling)
            n_chunk, tile_k, tile_c = tiling
            cp = wq.shape[-1]
            out = photonic_gemm_grouped_pallas(
                to_chunk_slots(xr, k, tiling, axis=1),
                wq,
                sx,
                jnp.pad(packed.w_scale.astype(jnp.float32), ((0, 0), (0, cp - c))),
                tile_expert,
                n_tiles,
                operand_bits=cfg.operand_bits,
                slice_bits=cfg.bits,
                num_slices=cfg.num_slices,
                chunk_lanes=chunk_lanes(n_chunk),
                adc_bits=ch.adc_bits if ch is not None else cfg.adc_bits,
                filter_alpha=ch.filter_alpha if analog else 0.0,
                intermod_eps=ch.intermod_eps if analog else 0.0,
                crossweight_eps=ch.crossweight_eps if analog else 0.0,
                tile_r=tile_r,
                tile_c=tile_c,
                tile_k=tile_k,
                interpret=pallas_interpret(),
            )
            return out[:, :c]
        # ref / exact: every bank over every row, each row keeping its own.
        xq = quantize_with_scale(xr, sx, cfg.operand_bits)
        wq = packed.logical_wq()
        row_bank = jnp.repeat(tile_expert, tile_r)[:, None]
        acc = jnp.zeros((xr.shape[0], c), jnp.int32)
        for e in range(wq.shape[0]):
            part = self.int_gemm(xq, wq[e], slicing="none")
            acc = jnp.where(row_bank == e, part, acc)
        ws = packed.w_scale.astype(jnp.float32)[row_bank[:, 0]]
        return apply_epilogue(acc, sx, ws, None, EpilogueSpec())


@functools.lru_cache(maxsize=None)
def _plane_engine(engine: PhotonicEngine, mode: SlicingSpec) -> PhotonicEngine:
    """The single-plane-pass engine of a sliced ``engine``: analog
    precision = plane width (one slice pass, no hardware re-slicing),
    geometry frozen at the parent's achievable N (slicing is an execution
    mode, not a different accelerator), channel re-referred to the plane
    full-scale.  Cached so jit retraces see one frozen engine identity.
    """
    cfg = engine.dpu
    p = mode.plane_bits
    updates = dict(bits=p, operand_bits=p, dpe_size=cfg.n)
    if cfg.channel is not None:
        updates["channel"] = sliced_channel(cfg.channel, p)
    elif cfg.noise_sigma_lsb > 0.0:
        # Legacy raw-sigma configs: sigma is referred to the product
        # full-scale, which shrinks with the plane width.
        scale = float((2**p - 1) ** 2) / float((2**cfg.bits - 1) ** 2)
        updates["noise_sigma_lsb"] = cfg.noise_sigma_lsb * scale
    return dataclasses.replace(
        engine, dpu=dataclasses.replace(cfg, **updates), slicing=None
    )


@functools.lru_cache(maxsize=None)
def engine_for(
    dpu: DPUConfig,
    backend: str,
    include: Tuple[str, ...] = ("*",),
    exclude: Tuple[str, ...] = ("router",),
    slicing=None,
) -> PhotonicEngine:
    """Cached engine construction (one frozen engine per operating point,
    so ``jit`` retraces don't multiply)."""
    return PhotonicEngine(
        dpu=dpu,
        backend=backend,
        policy=SitePolicy(include, exclude),
        slicing=resolve_slicing(slicing),
    )


# ---------------------------------------------------------------------------
# Shared float-entry forward (the quant / dequant shoulder logic lives once)
# ---------------------------------------------------------------------------
def _finish(acc: jax.Array, e: EpilogueArgs) -> jax.Array:
    """Apply the fused epilogue to a digital int32 accumulator, through the
    jitted twin so the compilation regime matches the Pallas kernel's."""
    return _jit_apply_epilogue(
        acc, e.x_scale, e.w_scale.astype(jnp.float32), e.bias, e.spec
    )


def _stream_gemm(
    eng: "PhotonicEngine",
    site,
    spec: EpilogueSpec,
    x,
    wq,
    w_scale,
    bias,
    fold,
    prng_key,
    *,
    logical_kc=None,
    tiling=None,
):
    """One forward through the fused hot path, shared by the per-call and
    prepacked float entry points (previously duplicated in both impls).

    Quantizes the streaming activation — *deferred* for f32 streams, where
    only the scale is computed here (bitwise `quantize_symmetric`'s) and
    the rounding happens in the Pallas prologue or digitally inside
    ``int_gemm`` — then runs the integer datapath with the epilogue fused.
    """
    lead = x.shape[:-1]
    xr = x.reshape(-1, x.shape[-1])
    if xr.dtype == jnp.float32:
        xs, sx = xr, quant_scale(xr, eng.dpu.operand_bits)
    else:
        # Non-f32 floats divide by the raw-dtype scale inside
        # quantize_symmetric (see its docstring) — not expressible as a
        # deferred f32-scale prologue, so quantize digitally up front.
        xs, sx = quantize_symmetric(xr, eng.dpu.operand_bits)
    cols = logical_kc[1] if logical_kc is not None else wq.shape[1]
    y = eng.int_gemm(
        xs,
        wq,
        site=site,
        fold=fold,
        prng_key=prng_key,
        logical_kc=logical_kc,
        tiling=tiling,
        epilogue=EpilogueArgs(spec, sx, w_scale, bias),
    )
    return y.reshape(*lead, cols).astype(x.dtype)


def _epilogue_bwd(spec: EpilogueSpec, g2, x2, wf, bias):
    """Backward of the epilogue under the engine's STE convention: straight
    through the quantized GEMM (pre-activation recomputed from the float
    operands), exact through bias and activation.  Returns the gradient at
    the GEMM output and the bias cotangent (``None`` when bias is)."""
    if spec.activation is not None:
        pre = x2 @ wf
        if bias is not None:
            pre = pre + bias.astype(jnp.float32)
        _, act_vjp = jax.vjp(ACTIVATIONS[spec.activation], pre)
        (g2,) = act_vjp(g2)
    db = None if bias is None else g2.sum(axis=0).astype(bias.dtype)
    return g2, db


# ---------------------------------------------------------------------------
# STE custom-VJP wrappers (module level: stable identity across jit traces)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _float_matmul(meta, x, w, bias, fold, prng_key):
    return _float_fwd_impl(meta, x, w, bias, fold, prng_key)


def _float_fwd_impl(meta, x, w, bias, fold, prng_key):
    eng, site, spec = meta
    wq, sw = quantize_symmetric(w, eng.dpu.operand_bits, axis=0)
    return _stream_gemm(eng, site, spec, x, wq, sw, bias, fold, prng_key)


def _float_fwd(meta, x, w, bias, fold, prng_key):
    y = _float_fwd_impl(meta, x, w, bias, fold, prng_key)
    return y, (x, w, bias, fold, prng_key)


def _float_bwd(meta, res, g):
    _, _, spec = meta
    x, w, bias, fold, prng_key = res
    g2 = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    g2, db = _epilogue_bwd(spec, g2, x2, wf, bias)
    dx, dw = dense_vjp(x, w, g2.reshape(*x.shape[:-1], g2.shape[-1]))
    return dx, dw, db, key_zero_cotangent(fold), key_zero_cotangent(prng_key)


_float_matmul.defvjp(_float_fwd, _float_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _packed_matmul(meta, x, wq, w_scale, bias, fold, prng_key):
    return _packed_fwd_impl(meta, x, wq, w_scale, bias, fold, prng_key)


def _packed_fwd_impl(meta, x, wq, w_scale, bias, fold, prng_key):
    eng, site, k, c, tiling, spec = meta
    return _stream_gemm(
        eng,
        site,
        spec,
        x,
        wq,
        w_scale,
        bias,
        fold,
        prng_key,
        logical_kc=(k, c),
        tiling=tiling,
    )


def _packed_fwd(meta, x, wq, w_scale, bias, fold, prng_key):
    y = _packed_fwd_impl(meta, x, wq, w_scale, bias, fold, prng_key)
    return y, (x, wq, w_scale, bias, fold, prng_key)


def _packed_bwd(meta, res, g):
    _, site, k, c, tiling, spec = meta
    x, wq, w_scale, bias, fold, prng_key = res
    wf = logical_weight(wq, k, c, tiling).astype(jnp.float32)
    wf = wf * w_scale.astype(jnp.float32)[None, :]
    g2 = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    g2, db = _epilogue_bwd(spec, g2, x2, wf, bias)
    dx, _ = dense_vjp(x, wf, g2.reshape(*x.shape[:-1], g2.shape[-1]))
    # Prepacked weights are frozen serving state: int8 slices get the
    # mandatory float0 cotangent, the scale a plain zero.
    return (
        dx,
        key_zero_cotangent(wq),
        jnp.zeros_like(w_scale),
        db,
        key_zero_cotangent(fold),
        key_zero_cotangent(prng_key),
    )


_packed_matmul.defvjp(_packed_fwd, _packed_bwd)


# ---------------------------------------------------------------------------
# Grouped GEMMs (held experts): digital product and photonic entry points
# ---------------------------------------------------------------------------
def grouped_digital(x, w, tile_expert, n_tiles, tile_r: int) -> jax.Array:
    """``x`` row tile ``i`` times float bank ``w[tile_expert[i]]`` for the
    first ``n_tiles`` tiles, zero rows after them (``lax.ragged_dot``)."""
    e = w.shape[0]
    used = jnp.arange(tile_expert.shape[0]) < n_tiles
    sizes = tile_r * jnp.sum(
        (tile_expert[None, :] == jnp.arange(e)[:, None]) & used[None, :], axis=1
    )
    return jax.lax.ragged_dot(x, w.astype(x.dtype), sizes.astype(jnp.int32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped_float(meta, x, w, tile_expert, n_tiles):
    from repro.photonic.packing import PackedDense

    eng, tile_r = meta
    wq, s = quantize_symmetric(w, eng.dpu.operand_bits, axis=-2)
    packed = PackedDense(wq, jnp.squeeze(s, axis=-2), w.shape[-2], w.shape[-1])
    return eng._grouped_int(x, packed, tile_expert, n_tiles, tile_r)


def _grouped_float_fwd(meta, x, w, tile_expert, n_tiles):
    y = _grouped_float(meta, x, w, tile_expert, n_tiles)
    return y, (x, w, tile_expert, n_tiles)


def _grouped_float_bwd(meta, res, g):
    x, w, tile_expert, n_tiles = res
    _, vjp = jax.vjp(
        lambda a, b: grouped_digital(a, b, tile_expert, n_tiles, meta[1]),
        x.astype(g.dtype),
        w.astype(g.dtype),
    )
    dx, dw = vjp(g)
    return (
        dx.astype(x.dtype),
        dw.astype(w.dtype),
        key_zero_cotangent(tile_expert),
        key_zero_cotangent(n_tiles),
    )


_grouped_float.defvjp(_grouped_float_fwd, _grouped_float_bwd)
