"""Fused GEMM shoulder work: the prologue/epilogue math shared by every
photonic backend.

The photonic GEMM proper is integer-in / int32-out, but every call site
wraps it in the same digital shoulder: quantize the streaming activation
on the way in, rescale the int32 accumulator by ``sx * w_scale`` (plus
optional bias and activation) on the way out.  Left as separate XLA ops
those shoulders dominate the dispatch count of a decode step (the
roofline gap ``benchmarks/roofline_report.py`` measures); fused into the
Pallas kernel they ride in the same VMEM residency as the GEMM.

This module is the *single definition* of that shoulder math.  The Pallas
kernel applies :func:`quantize_tile` / :func:`apply_epilogue` per tile,
the jnp oracle and the engine apply them to whole arrays — elementwise
identical ops, which is what makes the fused path bitwise-equal to the
unfused one under an ideal channel (DESIGN.md §14).

Bitwise fine print: the rescale stage is a pure multiply chain, so it is
contraction-free and bitwise-stable across eager/jit/backends — the full
historical engine contract carries over unchanged.  The *bias add* and
*activation* stages contain float add-of-multiply patterns that LLVM
contracts into FMAs inside compiled fusion regions (invisible at HLO
level, immune to ``optimization_barrier``), so their last ulp can differ
between compilation regimes — exactly as the pre-fusion digital
``y + b`` in ``models/common.py::dense`` already did.  The guarantee for
those stages is therefore *one shared op sequence* (this module) and
exact equality within a matching regime; the engine jit-aligns the ref
backend's epilogue with the Pallas kernel so the backends agree bitwise
in every calling context.

Deliberately a leaf: imports ``jax`` only, so it is importable from
``repro.kernels`` (below the engine) and re-exportable from
``repro.photonic`` (above it) without a cycle.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

# Activation table — the *same callables* everywhere (including what the
# digital models applied post-GEMM before fusion existed), so the fused
# epilogue and a digital application are the same op sequence.
ACTIVATIONS = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
}


@dataclasses.dataclass(frozen=True)
class EpilogueSpec:
    """What the fused GEMM epilogue applies to the int32 accumulator.

    The order is fixed (and bitwise-load-bearing): accumulator ``->`` f32
    ``-> * sx -> * w_scale[col] -> + bias -> activation``, exactly the op
    sequence the historical unfused path ran (``out.astype(f32) * sx *
    w_scale[None, :]`` then the digital bias add).  Frozen + hashable so
    it rides through ``jit`` closures and ``custom_vjp`` static metadata.
    """

    bias: bool = False
    activation: Optional[str] = None  # None | "gelu" | "silu"

    def __post_init__(self):
        if self.activation is not None and self.activation not in ACTIVATIONS:
            raise ValueError(
                f"activation={self.activation!r} is not one of "
                f"{(None, *sorted(ACTIVATIONS))}"
            )


class EpilogueArgs(NamedTuple):
    """Runtime operands of one fused-epilogue GEMM call.

    ``x_scale`` is the activation quantization scale (scalar, f32) — when
    the paired activation operand is still *float*, the Pallas backend
    quantizes it in-kernel with this scale (:func:`quantize_tile`); the
    other backends apply :func:`repro.core.dpu.quantize_with_scale`
    digitally, which is the same op sequence.  ``w_scale`` is the
    per-column dequant scale ``(C,)``; ``bias`` is ``(C,)`` or ``None``
    (must agree with ``spec.bias``).
    """

    spec: EpilogueSpec
    x_scale: jax.Array
    w_scale: jax.Array
    bias: Optional[jax.Array] = None


class Epilogue(NamedTuple):
    """The user-facing epilogue request of the unified GEMM surface.

    A static :class:`EpilogueSpec` plus its runtime bias operand — what
    ``engine.matmul`` / ``matmul_float`` / ``models.common.dense`` accept
    as ``epilogue=`` (PR-9 API redesign).  ``spec.bias`` must agree with
    ``bias is not None``; :func:`as_epilogue` enforces that eagerly.
    """

    spec: EpilogueSpec
    bias: Optional[jax.Array] = None


def as_epilogue(epilogue=None) -> "tuple[EpilogueSpec, Optional[jax.Array]]":
    """Normalize an ``epilogue=`` request to ``(spec, bias)`` — the one
    resolution point of the GEMM surface:

    * ``EpilogueSpec(...)`` — bias-free spec (``spec.bias`` must be
      False: the spec alone carries no bias operand);
    * ``Epilogue(spec, bias)`` — spec + bias operand;
    * ``None`` — the no-epilogue spec.

    Anything else, or a spec that disagrees with its bias operand,
    raises ``TypeError`` eagerly.
    """
    if epilogue is None:
        return EpilogueSpec(), None
    if isinstance(epilogue, Epilogue):
        spec, b = epilogue
        if not isinstance(spec, EpilogueSpec):
            raise TypeError(
                f"Epilogue.spec must be an EpilogueSpec, got "
                f"{type(spec).__name__}"
            )
        if spec.bias != (b is not None):
            raise TypeError(
                f"Epilogue spec.bias={spec.bias} disagrees with its bias "
                f"operand ({'present' if b is not None else 'absent'})"
            )
        return spec, b
    if isinstance(epilogue, EpilogueSpec):
        if epilogue.bias:
            raise TypeError(
                "EpilogueSpec(bias=True) carries no bias operand; pass "
                "Epilogue(spec, bias) instead"
            )
        return epilogue, None
    raise TypeError(
        f"epilogue must be an EpilogueSpec or Epilogue, got "
        f"{type(epilogue).__name__}"
    )


def quantize_tile(x: jax.Array, scale: jax.Array, qmax: float) -> jax.Array:
    """The in-kernel image of ``quantize_symmetric``'s rounding step.

    ``scale`` is traced (never a constant), so the division is the blessed
    second half of the reciprocal-multiply idiom (RPR005) and rounds
    identically eager vs compiled.  Elementwise => applying it per Pallas
    tile equals applying it to the whole array; zero padding quantizes to
    zero, so padded tiles stay hash- and value-neutral.
    """
    return jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)


def apply_epilogue(
    acc: jax.Array,  # (..., C) int32 accumulator (or a (TR, TC) tile of it)
    x_scale: jax.Array,  # scalar f32
    w_scale: jax.Array,  # (C,) or (1, TC) f32 — broadcasts over rows
    bias: Optional[jax.Array],  # (C,) / (1, TC) f32, or None
    spec: EpilogueSpec,
) -> jax.Array:
    """int32 accumulator -> rescale -> optional bias -> optional activation.

    Left-associated multiply order matches the historical unfused dequant
    (``acc.astype(f32) * sx * w_scale``) bit-for-bit; bias and activation
    run in f32 before the caller's output cast.  The rescale stage is
    contraction-free (multiplies only); the bias/activation stages are
    subject to FMA contraction, so their bitwise guarantee is per
    compilation regime (see the module docstring).
    """
    y = acc.astype(jnp.float32) * x_scale * w_scale
    return apply_bias_activation(y, bias, spec.activation)


def apply_bias_activation(
    y: jax.Array, bias: Optional[jax.Array], activation: Optional[str]
) -> jax.Array:
    """The bias/activation tail of the epilogue alone, for callers that
    already hold the rescaled float output (the shard-map bodies rescale
    inside the collective; same ops as the fused kernel's tail)."""
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if activation is not None:
        y = ACTIVATIONS[activation](y)
    return y
