"""Public jit'd entry points for the photonic GEMM kernel.

Since the ``repro.photonic`` engine refactor these are *thin
compatibility wrappers*: both functions delegate to
:class:`repro.photonic.engine.PhotonicEngine` with ``site=None`` (no
site folding), which reproduces the pre-engine behavior bit-for-bit —
same backend dispatch, same tiling, same seed derivation.  New code
should use the engine directly (per-site routing, prepacked weights,
threaded PRNG keys).

``photonic_gemm(x, w, cfg)`` — float in/out, quantize → kernel → dequantize.
Backend selection:

* ``"pallas"``   — the Pallas TPU kernel (interpret mode on CPU hosts);
* ``"ref"``      — the pure-jnp oracle (portable, differentiably wrapped);
* ``"exact"``    — plain int GEMM of the quantized operands (the ideal the
                   DPU converges to; useful as an upper bound in tests).

Analog channel semantics (DESIGN.md §8): the backends honour
``cfg.effective_channel()``.  ``"ref"`` is bitwise-equal to
``repro.core.dpu.dpu_int_gemm`` under noise (same stream derivation);
``"pallas"`` injects noise in-kernel from tile-local streams and agrees
with the oracle statistically.  ``"exact"`` ignores the channel by design.
Noisy calls need ``prng_key`` or ``cfg.noise_seed`` (deterministic: same
source => same result for a fixed backend and tiling).
"""

from __future__ import annotations

from typing import Optional

import jax

from repro.core.dpu import DPUConfig
from repro.photonic.engine import engine_for


def photonic_gemm_int(
    xq: jax.Array,
    wq: jax.Array,
    cfg: DPUConfig,
    *,
    backend: str = "pallas",
    tile_r: int = 128,
    tile_c: int = 128,
    prng_key: Optional[jax.Array] = None,
) -> jax.Array:
    """Integer-level DPU GEMM with automatic padding to kernel tiles."""
    eng = engine_for(cfg, backend)
    return eng.int_gemm(
        xq,
        wq,
        prng_key=prng_key,
        tile_r=tile_r,
        tile_c=tile_c,
    )


def photonic_gemm(
    x: jax.Array,
    w: jax.Array,
    cfg: DPUConfig = DPUConfig(),
    backend: str = "pallas",
    prng_key: Optional[jax.Array] = None,
) -> jax.Array:
    """Float GEMM through the photonic DPU. Differentiable via STE."""
    return engine_for(cfg, backend).matmul_float(x, w, prng_key=prng_key)
