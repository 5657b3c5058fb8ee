"""Compile the Pallas kernels for a described TPU v5e chip, at qwen2-0.5b
widths, without a chip attached.

Interpret mode (how every other test runs the kernels on the CPU) accepts
programs the TPU compiler refuses: int32 matmuls, blocks that are not
lane-aligned, casts Mosaic cannot lower.  These cases lower each kernel
entry point straight to Mosaic for a v5e and fail where the chip's
compiler would.  Nothing runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library at a time,
and every pytest worker imports this file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.dpu import DPUConfig
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.photonic_gemm.kernel import (
    photonic_gemm_fused_pallas,
    photonic_gemm_pallas,
)
from repro.noise import build_channel_model
from repro.photonic.engine import chunk_lanes, pallas_tiling, stored_k


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip can be written to the persistent cache
    # but never read back without one; keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _dpu(org):
    """The paper's operating point (4 bits, 5 GS/s): ideal, or the org's
    analog channel at its calibrated DPE size N."""
    if org is None:
        return DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0)
    ch = build_channel_model(org, bits=4, datarate_gs=5.0)
    return DPUConfig(organization=org, bits=4, datarate_gs=5.0, channel=ch)


def _stages(dpu, k, c):
    """Static kernel arguments as the engine derives them for a (K, C)
    weight (see ``PhotonicEngine.int_gemm``)."""
    n_chunk, tile_k, tile_c = pallas_tiling(dpu, k, c)
    ch = dpu.effective_channel()
    analog = ch is not None and ch.analog
    noisy = analog and ch.detector_sigma_lsb > 0.0
    stages = dict(
        slice_bits=dpu.bits,
        num_slices=dpu.num_slices,
        chunk_lanes=chunk_lanes(n_chunk),
        adc_bits=ch.adc_bits if ch is not None else dpu.adc_bits,
        noise_sigma=ch.detector_sigma_lsb if analog else 0.0,
        filter_alpha=ch.filter_alpha if analog else 0.0,
        intermod_eps=ch.intermod_eps if analog else 0.0,
        crossweight_eps=ch.crossweight_eps if analog else 0.0,
        valid_chunks=-(-k // n_chunk) if noisy else None,
        tile_c=tile_c,
        tile_k=tile_k,
    )
    kp = stored_k(k, (n_chunk, tile_k, tile_c))
    cp = -(-c // tile_c) * tile_c
    return n_chunk, stages, kp, cp


# (organization or None for the ideal channel, expected N, K, C, R, entry,
# activation dtype).  K and C are qwen2-0.5b's: d_model 896, d_ff 4864,
# fused QKV 1152, fused SwiGLU 9728.  R = 8 is a decode batch, 256 a
# prefill chunk.  The bf16 model hands the fused kernel int8 activations.
CASES = [
    (None, 128, 896, 1152, 8, "fused", jnp.int8),
    (None, 128, 896, 9728, 256, "fused", jnp.float32),
    (None, 128, 4864, 896, 8, "int", jnp.int8),
    ("ASMW", 17, 896, 1152, 8, "fused", jnp.int8),
    ("MASW", 21, 4864, 896, 256, "fused", jnp.int8),
    ("SMWA", 42, 896, 9728, 256, "fused", jnp.int8),
    ("SMWA", 42, 4864, 896, 8, "int", jnp.int8),
]


@pytest.mark.parametrize(
    "org,n,k,c,r,entry,xdtype",
    CASES,
    ids=[f"{o or 'ideal'}-{e}-k{k}-c{c}-r{r}" for o, _, k, c, r, e, _ in CASES],
)
def test_photonic_gemm_compiles_for_v5e(one_chip, org, n, k, c, r, entry, xdtype):
    dpu = _dpu(org)
    n_chunk, stages, kp, cp = _stages(dpu, k, c)
    assert n_chunk == n
    tile_r = min(128, r)
    rp = -(-r // tile_r) * tile_r
    noisy = stages["noise_sigma"] > 0.0

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x = sds((rp, kp), xdtype)
    w = sds((kp, cp), jnp.int8)
    seed = sds((1,), jnp.int32) if noisy else None
    if entry == "int":
        compiled = _compile(
            lambda x, w, seed: photonic_gemm_pallas(
                x, w, seed, tile_r=tile_r, **stages
            ),
            x,
            w,
            seed,
        )
    else:
        compiled = _compile(
            lambda x, w, sx, ws, b, seed: photonic_gemm_fused_pallas(
                x, w, sx, ws, b, seed, tile_r=tile_r, activation="silu", **stages
            ),
            x,
            w,
            sds((1,), jnp.float32),
            sds((cp,), jnp.float32),
            sds((cp,), jnp.float32),
            seed,
        )
    # The device shows the kernel under this instruction name, which the
    # benchmark's configuration matches (``device_names.kernel``).
    name = "photonic_gemm_pallas" if entry == "int" else "photonic_gemm_fused_pallas"
    assert re.search(rf"%{name}(\.\d+)? = .*custom-call", compiled.as_text())


def test_flash_attention_compiles_for_v5e(one_chip):
    # qwen2-0.5b attention: 14 query heads over 2 KV heads, head dim 64.
    b, h, kvh, t, hd = 1, 14, 2, 512, 64
    q = jax.ShapeDtypeStruct((b, h, t, hd), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, kvh, t, hd), jnp.bfloat16, sharding=one_chip)
    _compile(
        lambda q, k, v: flash_attention_pallas(
            q,
            k,
            v,
            scale=hd**-0.5,
            causal=True,
            q_offset=0,
            kv_valid=t,
            n_rep=h // kvh,
        ),
        q,
        kv,
        kv,
    )


# DeepSeek-V2-Lite's held experts: 8 banks of the SwiGLU input (2048 x
# 2816) and output (1408 x 2048); row tiles of 8 (a decode step's few rows
# an expert) and 128 (a 2048-token prefill chunk's).
GROUPED = [(2048, 2816, 8), (1408, 2048, 8), (2048, 2816, 128), (1408, 2048, 128)]


@pytest.mark.parametrize("k,c,tile_r", GROUPED, ids=[f"k{k}-c{c}-r{r}" for k, c, r in GROUPED])
def test_grouped_photonic_gemm_compiles_for_v5e(one_chip, k, c, tile_r):
    """The grouped kernel: a dynamic row-tile grid, each tile's bank chosen
    through a prefetched scalar per tile."""
    from repro.kernels.photonic_gemm.kernel import photonic_gemm_grouped_pallas

    n_chunk, stages, kp, cp = _stages(_dpu(None), k, c)
    for key in ("noise_sigma", "valid_chunks"):
        stages.pop(key)
    e, tiles = 8, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = _compile(
        lambda x, w, sx, ws, te, n: photonic_gemm_grouped_pallas(
            x, w, sx, ws, te, n, tile_r=tile_r, **stages
        ),
        sds((tiles * tile_r, kp), jnp.float32),
        sds((e, kp, cp), jnp.int8),
        sds((), jnp.float32),
        sds((e, cp), jnp.float32),
        sds((tiles,), jnp.int32),
        sds((), jnp.int32),
    )
    # The benchmark's expert metric finds the kernel by this name.
    assert re.search(r"%photonic_gemm_grouped_pallas(\.\d+)? = .*custom-call", compiled.as_text())
