"""Pallas TPU kernel for the photonic (bit-sliced, psum-chunked) GEMM.

TPU-native adaptation of the paper's DPU datapath (DESIGN.md §3):

* the DPE size ``N`` becomes the psum chunk along the contraction dim — each
  chunk's int32 partial sum models one analog summation + ADC event and can
  be saturated to ``adc_bits`` like the real converter;
* the fan-out ``M`` becomes the output-column tile — ``M`` parallel DPEs map
  onto MXU output columns;
* operand bit-slices (``ceil(operand_bits/B)`` per operand) are extracted
  *inside* the kernel from int8 residents of VMEM, so HBM traffic stays int8
  (one read per operand) while the MXU consumes one slice-pair per pass —
  mirroring the temporal passes of the photonic DPU.

Analog channel stages (DESIGN.md §8) run *inside* the kernel so the noisy
path needs no extra HBM traffic: inter-modulation / cross-weight crosstalk
as one extra chunk-local MXU pass against neighbor-shifted operands, filter
truncation as a psum scale, detector noise from a counter-based gaussian
generator (`repro.noise.stages`) seeded by a scalar SMEM input — bitwise
deterministic for a fixed seed + tiling, statistically matching the jnp
oracle (which draws from flat, untiled streams).

Blocking: grid ``(R/TR, C/TC, K/TK)`` with the K axis innermost so the output
tile stays resident in VMEM and accumulates across K-tiles (standard Pallas
matmul accumulation).  Every block is lane-aligned: ``TK`` is a multiple
of ``chunk_lanes``, itself a multiple of 128.  When ADC/analog fidelity is
off chunking is numerically irrelevant and the tile is one MXU pass; when
it is on, each DPE chunk of ``N`` operands sits zero-padded in its own
``chunk_lanes``-wide slot (``repro.photonic.engine.to_chunk_slots``), so
a chunk never starts off a lane boundary and zero padding adds nothing to
its integer psum.  All products run on the MXU as int8 x int8 -> int32.

Two entry points share the datapath:

* :func:`photonic_gemm_pallas` — the integer core: int8 in, int32 out.
* :func:`photonic_gemm_fused_pallas` — the fused hot path (DESIGN.md §14):
  optional in-kernel activation-quantization prologue (f32 tile + SMEM
  scale -> int, :func:`repro.kernels.photonic_gemm.epilogue.quantize_tile`)
  and the fused epilogue (int32 VMEM scratch accumulator -> ``sx *
  w_scale`` rescale -> optional bias -> optional activation) applied at
  the last K step, so neither the int32 accumulator nor the quantized
  activation ever round-trips through HBM.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.photonic_gemm.epilogue import (
    EpilogueSpec,
    apply_epilogue,
    quantize_tile,
)
from repro.noise.stages import fold_seed, gaussian_from_counter, neighbor_sum


_MM = (((1,), (0,)), ((), ()))


def _int8_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """One slice pair's psum on the MXU: int8 x int8 -> int32, exact.

    Slices are signed magnitudes of int8 operands, so they fit int8; the
    TPU's matrix unit multiplies int8 natively and refuses int32 operands.
    """
    return jax.lax.dot_general(
        a.astype(jnp.int8), b.astype(jnp.int8), _MM, preferred_element_type=jnp.int32
    )


def _f32_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        a.astype(jnp.float32),
        b.astype(jnp.float32),
        _MM,
        preferred_element_type=jnp.float32,
    )


def _accumulate(
    x: jax.Array,  # (TR, TK) int32
    w: jax.Array,  # (TK, TC) int32
    tile_seed: Optional[jax.Array],
    *,
    slice_bits: int,
    num_slices: int,
    chunk_lanes: int,
    adc_bits: Optional[int],
    noise_sigma: float,
    filter_alpha: float,
    intermod_eps: float,
    crossweight_eps: float,
    valid_chunks: Optional[int],
) -> jax.Array:
    """One K-tile's int32 contribution through the DPU datapath.

    The single definition of the bit-sliced / psum-chunked / analog-stage
    accumulation, shared by the integer and fused kernels so the two can
    never drift.  The K-tile holds ``TK // chunk_lanes`` chunk slots; each
    slot starts with one DPE chunk's ``N`` operands and is zero beyond
    them (the layout of :func:`repro.photonic.engine.to_chunk_slots`).
    """
    analog = (
        noise_sigma > 0.0
        or filter_alpha > 0.0
        or intermod_eps > 0.0
        or crossweight_eps > 0.0
    )
    tr, tk = x.shape
    _, tc = w.shape
    chunks = tk // chunk_lanes

    sgn_x, mag_x = jnp.sign(x), jnp.abs(x)
    sgn_w, mag_w = jnp.sign(w), jnp.abs(w)
    mask = (1 << slice_bits) - 1

    acc = jnp.zeros((tr, tc), jnp.int32)
    for si in range(num_slices):
        xs = sgn_x * ((mag_x >> (slice_bits * si)) & mask)
        for ti in range(num_slices):
            ws = sgn_w * ((mag_w >> (slice_bits * ti)) & mask)
            shift = slice_bits * (si + ti)
            if not analog and adc_bits is None:
                # Ideal ADC: chunk boundaries are numerically irrelevant —
                # one MXU pass over the whole K-tile.
                acc = acc + (_int8_dot(xs, ws) << shift)
                continue
            # DPU-faithful: run each chunk through the analog signal chain
            # (crosstalk -> filter -> noise -> ADC).
            lim = 2 ** (adc_bits - 1) - 1 if adc_bits is not None else None
            for g in range(chunks):
                sl = slice(g * chunk_lanes, (g + 1) * chunk_lanes)
                x_c, w_c = xs[:, sl], ws[sl, :]
                psum = _int8_dot(x_c, w_c)
                if analog:
                    a = psum.astype(jnp.float32)
                    if intermod_eps > 0.0 or crossweight_eps > 0.0:
                        # Inter-modulation (x leaks to adjacent lanes) and
                        # cross-weight (w to adjacent rows) crosstalk are
                        # one integer: nb(x) @ w == x @ nb(w), since the
                        # neighbour relation is symmetric and the slot's
                        # padding lanes are zero.  One exact contraction
                        # serves both terms.
                        t = _f32_dot(neighbor_sum(x_c, axis=1), w_c)
                        if intermod_eps > 0.0:
                            a = a + intermod_eps * t
                        if crossweight_eps > 0.0:
                            a = a + crossweight_eps * t
                    if filter_alpha > 0.0:
                        a = a * (1.0 - filter_alpha)
                    if noise_sigma > 0.0:
                        z = gaussian_from_counter(
                            fold_seed(tile_seed, si * num_slices + ti, g),
                            a.shape,
                        )
                        if valid_chunks is not None:
                            # Chunks entirely inside K-padding carry no
                            # data and fire no optical pass — mask their
                            # noise so variance matches the oracle.
                            gchunk = pl.program_id(2) * chunks + g
                            z = z * (gchunk < valid_chunks).astype(jnp.float32)
                        a = a + noise_sigma * z
                    psum = jnp.round(a).astype(jnp.int32)
                if lim is not None:
                    psum = jnp.clip(psum, -lim, lim)
                acc = acc + (psum << shift)
    return acc


def _kernel(
    *refs,
    slice_bits: int,
    num_slices: int,
    chunk_lanes: int,
    adc_bits: Optional[int],
    noise_sigma: float,
    filter_alpha: float,
    intermod_eps: float,
    crossweight_eps: float,
    valid_chunks: Optional[int],
):
    analog = (
        noise_sigma > 0.0
        or filter_alpha > 0.0
        or intermod_eps > 0.0
        or crossweight_eps > 0.0
    )
    if analog:
        seed_ref, x_ref, w_ref, out_ref = refs
    else:
        x_ref, w_ref, out_ref = refs

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    tile_seed = None
    if analog:
        # Per-tile noise stream: seed x grid position (bitwise deterministic
        # for fixed seed and tiling; independent across tiles).
        tile_seed = fold_seed(
            seed_ref[0].astype(jnp.uint32),
            pl.program_id(0),
            pl.program_id(1),
            pl.program_id(2),
        )

    out_ref[...] += _accumulate(
        x_ref[...].astype(jnp.int32),
        w_ref[...].astype(jnp.int32),
        tile_seed,
        slice_bits=slice_bits,
        num_slices=num_slices,
        chunk_lanes=chunk_lanes,
        adc_bits=adc_bits,
        noise_sigma=noise_sigma,
        filter_alpha=filter_alpha,
        intermod_eps=intermod_eps,
        crossweight_eps=crossweight_eps,
        valid_chunks=valid_chunks,
    )


def _fused_kernel(
    *refs,
    operand_bits: int,
    fuse_quant: bool,
    has_bias: bool,
    activation: Optional[str],
    out_dtype,
    slice_bits: int,
    num_slices: int,
    chunk_lanes: int,
    adc_bits: Optional[int],
    noise_sigma: float,
    filter_alpha: float,
    intermod_eps: float,
    crossweight_eps: float,
    valid_chunks: Optional[int],
):
    analog = (
        noise_sigma > 0.0
        or filter_alpha > 0.0
        or intermod_eps > 0.0
        or crossweight_eps > 0.0
    )
    refs = list(refs)
    seed_ref = refs.pop(0) if analog else None
    xs_ref = refs.pop(0)  # SMEM (1,) f32 activation scale (always present)
    x_ref, w_ref, wscale_ref = refs[0], refs[1], refs[2]
    bias_ref = refs[3] if has_bias else None
    out_ref = refs[4] if has_bias else refs[3]
    acc_ref = refs[-1]  # VMEM (TR, TC) int32 scratch accumulator

    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if fuse_quant:
        # In-kernel prologue: the rounding half of quantize_symmetric
        # against the SMEM scale (elementwise, so per-tile == whole-array;
        # zero padding quantizes to zero).
        qmax = float(2 ** (operand_bits - 1) - 1)
        x = quantize_tile(x_ref[...], xs_ref[0], qmax)
    else:
        x = x_ref[...].astype(jnp.int32)

    tile_seed = None
    if analog:
        tile_seed = fold_seed(
            seed_ref[0].astype(jnp.uint32),
            pl.program_id(0),
            pl.program_id(1),
            pl.program_id(2),
        )

    acc_ref[...] += _accumulate(
        x,
        w_ref[...].astype(jnp.int32),
        tile_seed,
        slice_bits=slice_bits,
        num_slices=num_slices,
        chunk_lanes=chunk_lanes,
        adc_bits=adc_bits,
        noise_sigma=noise_sigma,
        filter_alpha=filter_alpha,
        intermod_eps=intermod_eps,
        crossweight_eps=crossweight_eps,
        valid_chunks=valid_chunks,
    )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _epilogue():
        spec = EpilogueSpec(bias=has_bias, activation=activation)
        y = apply_epilogue(
            acc_ref[...],
            xs_ref[0],
            wscale_ref[...],  # (1, TC), broadcasts over rows
            bias_ref[...] if has_bias else None,
            spec,
        )
        out_ref[...] = y.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "slice_bits",
        "num_slices",
        "chunk_lanes",
        "adc_bits",
        "noise_sigma",
        "filter_alpha",
        "intermod_eps",
        "crossweight_eps",
        "valid_chunks",
        "tile_r",
        "tile_c",
        "tile_k",
        "interpret",
    ),
)
def photonic_gemm_pallas(
    xq: jax.Array,  # (R, K) int8, R % tile_r == 0, K % tile_k == 0
    wq: jax.Array,  # (K, C) int8, C % tile_c == 0
    seed: Optional[jax.Array] = None,  # int32 scalar (1,), required if noisy
    *,
    slice_bits: int = 4,
    num_slices: int = 2,
    chunk_lanes: int = 128,
    adc_bits: Optional[int] = None,
    noise_sigma: float = 0.0,
    filter_alpha: float = 0.0,
    intermod_eps: float = 0.0,
    crossweight_eps: float = 0.0,
    valid_chunks: Optional[int] = None,
    tile_r: int = 128,
    tile_c: int = 128,
    tile_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    r, k = xq.shape
    _, c = wq.shape
    assert r % tile_r == 0 and c % tile_c == 0 and k % tile_k == 0, (
        xq.shape,
        wq.shape,
        (tile_r, tile_c, tile_k),
    )
    assert tile_k % chunk_lanes == 0 and chunk_lanes % 128 == 0, (
        tile_k,
        chunk_lanes,
    )
    analog = (
        noise_sigma > 0.0
        or filter_alpha > 0.0
        or intermod_eps > 0.0
        or crossweight_eps > 0.0
    )
    if noise_sigma > 0.0 and seed is None:
        raise ValueError("noise_sigma > 0 requires a seed")

    grid = (r // tile_r, c // tile_c, k // tile_k)
    kernel = functools.partial(
        _kernel,
        slice_bits=slice_bits,
        num_slices=num_slices,
        chunk_lanes=chunk_lanes,
        adc_bits=adc_bits,
        noise_sigma=noise_sigma,
        filter_alpha=filter_alpha,
        intermod_eps=intermod_eps,
        crossweight_eps=crossweight_eps,
        valid_chunks=valid_chunks,
    )
    in_specs = [
        pl.BlockSpec((tile_r, tile_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((tile_k, tile_c), lambda i, j, kk: (kk, j)),
    ]
    args = [xq, wq]
    if analog:
        if seed is None:
            seed = jnp.zeros((1,), jnp.int32)
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, jnp.asarray(seed, jnp.int32).reshape(1))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_r, tile_c), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="photonic_gemm_pallas",
    )(*args)


@functools.partial(
    jax.jit,
    static_argnames=(
        "operand_bits",
        "activation",
        "out_dtype",
        "slice_bits",
        "num_slices",
        "chunk_lanes",
        "adc_bits",
        "noise_sigma",
        "filter_alpha",
        "intermod_eps",
        "crossweight_eps",
        "valid_chunks",
        "tile_r",
        "tile_c",
        "tile_k",
        "interpret",
    ),
)
def photonic_gemm_fused_pallas(
    x: jax.Array,  # (R, K) f32 activations, or pre-quantized int8
    wq: jax.Array,  # (K, C) int8, C % tile_c == 0
    x_scale: jax.Array,  # () or (1,) f32 — activation quantization scale
    w_scale: jax.Array,  # (C,) f32 per-column dequant scale
    bias: Optional[jax.Array] = None,  # (C,) f32
    seed: Optional[jax.Array] = None,  # int32 scalar (1,), required if noisy
    *,
    operand_bits: int = 8,
    activation: Optional[str] = None,
    out_dtype=jnp.float32,
    slice_bits: int = 4,
    num_slices: int = 2,
    chunk_lanes: int = 128,
    adc_bits: Optional[int] = None,
    noise_sigma: float = 0.0,
    filter_alpha: float = 0.0,
    intermod_eps: float = 0.0,
    crossweight_eps: float = 0.0,
    valid_chunks: Optional[int] = None,
    tile_r: int = 128,
    tile_c: int = 128,
    tile_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """The fused hot path: [quantize] -> integer GEMM -> epilogue, one kernel.

    When ``x`` is floating point it is quantized in-kernel against the
    SMEM-resident ``x_scale`` (the prologue); pre-quantized int operands
    skip the prologue (the noisy channel pre-quantizes digitally because
    its seed derivation hashes the integer operand).  The int32
    accumulator lives in a VMEM scratch tile across K steps; at the last
    K step the epilogue (rescale / bias / activation) writes the f32
    output — the int32 intermediate never reaches HBM.
    """
    r, k = x.shape
    _, c = wq.shape
    assert r % tile_r == 0 and c % tile_c == 0 and k % tile_k == 0, (
        x.shape,
        wq.shape,
        (tile_r, tile_c, tile_k),
    )
    assert tile_k % chunk_lanes == 0 and chunk_lanes % 128 == 0, (
        tile_k,
        chunk_lanes,
    )
    analog = (
        noise_sigma > 0.0
        or filter_alpha > 0.0
        or intermod_eps > 0.0
        or crossweight_eps > 0.0
    )
    if noise_sigma > 0.0 and seed is None:
        raise ValueError("noise_sigma > 0 requires a seed")
    fuse_quant = jnp.issubdtype(x.dtype, jnp.floating)
    has_bias = bias is not None

    grid = (r // tile_r, c // tile_c, k // tile_k)
    kernel = functools.partial(
        _fused_kernel,
        operand_bits=operand_bits,
        fuse_quant=fuse_quant,
        has_bias=has_bias,
        activation=activation,
        out_dtype=out_dtype,
        slice_bits=slice_bits,
        num_slices=num_slices,
        chunk_lanes=chunk_lanes,
        adc_bits=adc_bits,
        noise_sigma=noise_sigma,
        filter_alpha=filter_alpha,
        intermod_eps=intermod_eps,
        crossweight_eps=crossweight_eps,
        valid_chunks=valid_chunks,
    )
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # x_scale
        pl.BlockSpec((tile_r, tile_k), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((tile_k, tile_c), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((1, tile_c), lambda i, j, kk: (0, j)),  # w_scale
    ]
    args = [
        jnp.asarray(x_scale, jnp.float32).reshape(1),
        x,
        wq,
        w_scale.astype(jnp.float32).reshape(1, c),
    ]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, tile_c), lambda i, j, kk: (0, j)))
        args.append(bias.astype(jnp.float32).reshape(1, c))
    if analog:
        if seed is None:
            seed = jnp.zeros((1,), jnp.int32)
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, jnp.asarray(seed, jnp.int32).reshape(1))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_r, tile_c), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), out_dtype),
        scratch_shapes=[pltpu.VMEM((tile_r, tile_c), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="photonic_gemm_fused_pallas",
    )(*args)


def _grouped_kernel(tile_expert_ref, *refs, analog: bool, **stages):
    """One row tile of the grouped GEMM: the fused kernel's body against
    the weight bank of the tile's expert (``tile_expert_ref``, prefetched
    into SMEM, is read only by the index maps)."""
    del tile_expert_ref
    refs = list(refs)
    seed_ref = refs.pop(0) if analog else None
    xs_ref, x_ref, w_ref, wscale_ref, out_ref, acc_ref = refs
    operand_bits = stages.pop("operand_bits")
    out_dtype = stages.pop("out_dtype")
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qmax = float(2 ** (operand_bits - 1) - 1)
    tile_seed = None
    if analog:
        tile_seed = fold_seed(
            seed_ref[0].astype(jnp.uint32),
            pl.program_id(0),
            pl.program_id(1),
            pl.program_id(2),
        )
    acc_ref[...] += _accumulate(
        quantize_tile(x_ref[...], xs_ref[0], qmax),
        w_ref[...].astype(jnp.int32),
        tile_seed,
        **stages,
    )

    @pl.when(kk == pl.num_programs(2) - 1)
    def _epilogue():
        y = apply_epilogue(acc_ref[...], xs_ref[0], wscale_ref[...], None, EpilogueSpec())
        out_ref[...] = y.astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "operand_bits",
        "out_dtype",
        "slice_bits",
        "num_slices",
        "chunk_lanes",
        "adc_bits",
        "noise_sigma",
        "filter_alpha",
        "intermod_eps",
        "crossweight_eps",
        "valid_chunks",
        "tile_r",
        "tile_c",
        "tile_k",
        "interpret",
    ),
)
def photonic_gemm_grouped_pallas(
    x: jax.Array,  # (M, K) f32 rows, grouped by expert in tile_r-row tiles
    wq: jax.Array,  # (E, K, C) int8 banks, C % tile_c == 0
    x_scale: jax.Array,  # () or (1,) f32 activation quantization scale
    w_scale: jax.Array,  # (E, C) f32 per-expert per-column dequant scale
    tile_expert: jax.Array,  # (M // tile_r,) int32: the expert of each row tile
    n_tiles: jax.Array,  # () int32: row tiles in use, 1 .. M // tile_r
    seed: Optional[jax.Array] = None,  # int32 (1,), required if noisy
    *,
    operand_bits: int = 8,
    out_dtype=jnp.float32,
    slice_bits: int = 4,
    num_slices: int = 2,
    chunk_lanes: int = 128,
    adc_bits: Optional[int] = None,
    noise_sigma: float = 0.0,
    filter_alpha: float = 0.0,
    intermod_eps: float = 0.0,
    crossweight_eps: float = 0.0,
    valid_chunks: Optional[int] = None,
    tile_r: int = 128,
    tile_c: int = 128,
    tile_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """The fused hot path over many weight banks in one call (a grouped, or
    ragged, GEMM): row tile ``i`` of ``x`` is quantized in-kernel, runs the
    DPU datapath against bank ``tile_expert[i]`` and is rescaled by that
    bank's column scales.  Only the first ``n_tiles`` row tiles are
    computed: the grid's row extent is ``n_tiles`` itself, so tiles past it
    cost nothing, and their output rows are left unwritten."""
    m, k = x.shape
    e, _, c = wq.shape
    assert m % tile_r == 0 and c % tile_c == 0 and k % tile_k == 0, (
        x.shape,
        wq.shape,
        (tile_r, tile_c, tile_k),
    )
    assert tile_k % chunk_lanes == 0 and chunk_lanes % 128 == 0, (tile_k, chunk_lanes)
    analog = (
        noise_sigma > 0.0
        or filter_alpha > 0.0
        or intermod_eps > 0.0
        or crossweight_eps > 0.0
    )
    if noise_sigma > 0.0 and seed is None:
        raise ValueError("noise_sigma > 0 requires a seed")
    kernel = functools.partial(
        _grouped_kernel,
        analog=analog,
        operand_bits=operand_bits,
        out_dtype=out_dtype,
        slice_bits=slice_bits,
        num_slices=num_slices,
        chunk_lanes=chunk_lanes,
        adc_bits=adc_bits,
        noise_sigma=noise_sigma,
        filter_alpha=filter_alpha,
        intermod_eps=intermod_eps,
        crossweight_eps=crossweight_eps,
        valid_chunks=valid_chunks,
    )
    in_specs = [
        pl.BlockSpec(memory_space=pltpu.SMEM),  # x_scale
        pl.BlockSpec((tile_r, tile_k), lambda i, j, kk, te: (i, kk)),
        pl.BlockSpec((None, tile_k, tile_c), lambda i, j, kk, te: (te[i], kk, j)),
        pl.BlockSpec((None, 1, tile_c), lambda i, j, kk, te: (te[i], 0, j)),
    ]
    args = [
        jnp.asarray(x_scale, jnp.float32).reshape(1),
        x,
        wq,
        w_scale.astype(jnp.float32).reshape(e, 1, c),
    ]
    if analog:
        if seed is None:
            seed = jnp.zeros((1,), jnp.int32)
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, jnp.asarray(seed, jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(jnp.asarray(n_tiles, jnp.int32), c // tile_c, k // tile_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile_r, tile_c), lambda i, j, kk, te: (i, j)),
        scratch_shapes=[pltpu.VMEM((tile_r, tile_c), jnp.int32)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, c), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "parallel", "arbitrary")
        ),
        interpret=interpret,
        name="photonic_gemm_grouped_pallas",
    )(tile_expert.astype(jnp.int32), *args)
