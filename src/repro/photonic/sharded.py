"""Tensor-parallel photonic execution: shard-local channels over the mesh.

The paper's fifth signal manipulation — **Summation** — accumulates each
DPE's analog partial dot-product in the electrical/digital domain.  That
is exactly the semantics of sharding a GEMM's reduction axis K over a
device mesh: every shard evaluates its local fan-in and the partials meet
in one digital ``psum``.  The correspondence is physical, not just
notational — Table II crosstalk and the Table III loss chain scale with
the *per-DPE* fan-in, so a K-sharded GEMM must evaluate its
:class:`~repro.noise.ChannelModel` at ``N_local = min(N, K/shards)``
rather than the global ``N`` (the circuit-level N-partitioning argument
of arXiv:2407.06134, lifted to the system-sharding level).  Sharding
*helps* the analog channel: fewer rings per waveguide, shorter
propagation, more delivered power per psum.  The rebuild goes through
:func:`repro.noise.shard_local_channel`, whose builder provenance records
the canonical organization name — so sharding works identically for the
paper-studied orders and any :class:`repro.orgs.OrgSpec` ordering.

Execution modes (both dispatch from ``models.common.dense`` via
:func:`maybe_tp_matmul`):

* **GSPMD mode** — :func:`tensor_parallel` ``(mesh, axis)``: each routed
  GEMM wraps itself in a ``shard_map`` over the tensor-parallel axis.
  Activations shard on K, prepacked int8 banks shard on their fan-in
  rows (``repro.photonic.packing.prepack_params(mesh=...)``), per-column
  scales replicate.  Quantization scales are ``pmax``-reduced to the
  global abs-max, so every shard quantizes bitwise-identically to the
  unsharded path.
* **manual mode** — :func:`manual_tp` ``(axis)``: for call sites already
  inside a ``shard_map`` body (``runtime/dp_step.py``), where a nested
  ``shard_map`` is illegal.  Operands arrive replicated; each device
  slices its K block by ``axis_index`` and the same collective core runs.

Contracts (DESIGN.md §10, ``tests/test_sharded_engine.py``):

* ideal channel ⇒ K-sharded output is **bitwise equal** to the unsharded
  engine on every backend (integer psum is associative; max-based scales
  are reduction-order exact);
* each shard's channel model equals ``build_channel_model`` evaluated at
  its ``N_local`` (:func:`repro.noise.shard_local_channel`);
* noisy calls stay deterministic per ``noise_seed``/``prng_key`` and
  decorrelate across shards — the (site, layer, shard) triple is folded
  into the noise stream (:data:`repro.photonic.engine.SHARD_STREAM_TAG`).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import PartitionSpec as P
from repro.core.dpu import dense_vjp, quantize_symmetric
from repro.kernels.photonic_gemm.epilogue import apply_epilogue, as_epilogue
from repro.noise.stages import key_zero_cotangent
from repro.photonic.engine import PhotonicEngine, _epilogue_bwd
from repro.photonic.packing import PackedDense


# ---------------------------------------------------------------------------
# Shard-local operating points
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def shard_local_engine(engine: PhotonicEngine, k_local: int) -> PhotonicEngine:
    """The engine one K-shard executes: same backend/policy, DPU rebuilt
    at the shard-local fan-in (:meth:`repro.core.dpu.DPUConfig.shard_local`
    — the channel model re-derived at ``N_local``)."""
    return dataclasses.replace(engine, dpu=engine.dpu.shard_local(k_local))


# ---------------------------------------------------------------------------
# Tensor-parallel context
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TPContext:
    """Active tensor-parallel scope: a mesh axis to K-shard over.

    ``mesh=None`` is *manual* mode — the caller is already inside a
    ``shard_map`` body and the axis name is bound there.
    """

    axis: str
    mesh: Optional[compat.Mesh] = None

    @property
    def manual(self) -> bool:
        return self.mesh is None

    def size(self) -> int:
        if self.mesh is not None:
            return int(self.mesh.shape[self.axis])
        return int(compat.axis_size(self.axis))


class _Ctx(threading.local):
    current: Optional[TPContext] = None


_CTX = _Ctx()


def current_tp() -> Optional[TPContext]:
    """The active TP context, or ``None`` (single-device execution)."""
    return _CTX.current


@contextlib.contextmanager
def tensor_parallel(mesh: compat.Mesh, axis: str = "model"):
    """Run policy-routed ``dense()`` GEMMs K-sharded over ``mesh[axis]``.

    GSPMD mode: every routed GEMM wraps its own ``shard_map`` over
    ``axis`` (legal under an enclosing ``jit``; illegal inside another
    ``shard_map`` — use :func:`manual_tp` there).
    """
    if axis not in mesh.shape:
        raise ValueError(
            f"mesh has no axis {axis!r}; axes are {tuple(mesh.axis_names)}"
        )
    prev = _CTX.current
    _CTX.current = TPContext(axis=axis, mesh=mesh)
    try:
        yield
    finally:
        _CTX.current = prev


@contextlib.contextmanager
def manual_tp(axis: str = "model"):
    """TP for call sites already inside a ``shard_map`` body
    (``runtime/dp_step.py``): operands arrive replicated, each device
    slices its K block by ``axis_index`` and partials meet in ``psum``."""
    prev = _CTX.current
    _CTX.current = TPContext(axis=axis, mesh=None)
    try:
        yield
    finally:
        _CTX.current = prev


# ---------------------------------------------------------------------------
# The collective core (runs with mesh axes bound, i.e. inside shard_map)
# ---------------------------------------------------------------------------
def psum_int_gemm(
    engine: PhotonicEngine,
    xq: jax.Array,  # (R, K_local) int — this shard's activation block
    wq: jax.Array,  # (K_local, C) int, or the shard's padded bank
    *,
    axis: str,
    site: Optional[str] = None,
    fold=None,
    prng_key: Optional[jax.Array] = None,
    logical_kc=None,
    tiling=None,
) -> jax.Array:
    """Shard-local integer GEMM + digital-domain ``psum`` — Summation.

    Must run with ``axis`` bound (inside ``shard_map``).  The shard
    executes ``engine`` rebuilt at its local fan-in, folds its mesh index
    into the noise stream (shards decorrelate), and the int32 partials
    accumulate exactly — bitwise equal to the unsharded engine whenever
    the channel is ideal.
    """
    k_local = int((logical_kc or wq.shape[-2:])[0])
    local = shard_local_engine(engine, k_local)
    shard = jax.lax.axis_index(axis)
    out = local.int_gemm(
        xq,
        wq,
        site=site,
        fold=fold,
        shard=shard,
        prng_key=prng_key,
        logical_kc=logical_kc,
        tiling=tiling,
    )
    return jax.lax.psum(out, axis)


# ---------------------------------------------------------------------------
# shard_map plumbing: optional fold/key operands need static arity
# ---------------------------------------------------------------------------
def _row_sharding(mesh, axis, rows):
    """How the non-contraction (row/batch) dim shards in GSPMD mode.

    Returns the mesh axes to spread rows over — every axis except the TP
    axis — so a DP+TP mesh keeps its data parallelism instead of
    replicating the batch into every TP group; ``None`` (replicate) when
    the row count does not divide, mirroring ``runtime/sharding.py``'s
    divisibility fallback.
    """
    dp_axes = tuple(a for a in mesh.axis_names if a != axis)
    if not dp_axes:
        return None
    dp_size = 1
    for a in dp_axes:
        dp_size *= int(mesh.shape[a])
    if dp_size == 1 or rows % dp_size:
        return None
    return dp_axes


def _run_shard_map(
    mesh, axis, body, args, specs, fold, prng_key, out_spec=P(), bias=None
):
    """Invoke ``body(*main, bias=..., fold=..., prng_key=...)`` under
    shard_map.

    ``bias``/``fold``/``prng_key`` may be ``None`` (absent), an array, or
    (for ``prng_key``) a typed PRNG key; they ride as replicated trailing
    operands so the body signature stays static per presence combination.
    """
    args = list(args)
    specs = list(specs)
    has_bias = bias is not None
    if has_bias:
        args.append(bias)
        specs.append(P())
    has_fold = fold is not None
    if has_fold:
        args.append(jnp.asarray(fold, jnp.int32))
        specs.append(P())
    has_key = prng_key is not None
    typed_key = False
    if has_key:
        if jnp.issubdtype(prng_key.dtype, jax.dtypes.prng_key):
            args.append(jax.random.key_data(prng_key))
            typed_key = True
        else:
            args.append(prng_key)
        specs.append(P())
    n_main = len(args) - int(has_bias) - int(has_fold) - int(has_key)

    def wrapped(*vals):
        main = vals[:n_main]
        i = n_main
        b = vals[i] if has_bias else None
        i += int(has_bias)
        f = vals[i] if has_fold else None
        i += int(has_fold)
        key = vals[i] if has_key else None
        if key is not None and typed_key:
            key = jax.random.wrap_key_data(key)
        return body(*main, bias=b, fold=f, prng_key=key)

    fn = compat.shard_map(
        wrapped,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=out_spec,
        check_vma=False,
    )
    return fn(*args)


# ---------------------------------------------------------------------------
# STE float wrappers (module level: stable identity across jit traces)
# ---------------------------------------------------------------------------
def _float_fwd_impl(meta, x, w, bias, fold, prng_key):
    eng, site, axis, mesh, spec = meta
    bits = eng.dpu.operand_bits
    lead = x.shape[:-1]
    k, c = w.shape
    xr = x.reshape(-1, k)
    if mesh is None:
        # Manual mode: operands are replicated inside the enclosing
        # shard_map.  Quantize at the (locally visible) global abs-max,
        # then slice this device's K block — bitwise the scales the
        # unsharded path derives.
        size = int(compat.axis_size(axis))
        k_local = k // size
        xq, sx = quantize_symmetric(xr, bits)
        wq, sw = quantize_symmetric(w, bits, axis=0)
        idx = jax.lax.axis_index(axis)
        xl = jax.lax.dynamic_slice_in_dim(xq, idx * k_local, k_local, axis=1)
        wl = jax.lax.dynamic_slice_in_dim(wq, idx * k_local, k_local, axis=0)
        out = psum_int_gemm(
            eng, xl, wl, axis=axis, site=site, fold=fold, prng_key=prng_key
        )
        y = apply_epilogue(out, sx, sw.astype(jnp.float32), bias, spec)
    else:
        rows = _row_sharding(mesh, axis, xr.shape[0])
        x_axes = (axis,) if rows is None else rows + (axis,)

        def body(xl, wl, *, bias, fold, prng_key):
            # pmax-reduced global abs-max => shard-local quantization is
            # bitwise identical to the unsharded quantization (max is
            # exact under any reduction order).
            ax = jax.lax.pmax(jnp.max(jnp.abs(xl)), x_axes)
            xq, sx = quantize_symmetric(xl, bits, amax=ax)
            aw = jax.lax.pmax(jnp.max(jnp.abs(wl), axis=0, keepdims=True), axis)
            wq, sw = quantize_symmetric(wl, bits, axis=0, amax=aw)
            out = psum_int_gemm(
                eng, xq, wq, axis=axis, site=site, fold=fold,
                prng_key=prng_key,
            )
            # Full fused epilogue inside the collective body: partials meet
            # in the psum, then the replicated bias/activation tail runs on
            # the replicated output — the same op sequence as the
            # single-device epilogue.
            return apply_epilogue(out, sx, sw.astype(jnp.float32), bias, spec)

        y = _run_shard_map(
            mesh,
            axis,
            body,
            (xr, w),
            (P(rows, axis), P(axis, None)),
            fold,
            prng_key,
            out_spec=P(rows),
            bias=bias,
        )
    return y.reshape(*lead, c).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tp_float_matmul(meta, x, w, bias, fold, prng_key):
    return _float_fwd_impl(meta, x, w, bias, fold, prng_key)


def _tp_float_fwd(meta, x, w, bias, fold, prng_key):
    y = _float_fwd_impl(meta, x, w, bias, fold, prng_key)
    return y, (x, w, bias, fold, prng_key)


def _tp_float_bwd(meta, res, g):
    spec = meta[4]
    x, w, bias, fold, prng_key = res
    g2 = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    wf = w.astype(jnp.float32)
    g2, db = _epilogue_bwd(spec, g2, x2, wf, bias)
    dx, dw = dense_vjp(x, w, g2.reshape(*x.shape[:-1], g2.shape[-1]))
    return dx, dw, db, key_zero_cotangent(fold), key_zero_cotangent(prng_key)


_tp_float_matmul.defvjp(_tp_float_fwd, _tp_float_bwd)


def _packed_fwd_impl(meta, x, wq, w_scale, bias, fold, prng_key):
    eng, site, axis, mesh, k, c, tiling, shards, spec = meta
    bits = eng.dpu.operand_bits
    lead = x.shape[:-1]
    xr = x.reshape(-1, x.shape[-1])
    if mesh is None:
        # Manual mode: raw (K, C) int8 layout only (guarded by
        # maybe_tp_matmul) — slice this device's rows.
        size = int(compat.axis_size(axis))
        k_local = k // size
        xq, sx = quantize_symmetric(xr, bits)
        idx = jax.lax.axis_index(axis)
        xl = jax.lax.dynamic_slice_in_dim(xq, idx * k_local, k_local, axis=1)
        wl = jax.lax.dynamic_slice_in_dim(wq, idx * k_local, k_local, axis=0)
        out = psum_int_gemm(
            eng,
            xl,
            wl,
            axis=axis,
            site=site,
            fold=fold,
            prng_key=prng_key,
            logical_kc=(k_local, c),
        )
        y = apply_epilogue(out, sx, w_scale.astype(jnp.float32), bias, spec)
    else:
        size = int(mesh.shape[axis])
        k_local = k // size
        rows = _row_sharding(mesh, axis, xr.shape[0])
        x_axes = (axis,) if rows is None else rows + (axis,)

        def body(xl, wl, scale, *, bias, fold, prng_key):
            ax = jax.lax.pmax(jnp.max(jnp.abs(xl)), x_axes)
            xq, sx = quantize_symmetric(xl, bits, amax=ax)
            out = psum_int_gemm(
                eng,
                xq,
                wl,
                axis=axis,
                site=site,
                fold=fold,
                prng_key=prng_key,
                logical_kc=(k_local, c),
                tiling=tiling,
            )
            return apply_epilogue(out, sx, scale.astype(jnp.float32), bias, spec)

        # Activations shard rows over the DP axes and K over the TP axis,
        # int8 banks shard on their fan-in rows (the sharded pack stores
        # per-shard padded banks contiguously), the global per-column
        # scales replicate.
        y = _run_shard_map(
            mesh,
            axis,
            body,
            (xr, wq, w_scale),
            (P(rows, axis), P(axis, None), P()),
            fold,
            prng_key,
            out_spec=P(rows),
            bias=bias,
        )
    return y.reshape(*lead, c).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _tp_packed_matmul(meta, x, wq, w_scale, bias, fold, prng_key):
    return _packed_fwd_impl(meta, x, wq, w_scale, bias, fold, prng_key)


def _tp_packed_fwd(meta, x, wq, w_scale, bias, fold, prng_key):
    y = _packed_fwd_impl(meta, x, wq, w_scale, bias, fold, prng_key)
    return y, (x, wq, w_scale, bias, fold, prng_key)


def _tp_packed_bwd(meta, res, g):
    _, _, _, _, k, c, tiling, shards, spec = meta
    x, wq, w_scale, bias, fold, prng_key = res
    wf = PackedDense(wq, w_scale, k, c, tiling, shards).dequant()
    g2 = g.reshape(-1, g.shape[-1]).astype(jnp.float32)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    g2, db = _epilogue_bwd(spec, g2, x2, wf, bias)
    dx, _ = dense_vjp(x, wf, g2.reshape(*x.shape[:-1], g2.shape[-1]))
    # Prepacked weights are frozen serving state: int8 banks get the
    # mandatory float0 cotangent, the scale a plain zero.
    return (
        dx,
        key_zero_cotangent(wq),
        jnp.zeros_like(w_scale),
        db,
        key_zero_cotangent(fold),
        key_zero_cotangent(prng_key),
    )


_tp_packed_matmul.defvjp(_tp_packed_fwd, _tp_packed_bwd)


# ---------------------------------------------------------------------------
# dense() dispatch
# ---------------------------------------------------------------------------
def maybe_tp_matmul(
    engine: Optional[PhotonicEngine],
    w,
    x: jax.Array,
    *,
    site: Optional[str] = None,
    fold=None,
    prng_key: Optional[jax.Array] = None,
    epilogue=None,
    slicing=None,
) -> Optional[jax.Array]:
    """The tensor-parallel product for ``models.common.dense``.

    ``w`` is the dense site's weight: a :class:`PackedDense` or a float
    ``(K, C)`` array.  Returns ``None`` when TP does not apply — no
    active context, TP degree 1, a site the policy keeps digital, a
    contraction K the axis does not divide, or a pack layout the active
    mode cannot shard — and the caller falls through to the
    single-device path.  ``epilogue=`` rides the fused epilogue inside
    the collective body (replicated operands, applied after the psum).
    ``slicing`` overrides the engine's bit-slicing mode — it rides into
    every shard-local pass through :func:`shard_local_engine`.
    """
    ctx = current_tp()
    if ctx is None or engine is None or not engine.routes(site):
        return None
    size = ctx.size()
    if size <= 1:
        return None
    spec, bias = as_epilogue(epilogue)
    if slicing is not None:
        engine = engine.with_slicing(slicing)
    fold = None if fold is None else jnp.asarray(fold, jnp.int32)
    if not isinstance(w, PackedDense):
        if w.shape[0] % size:
            return None
        meta = (engine, site, ctx.axis, ctx.mesh, spec)
        return _tp_float_matmul(meta, x, w, bias, fold, prng_key)
    if w.k % size:
        return None
    if w.tiling is not None:
        # Tile-padded banks are only shardable in the layout they were
        # packed for: GSPMD mode, pack shards == TP degree.
        if ctx.mesh is None or w.shards != size:
            return None
    elif w.shards not in (1, size):
        return None
    meta = (
        engine,
        site,
        ctx.axis,
        ctx.mesh,
        w.k,
        w.c,
        w.tiling,
        w.shards,
        spec,
    )
    return _tp_packed_matmul(meta, x, w.wq, w.w_scale, bias, fold, prng_key)
