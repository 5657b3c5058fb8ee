"""Flash-attention forward Pallas kernel (TPU target, interpret-validated).

Grid ``(B, H, Tq/bq, Tk/bk)`` with the KV axis innermost; the output tile
and the online-softmax state (m, l, acc) live in VMEM scratch across KV
steps, so the ``Tq x Tk`` score/probability matrices NEVER reach HBM — the
structural basis for the §Perf claim that attention-score HBM traffic is
removable (compare ``repro.models.attention.chunked_attention``, whose
scanned accumulators round-trip HBM every KV chunk).

GQA in the index map: query head h reads kv head ``h // n_rep``.  Causal and
kv-validity masks are computed on block coordinates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(
    q_ref,    # (1, 1, bq, hd)
    k_ref,    # (1, 1, bk, hd)
    v_ref,    # (1, 1, bk, hd)
    o_ref,    # (1, 1, bq, hd)
    m_ref,    # (bq,)     scratch f32
    l_ref,    # (bq,)     scratch f32
    acc_ref,  # (bq, hd)  scratch f32
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    kv_valid: int,
    bq: int,
    bk: int,
    n_k: int,
):
    iq = pl.program_id(2)
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                  # (bq, bk)

    kv_pos = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kv_pos < kv_valid
    if causal:
        q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        valid &= kv_pos <= q_pos
    s = jnp.where(valid, s, -1e30)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=1)
    acc_new = acc_prev * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(kk == n_k - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "scale", "causal", "q_offset", "kv_valid", "n_rep", "bq", "bk", "interpret"
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, H, Tq, hd)
    k: jax.Array,  # (B, KV, Tk, hd)
    v: jax.Array,  # (B, KV, Tk, hd)
    *,
    scale: float,
    causal: bool,
    q_offset: int,
    kv_valid: int,
    n_rep: int,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, tq, hd = q.shape
    _, kvh, tk, _ = k.shape
    assert h == kvh * n_rep, (h, kvh, n_rep)
    assert tq % bq == 0 and tk % bk == 0, (tq, tk, bq, bk)
    n_k = tk // bk

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        q_offset=q_offset,
        kv_valid=kv_valid,
        bq=bq,
        bk=bk,
        n_k=n_k,
    )
    return pl.pallas_call(
        kernel,
        grid=(b, h, tq // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bb, hh, iq, kk: (bb, hh, iq, 0)),
            pl.BlockSpec(
                (1, 1, bk, hd), lambda bb, hh, iq, kk: (bb, hh // n_rep, kk, 0)
            ),
            pl.BlockSpec(
                (1, 1, bk, hd), lambda bb, hh, iq, kk: (bb, hh // n_rep, kk, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd), lambda bb, hh, iq, kk: (bb, hh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, tq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(q, k, v)
