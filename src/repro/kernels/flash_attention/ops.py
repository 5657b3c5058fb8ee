"""jit'd wrapper: layout handling + padding for the flash-attention kernel."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import pallas_interpret
from repro.kernels.flash_attention.kernel import flash_attention_pallas


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def flash_attention(
    q: jax.Array,  # (B, Tq, H, hd)
    k: jax.Array,  # (B, Tk, KV, hd)
    v: jax.Array,  # (B, Tk, KV, hd)
    *,
    causal: bool = True,
    q_offset: int = 0,
    kv_valid: Optional[int] = None,
    scale: Optional[float] = None,
    bq: int = 128,
    bk: int = 128,
) -> jax.Array:
    b, tq, h, hd = q.shape
    _, tk, kvh, _ = k.shape
    scale = scale if scale is not None else hd ** -0.5
    kv_valid = tk if kv_valid is None else kv_valid

    bq = min(bq, _round_up(tq, 8))
    bk = min(bk, _round_up(tk, 8))
    tq_p, tk_p = _round_up(tq, bq), _round_up(tk, bk)
    qt = jnp.pad(q, ((0, 0), (0, tq_p - tq), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    kt = jnp.pad(k, ((0, 0), (0, tk_p - tk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v, ((0, 0), (0, tk_p - tk), (0, 0), (0, 0))).transpose(0, 2, 1, 3)

    out = flash_attention_pallas(
        qt, kt, vt,
        scale=scale,
        causal=causal,
        q_offset=q_offset,
        kv_valid=min(kv_valid, tk),
        n_rep=h // kvh,
        bq=bq,
        bk=bk,
        interpret=pallas_interpret(),
    )
    return out.transpose(0, 2, 1, 3)[:, :tq]
