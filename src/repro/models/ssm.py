"""Mamba2 (SSD) blocks with chunkwise-parallel training scan and O(1)
recurrent decode.  Used standalone and inside the zamba2 hybrid.

SSD recurrence per head (scalar decay a_t = exp(-exp(A_log) * dt_t)):

    S_t = a_t * S_{t-1} + dt_t * x_t (outer) B_t        # (head_dim, state)
    y_t = S_t @ C_t + D * x_t

Chunkwise: within a chunk the quadratic masked form
``L[t,s] = (C_t . B_s) * exp(b_t - b_s) * dt_s`` (s <= t) computes intra-chunk
contributions; a `lax.scan` over chunks carries the inter-chunk state — O(T)
memory, parallel within chunks (the TPU-friendly SSD layout).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.common import P, ModelConfig, dense, dense_def


def _inner(cfg: ModelConfig) -> int:
    return cfg.ssm_expand * cfg.d_model


def _nheads(cfg: ModelConfig) -> int:
    return _inner(cfg) // cfg.ssm_head_dim


def mamba_def(cfg: ModelConfig) -> Dict[str, Any]:
    d, inner, st, h = cfg.d_model, _inner(cfg), cfg.ssm_state_size, _nheads(cfg)
    conv_ch = inner + 2 * st
    return {
        "ln": cm.rmsnorm_def(d),
        "in_proj": dense_def(d, 2 * inner + 2 * st + h, (None, "inner")),
        "conv_w": P((cfg.ssm_conv_width, conv_ch), (None, "inner")),
        "conv_b": P((conv_ch,), ("inner",), init="zeros"),
        "a_log": P((h,), ("mamba_heads",), init="zeros"),
        "dt_bias": P((h,), ("mamba_heads",), init="zeros"),
        "d_skip": P((h,), ("mamba_heads",), init="ones"),
        "out_norm": cm.rmsnorm_def(inner),
        "out_proj": dense_def(inner, d, ("inner", None)),
    }


def _split_in(params, x, cfg: ModelConfig):
    inner, st, h = _inner(cfg), cfg.ssm_state_size, _nheads(cfg)
    u = dense(params["in_proj"], x, cfg, site="in_proj")
    z = u[..., :inner]
    xbc = u[..., inner : 2 * inner + 2 * st]
    dt = u[..., 2 * inner + 2 * st :]
    return z, xbc, dt


def _causal_conv(params, xbc: jax.Array) -> jax.Array:
    """Depthwise causal conv over time. xbc: (B, T, C)."""
    w = params["conv_w"].astype(xbc.dtype)  # (W, C)
    width = w.shape[0]
    pad = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(
        pad[:, i : i + xbc.shape[1], :] * w[i][None, None, :] for i in range(width)
    )
    return jax.nn.silu(out + params["conv_b"].astype(xbc.dtype))


def _ssd_chunked(
    xh: jax.Array,   # (B, T, H, dh)
    b_mat: jax.Array,  # (B, T, st)
    c_mat: jax.Array,  # (B, T, st)
    dt: jax.Array,   # (B, T, H)  (softplus'd)
    a_log: jax.Array,  # (H,)
    chunk: int,
    s0: jax.Array | None = None,  # (B, H, dh, st) initial state
    unroll: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Chunkwise SSD. Returns (y (B,T,H,dh), final state (B,H,dh,st))."""
    bsz, t, h, dh = xh.shape
    st = b_mat.shape[-1]
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
    tp = t + pad
    n_chunks = tp // chunk

    decay = -jnp.exp(a_log.astype(jnp.float32))  # (H,) negative rates

    def reshape_c(x):
        return x.reshape(bsz, n_chunks, chunk, *x.shape[2:]).swapaxes(0, 1)

    xs, bs, cs, dts = map(reshape_c, (xh, b_mat, c_mat, dt))

    if s0 is None:
        s0 = jnp.zeros((bsz, h, dh, st), jnp.float32)

    def step(state, inp):
        xc, bc, cc, dtc = inp  # (B, L, H, dh), (B, L, st), (B, L, st), (B, L, H)
        xc = xc.astype(jnp.float32)
        bc = bc.astype(jnp.float32)
        cc = cc.astype(jnp.float32)
        dtc = dtc.astype(jnp.float32)
        loga = dtc * decay[None, None, :]              # (B, L, H) log a_t
        b_cum = jnp.cumsum(loga, axis=1)               # (B, L, H)
        # intra-chunk: L[t,s] = (C_t.B_s) exp(b_t - b_s) dt_s  (s <= t)
        cb = jnp.einsum("bts,bls->btl", cc, bc)
        gap = b_cum[:, :, None, :] - b_cum[:, None, :, :]  # (B, L_t, L_s, H)
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        lmat = jnp.where(
            tri[None, :, :, None], cb[..., None] * jnp.exp(gap), 0.0
        ) * dtc[:, None, :, :]
        y_intra = jnp.einsum("btsh,bshd->bthd", lmat, xc)
        # inter-chunk: y_t += C_t @ (exp(b_t) * S_prev)
        y_inter = jnp.einsum("bts,bhds,bth->bthd", cc, state, jnp.exp(b_cum))
        # state update
        b_tot = b_cum[:, -1, :]                        # (B, H)
        w = jnp.exp(b_tot[:, None, :] - b_cum) * dtc   # (B, L, H)
        s_new = jnp.einsum("blh,bls,blhd->bhds", w, bc, xc)
        state = state * jnp.exp(b_tot)[:, :, None, None] + s_new
        return state, y_intra + y_inter

    state, ys = jax.lax.scan(step, s0, (xs, bs, cs, dts), unroll=True if unroll else 1)
    ys = ys.swapaxes(0, 1).reshape(bsz, tp, h, dh)[:, :t]
    return ys, state


def mamba_block(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Full-sequence (train/prefill) Mamba2 block with residual."""
    inner, st, h, dh = _inner(cfg), cfg.ssm_state_size, _nheads(cfg), cfg.ssm_head_dim
    res = x
    xn = cm.rmsnorm(params["ln"], x, cfg.norm_eps)
    z, xbc, dt = _split_in(params, xn, cfg)
    xbc = _causal_conv(params, xbc)
    xi = xbc[..., :inner].reshape(*x.shape[:2], h, dh)
    b_mat = xbc[..., inner : inner + st]
    c_mat = xbc[..., inner + st :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    y, _ = _ssd_chunked(
        xi, b_mat, c_mat, dt, params["a_log"], cfg.ssm_chunk, unroll=cfg.unroll_scans
    )
    y = y + params["d_skip"].astype(jnp.float32)[None, None, :, None] * xi.astype(
        jnp.float32
    )
    y = y.reshape(*x.shape[:2], inner).astype(x.dtype)
    y = cm.rmsnorm(params["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = dense(params["out_proj"], y, cfg, site="out_proj")
    return res + out


def mamba_state_def(cfg: ModelConfig, batch: int, dtype) -> Dict[str, Any]:
    inner, st, h, dh = _inner(cfg), cfg.ssm_state_size, _nheads(cfg), cfg.ssm_head_dim
    conv_ch = inner + 2 * st
    return {
        "ssm": ((batch, h, dh, st), ("batch", "mamba_heads", None, None), jnp.float32),
        "conv": (
            (batch, cfg.ssm_conv_width - 1, conv_ch),
            ("batch", None, "inner"),
            dtype,
        ),
    }


def mamba_prefill(
    params, x: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Like mamba_block but also returns the decode state."""
    inner, st, h, dh = _inner(cfg), cfg.ssm_state_size, _nheads(cfg), cfg.ssm_head_dim
    res = x
    xn = cm.rmsnorm(params["ln"], x, cfg.norm_eps)
    z, xbc, dt = _split_in(params, xn, cfg)
    conv_state = xbc[:, -(cfg.ssm_conv_width - 1) :, :]
    xbc = _causal_conv(params, xbc)
    xi = xbc[..., :inner].reshape(*x.shape[:2], h, dh)
    b_mat = xbc[..., inner : inner + st]
    c_mat = xbc[..., inner + st :]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    y, s = _ssd_chunked(
        xi, b_mat, c_mat, dt, params["a_log"], cfg.ssm_chunk, unroll=cfg.unroll_scans
    )
    y = y + params["d_skip"].astype(jnp.float32)[None, None, :, None] * xi.astype(
        jnp.float32
    )
    y = y.reshape(*x.shape[:2], inner).astype(x.dtype)
    y = cm.rmsnorm(params["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = dense(params["out_proj"], y, cfg, site="out_proj")
    return res + out, {"ssm": s, "conv": conv_state}


def mamba_decode(
    params, x: jax.Array, state: Dict[str, jax.Array], cfg: ModelConfig
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token step. x: (B, 1, D)."""
    inner, st, h, dh = _inner(cfg), cfg.ssm_state_size, _nheads(cfg), cfg.ssm_head_dim
    res = x
    xn = cm.rmsnorm(params["ln"], x, cfg.norm_eps)
    z, xbc, dt = _split_in(params, xn, cfg)  # (B,1,...)
    conv_in = jnp.concatenate([state["conv"].astype(xbc.dtype), xbc], axis=1)
    new_conv = conv_in[:, 1:, :]
    w = params["conv_w"].astype(xbc.dtype)
    width = w.shape[0]
    conv_out = jnp.einsum("bwc,wc->bc", conv_in[:, -width:, :], w)
    xbc1 = jax.nn.silu(conv_out + params["conv_b"].astype(xbc.dtype))  # (B, C)
    xi = xbc1[:, :inner].reshape(-1, h, dh).astype(jnp.float32)
    b_v = xbc1[:, inner : inner + st].astype(jnp.float32)
    c_v = xbc1[:, inner + st :].astype(jnp.float32)
    dt1 = jax.nn.softplus(
        dt[:, 0, :].astype(jnp.float32) + params["dt_bias"].astype(jnp.float32)
    )  # (B,H)
    a = jnp.exp(dt1 * -jnp.exp(params["a_log"].astype(jnp.float32)))  # (B,H)
    s = state["ssm"] * a[:, :, None, None] + jnp.einsum("bh,bhd,bs->bhds", dt1, xi, b_v)
    y = jnp.einsum("bhds,bs->bhd", s, c_v)
    y = y + params["d_skip"].astype(jnp.float32)[None, :, None] * xi
    y = y.reshape(-1, 1, inner).astype(x.dtype)
    y = cm.rmsnorm(params["out_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = dense(params["out_proj"], y, cfg, site="out_proj")
    return res + out, {"ssm": s, "conv": new_conv}


# ---------------------------------------------------------------------------
# Naive recurrent reference (for tests)
# ---------------------------------------------------------------------------
def ssd_reference(xh, b_mat, c_mat, dt, a_log):
    """Step-by-step recurrence — oracle for _ssd_chunked."""
    bsz, t, h, dh = xh.shape
    st = b_mat.shape[-1]
    decay = -jnp.exp(a_log.astype(jnp.float32))
    s = jnp.zeros((bsz, h, dh, st), jnp.float32)
    ys = []
    for i in range(t):
        a = jnp.exp(dt[:, i, :] * decay[None, :])  # (B,H)
        s = s * a[:, :, None, None] + jnp.einsum(
            "bh,bhd,bs->bhds", dt[:, i, :], xh[:, i].astype(jnp.float32),
            b_mat[:, i].astype(jnp.float32),
        )
        ys.append(jnp.einsum("bhds,bs->bhd", s, c_mat[:, i].astype(jnp.float32)))
    return jnp.stack(ys, axis=1), s
