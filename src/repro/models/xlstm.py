"""xLSTM blocks (arXiv:2405.04517): mLSTM (matrix memory, chunkwise-parallel
with log-domain stabilization) and sLSTM (scalar memory, sequential scan with
recurrent gating).  Layer pattern 7:1 mLSTM:sLSTM (`slstm_every = 8`).

The mLSTM recurrences (stabilizer m_t):

    m_t = max(log f_t + m_{t-1}, i~_t)
    C_t = e^{log f_t + m_{t-1} - m_t} C_{t-1} + e^{i~_t - m_t} k_t v_t^T
    n_t = e^{log f_t + m_{t-1} - m_t} n_{t-1} + e^{i~_t - m_t} k_t
    h_t = (q_t C_t) / max(|q_t n_t|, e^{-m_t})

Training uses the chunkwise form (within-chunk quadratic masked attention +
`lax.scan` over chunks) — O(T) memory; decode is the O(1) recurrence.
The sLSTM recurrence is sequential by construction (recurrent weights R act
on h_{t-1}); it appears in 1/8 of layers so the scan cost stays contained.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.common import P, ModelConfig, dense, dense_def


def _inner(cfg: ModelConfig) -> int:
    return cfg.ssm_expand * cfg.d_model  # projection factor 2 by default


def _dh(cfg: ModelConfig) -> int:
    return _inner(cfg) // cfg.num_heads


# ---------------------------------------------------------------------------
# mLSTM
# ---------------------------------------------------------------------------
def mlstm_def(cfg: ModelConfig) -> Dict[str, Any]:
    d, inner, h = cfg.d_model, _inner(cfg), cfg.num_heads
    return {
        "ln": cm.rmsnorm_def(d),
        "up": dense_def(d, 2 * inner, (None, "inner")),
        "wq": dense_def(inner, inner, (None, "inner")),
        "wk": dense_def(inner, inner, (None, "inner")),
        "wv": dense_def(inner, inner, (None, "inner")),
        "wi": dense_def(inner, h, (None, None), init="zeros"),
        "wf": dense_def(inner, h, (None, None), init="zeros"),
        "out_norm": cm.rmsnorm_def(inner),
        "down": dense_def(inner, d, ("inner", None)),
    }


def _mlstm_chunked(
    q, k, v,          # (B, T, H, dh)
    li, lf,           # (B, T, H)  input-gate preact, log-forget
    chunk: int,
    state: Tuple[jax.Array, jax.Array, jax.Array] | None = None,
    unroll: bool = False,
):
    b, t, h, dh = q.shape
    chunk = min(chunk, t)
    pad = (-t) % chunk
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        li = jnp.pad(li, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        lf = jnp.pad(lf, ((0, 0), (0, pad), (0, 0)))
    tp = t + pad
    nc = tp // chunk

    def rs(x):
        return x.reshape(b, nc, chunk, *x.shape[2:]).swapaxes(0, 1)

    qs, ks, vs, lis, lfs = map(rs, (q, k, v, li, lf))
    scale = dh ** -0.5

    if state is None:
        state = (
            jnp.zeros((b, h, dh, dh), jnp.float32),  # C
            jnp.zeros((b, h, dh), jnp.float32),      # n
            jnp.full((b, h), -1e30, jnp.float32),    # m
        )

    def step(carry, inp):
        c_prev, n_prev, m_prev = carry
        qc, kc, vc, lic, lfc = inp
        qc = qc.astype(jnp.float32) * scale
        kc = kc.astype(jnp.float32)
        vc = vc.astype(jnp.float32)
        bcum = jnp.cumsum(lfc, axis=1)                      # (B,L,H)
        # intra log-weights: D[t,s] = b_t - b_s + li_s  (s <= t)
        dmat = bcum[:, :, None, :] - bcum[:, None, :, :] + lic[:, None, :, :]
        tri = jnp.tril(jnp.ones((chunk, chunk), bool))
        dmat = jnp.where(tri[None, :, :, None], dmat, -1e30)
        inter_w = bcum + m_prev[:, None, :]                 # (B,L,H)
        m_t = jnp.maximum(dmat.max(axis=2), inter_w)        # (B,L,H)
        dexp = jnp.exp(dmat - m_t[:, :, None, :])
        s = jnp.einsum("blhd,bshd->blsh", qc, kc) * dexp    # (B,L,S,H)
        num = jnp.einsum("blsh,bshd->blhd", s, vc)
        den = s.sum(axis=2)                                 # (B,L,H)
        wi = jnp.exp(inter_w - m_t)                         # (B,L,H)
        num = num + wi[..., None] * jnp.einsum("blhd,bhde->blhe", qc, c_prev)
        den = den + wi * jnp.einsum("blhd,bhd->blh", qc, n_prev)
        hout = num / jnp.maximum(jnp.abs(den), jnp.exp(-m_t))[..., None]
        # chunk-end state
        btot = bcum[:, -1, :]                               # (B,H)
        up_w = btot[:, None, :] - bcum + lic                # (B,L,H)
        m_next = jnp.maximum(btot + m_prev, up_w.max(axis=1))
        wexp = jnp.exp(up_w - m_next[:, None, :])
        c_new = jnp.einsum("blh,blhd,blhe->bhde", wexp, kc, vc)
        n_new = jnp.einsum("blh,blhd->bhd", wexp, kc)
        decay = jnp.exp(btot + m_prev - m_next)
        c_next = decay[:, :, None, None] * c_prev + c_new
        n_next = decay[:, :, None] * n_prev + n_new
        return (c_next, n_next, m_next), hout

    state, hs = jax.lax.scan(
        step, state, (qs, ks, vs, lis, lfs), unroll=True if unroll else 1
    )
    hs = hs.swapaxes(0, 1).reshape(b, tp, h, dh)[:, :t]
    return hs, state


def _mlstm_qkv_gates(params, xin, cfg: ModelConfig):
    b, t, _ = xin.shape
    h, dh = cfg.num_heads, _dh(cfg)
    q = dense(params["wq"], xin, cfg, site="wq").reshape(b, t, h, dh)
    k = dense(params["wk"], xin, cfg, site="wk").reshape(b, t, h, dh)
    v = dense(params["wv"], xin, cfg, site="wv").reshape(b, t, h, dh)
    li = dense(params["wi"], xin, cfg, site="wi").astype(jnp.float32)         # (B,T,H)
    lf = jax.nn.log_sigmoid(
        dense(params["wf"], xin, cfg, site="wf").astype(jnp.float32)
    )
    return q, k, v, li, lf


def mlstm_block(params, x, cfg: ModelConfig) -> jax.Array:
    res = x
    xn = cm.rmsnorm(params["ln"], x, cfg.norm_eps)
    u = dense(params["up"], xn, cfg, site="up")
    xin, gate = jnp.split(u, 2, axis=-1)
    q, k, v, li, lf = _mlstm_qkv_gates(params, xin, cfg)
    hs, _ = _mlstm_chunked(q, k, v, li, lf, cfg.ssm_chunk, unroll=cfg.unroll_scans)
    hs = hs.reshape(*x.shape[:2], -1).astype(x.dtype)
    y = cm.rmsnorm(params["out_norm"], hs, cfg.norm_eps) * jax.nn.silu(gate)
    return res + dense(params["down"], y, cfg, site="down")


def mlstm_prefill(params, x, cfg: ModelConfig):
    res = x
    xn = cm.rmsnorm(params["ln"], x, cfg.norm_eps)
    u = dense(params["up"], xn, cfg, site="up")
    xin, gate = jnp.split(u, 2, axis=-1)
    q, k, v, li, lf = _mlstm_qkv_gates(params, xin, cfg)
    hs, (c, n, m) = _mlstm_chunked(
        q, k, v, li, lf, cfg.ssm_chunk, unroll=cfg.unroll_scans
    )
    hs = hs.reshape(*x.shape[:2], -1).astype(x.dtype)
    y = cm.rmsnorm(params["out_norm"], hs, cfg.norm_eps) * jax.nn.silu(gate)
    return res + dense(params["down"], y, cfg, site="down"), {"C": c, "n": n, "m": m}


def mlstm_decode(params, x, state, cfg: ModelConfig):
    """x: (B,1,D); O(1) recurrent step."""
    res = x
    h, dh = cfg.num_heads, _dh(cfg)
    xn = cm.rmsnorm(params["ln"], x, cfg.norm_eps)
    u = dense(params["up"], xn, cfg, site="up")
    xin, gate = jnp.split(u, 2, axis=-1)
    q, k, v, li, lf = _mlstm_qkv_gates(params, xin, cfg)
    q1 = q[:, 0].astype(jnp.float32) * (dh ** -0.5)  # (B,H,dh)
    k1, v1 = k[:, 0].astype(jnp.float32), v[:, 0].astype(jnp.float32)
    li1, lf1 = li[:, 0], lf[:, 0]                    # (B,H)
    c_prev, n_prev, m_prev = state["C"], state["n"], state["m"]
    m_t = jnp.maximum(lf1 + m_prev, li1)
    fw = jnp.exp(lf1 + m_prev - m_t)
    iw = jnp.exp(li1 - m_t)
    c_t = fw[:, :, None, None] * c_prev + iw[:, :, None, None] * jnp.einsum(
        "bhd,bhe->bhde", k1, v1
    )
    n_t = fw[:, :, None] * n_prev + iw[:, :, None] * k1
    num = jnp.einsum("bhd,bhde->bhe", q1, c_t)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q1, n_t)), jnp.exp(-m_t))
    hout = (num / den[..., None]).reshape(x.shape[0], 1, -1).astype(x.dtype)
    y = cm.rmsnorm(params["out_norm"], hout, cfg.norm_eps) * jax.nn.silu(gate)
    return res + dense(params["down"], y, cfg, site="down"), {
        "C": c_t, "n": n_t, "m": m_t
    }


# ---------------------------------------------------------------------------
# sLSTM
# ---------------------------------------------------------------------------
def slstm_def(cfg: ModelConfig) -> Dict[str, Any]:
    d, h = cfg.d_model, cfg.num_heads
    dh = d // h
    return {
        "ln": cm.rmsnorm_def(d),
        "wx": dense_def(d, 4 * d, (None, "inner")),
        "r": P((h, dh, 4 * dh), (None, None, None)),  # block-diag recurrent
        "out_norm": cm.rmsnorm_def(d),
        "down": dense_def(d, d, ("inner", None)),
    }


def _slstm_scan(params, gx, cfg: ModelConfig, state):
    """gx: (B, T, 4D) input-side gate preacts. Sequential over T."""
    b, t, _ = gx.shape
    d = cfg.d_model
    h = cfg.num_heads
    dh = d // h
    r = params["r"].astype(jnp.float32)

    def step(carry, g_t):
        c, n, m, hprev = carry  # (B,D),(B,D),(B,D),(B,D)
        rec = jnp.einsum("bhd,hde->bhe", hprev.reshape(b, h, dh), r).reshape(b, 4 * d)
        g = g_t.astype(jnp.float32) + rec
        gi, gf, gz, go = jnp.split(g, 4, axis=-1)
        m_t = jnp.maximum(jax.nn.log_sigmoid(gf) + m, gi)
        fw = jnp.exp(jax.nn.log_sigmoid(gf) + m - m_t)
        iw = jnp.exp(gi - m_t)
        c_t = fw * c + iw * jnp.tanh(gz)
        n_t = fw * n + iw
        h_t = jax.nn.sigmoid(go) * c_t / jnp.maximum(n_t, 1e-6)
        return (c_t, n_t, m_t, h_t), h_t

    state, hs = jax.lax.scan(step, state, gx.swapaxes(0, 1))
    return hs.swapaxes(0, 1), state


def _slstm_init_state(b, d):
    z = jnp.zeros((b, d), jnp.float32)
    return (z, z, jnp.full((b, d), -1e30, jnp.float32), z)


def slstm_block(params, x, cfg: ModelConfig) -> jax.Array:
    res = x
    xn = cm.rmsnorm(params["ln"], x, cfg.norm_eps)
    gx = dense(params["wx"], xn, cfg, site="wx")
    hs, _ = _slstm_scan(params, gx, cfg, _slstm_init_state(x.shape[0], cfg.d_model))
    hs = hs.astype(x.dtype)
    y = cm.rmsnorm(params["out_norm"], hs, cfg.norm_eps)
    return res + dense(params["down"], y, cfg, site="down")


def slstm_prefill(params, x, cfg: ModelConfig):
    res = x
    xn = cm.rmsnorm(params["ln"], x, cfg.norm_eps)
    gx = dense(params["wx"], xn, cfg, site="wx")
    hs, (c, n, m, h) = _slstm_scan(
        params, gx, cfg, _slstm_init_state(x.shape[0], cfg.d_model)
    )
    hs = hs.astype(x.dtype)
    y = cm.rmsnorm(params["out_norm"], hs, cfg.norm_eps)
    return res + dense(params["down"], y, cfg, site="down"), {
        "c": c, "n": n, "m": m, "h": h
    }


def slstm_decode(params, x, state, cfg: ModelConfig):
    res = x
    xn = cm.rmsnorm(params["ln"], x, cfg.norm_eps)
    gx = dense(params["wx"], xn, cfg, site="wx")
    st = (state["c"], state["n"], state["m"], state["h"])
    hs, (c, n, m, h) = _slstm_scan(params, gx, cfg, st)
    hs = hs.astype(x.dtype)
    y = cm.rmsnorm(params["out_norm"], hs, cfg.norm_eps)
    return res + dense(params["down"], y, cfg, site="down"), {
        "c": c, "n": n, "m": m, "h": h
    }


# ---------------------------------------------------------------------------
# Full xLSTM LM (groups of slstm_every-1 mLSTM + 1 sLSTM)
# ---------------------------------------------------------------------------
def xlstm_def(cfg: ModelConfig) -> Dict[str, Any]:
    from repro.models.lm import stack_defs

    n_groups = cfg.num_layers // cfg.slstm_every
    per = cfg.slstm_every - 1
    return {
        "embed": cm.embed_def(cfg.n_vocab, cfg.d_model),
        "mlstm": stack_defs(stack_defs(mlstm_def(cfg), per), n_groups),
        "slstm": stack_defs(slstm_def(cfg), n_groups),
        "final_norm": cm.rmsnorm_def(cfg.d_model),
        "lm_head": cm.dense_def(cfg.d_model, cfg.n_vocab, (None, "vocab")),
    }


def _xlstm_body(params, x, cfg: ModelConfig, mode: str, states=None):
    """Shared scan over groups for train ('full'), prefill, decode."""

    def group(carry, inp):
        x = carry
        if mode == "full":
            mparams, sparams = inp
            blk = cm.apply_remat(lambda p, x: mlstm_block(p, x, cfg), cfg)

            def inner_step(x, p):
                x = blk(p, x)
                return cm.with_logical(x, ("batch", None, None)), None

            x, _ = jax.lax.scan(inner_step, x, mparams)
            x = slstm_block(sparams, x, cfg)
            return x, None
        elif mode == "prefill":
            mparams, sparams = inp

            def inner_step(x, p):
                x, st = mlstm_prefill(p, x, cfg)
                return x, st

            x, msts = jax.lax.scan(inner_step, x, mparams)
            x, sst = slstm_prefill(sparams, x, cfg)
            return x, (msts, sst)
        else:  # decode
            mparams, sparams, mst, sst = inp

            def inner_step(x, pst):
                p, st = pst
                x, st = mlstm_decode(p, x, st, cfg)
                return x, st

            x, msts = jax.lax.scan(inner_step, x, (mparams, mst))
            x, sst = slstm_decode(sparams, x, sst, cfg)
            return x, (msts, sst)

    if mode == "decode":
        xs = (params["mlstm"], params["slstm"], states["mlstm"], states["slstm"])
    else:
        xs = (params["mlstm"], params["slstm"])
    x, sts = jax.lax.scan(group, x, xs)
    return x, sts


def xlstm_logits(params, tokens, cfg: ModelConfig):
    x = cm.embed(params["embed"], tokens, cfg)
    x = cm.with_logical(x, ("batch", None, None))
    x, _ = _xlstm_body(params, x, cfg, "full")
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return cm.dense(params["lm_head"], x, cfg, site="lm_head"), jnp.zeros(
        (), jnp.float32
    )


def xlstm_loss(params, batch, cfg: ModelConfig):
    logits, _ = xlstm_logits(params, batch["tokens"], cfg)
    return cm.softmax_cross_entropy(logits, batch["labels"], cfg.vocab_size)


def xlstm_prefill(params, tokens, cfg: ModelConfig, max_seq: int = 0):
    x = cm.embed(params["embed"], tokens, cfg)
    x, sts = _xlstm_body(params, x, cfg, "prefill")
    msts, ssts = sts
    x = cm.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x, cfg, site="lm_head")
    cache = {"mlstm": msts, "slstm": ssts, "pos": jnp.array(tokens.shape[1], jnp.int32)}
    return logits, cache


def xlstm_decode(params, token, cache, cfg: ModelConfig):
    x = cm.embed(params["embed"], token, cfg)
    x, sts = _xlstm_body(params, x, cfg, "decode", states=cache)
    msts, ssts = sts
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x, cfg, site="lm_head")
    return logits, {"mlstm": msts, "slstm": ssts, "pos": cache["pos"] + 1}


def xlstm_cache_def(cfg: ModelConfig, batch: int, max_seq: int, dtype):
    n_groups = cfg.num_layers // cfg.slstm_every
    per = cfg.slstm_every - 1
    h, dh, d = cfg.num_heads, _dh(cfg), cfg.d_model
    return {
        "mlstm": {
            "C": (
                (n_groups, per, batch, h, dh, dh),
                (None, None, "batch", None, "inner", None),
                jnp.float32,
            ),
            "n": (
                (n_groups, per, batch, h, dh),
                (None, None, "batch", None, "inner"),
                jnp.float32,
            ),
            "m": ((n_groups, per, batch, h), (None, None, "batch", None), jnp.float32),
        },
        "slstm": {
            k: ((n_groups, batch, d), (None, "batch", None), jnp.float32)
            for k in ("c", "n", "m", "h")
        },
        "pos": ((), (), jnp.int32),
    }
