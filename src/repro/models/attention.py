"""Attention variants: GQA (+RoPE, qkv-bias), MLA (DeepSeek-V2), cross-attn.

Train/prefill use a chunked (flash-style) online-softmax scan over KV blocks
— O(T) memory, the TPU-friendly pattern.  Decode consumes a KV cache updated
in place; cache layouts carry logical sharding axes so long-context caches
sequence-shard over the `data` mesh axis (DESIGN.md §5).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.common import P, ModelConfig, apply_rope, dense, dense_def

# Device-side name (``named_scope``) of the paged attention core: the KV
# scatter and gather, scores, softmax and P·V, up to ``wo``.
ATTN_CORE = "attn.core"


# ---------------------------------------------------------------------------
# Param definitions
# ---------------------------------------------------------------------------
def gqa_def(cfg: ModelConfig) -> Dict[str, Any]:
    d, h, kv, hd = cfg.d_model, cfg.n_q_heads, cfg.num_kv_heads, cfg.hd
    return {
        "wq": dense_def(d, h * hd, (None, "heads"), bias=cfg.qkv_bias),
        "wk": dense_def(d, kv * hd, (None, "kv_heads"), bias=cfg.qkv_bias),
        "wv": dense_def(d, kv * hd, (None, "kv_heads"), bias=cfg.qkv_bias),
        "wo": dense_def(h * hd, d, ("heads", None)),
    }


def cross_attn_def(cfg: ModelConfig) -> Dict[str, Any]:
    d = gqa_def(cfg)
    d["gate"] = P((1,), (None,), init="zeros")  # gated cross-attn (llama-vision)
    return d


def mla_def(cfg: ModelConfig) -> Dict[str, Any]:
    """DeepSeek-V2's latent attention without query compression: ``wq``,
    the joint down-projection ``wdkv`` (latent ``c_kv`` then the shared
    rope key), the latent's RMSNorm ``kv_norm``, the up-projections
    ``wuk``/``wuv`` (the published ``kv_b_proj`` split by head part) and
    ``wo``.  No projection has a bias."""
    if cfg.qkv_bias:
        raise ValueError("MLA projections with a bias are not implemented")
    d, h = cfg.d_model, cfg.n_q_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r = cfg.kv_lora_rank
    return {
        "wq": dense_def(d, h * (nope + rope), (None, "heads")),
        "wdkv": dense_def(d, r + rope, (None, None)),
        "kv_norm": cm.rmsnorm_def(r),
        "wuk": dense_def(r, h * nope, (None, "heads")),
        "wuv": dense_def(r, h * vd, (None, "heads")),
        "wo": dense_def(h * vd, d, ("heads", None)),
    }


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (train / prefill)
# ---------------------------------------------------------------------------
def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return k
    return jnp.repeat(k, n_rep, axis=2)


def chunked_attention(
    q: jax.Array,  # (B, Tq, H, hd)
    k: jax.Array,  # (B, Tk, KV, hd)
    v: jax.Array,  # (B, Tk, KV, hd_v)
    *,
    causal: bool,
    q_offset: int = 0,
    chunk: int = 512,
    scale: Optional[float] = None,
    unroll: bool = False,
    acc_dtype=jnp.float32,
) -> jax.Array:
    b, tq, h, hd = q.shape
    _, tk, kvh, hdv = v.shape
    n_rep = h // kvh
    scale = scale if scale is not None else hd ** -0.5
    chunk = min(chunk, tk)
    if tk % chunk:
        pad = (-tk) % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_valid = tk
        tk = tk + pad
    else:
        kv_valid = tk
    n_chunks = tk // chunk

    qf = (q.astype(acc_dtype) * scale).transpose(0, 2, 1, 3)  # (B,H,Tq,hd)
    kc = k.reshape(b, n_chunks, chunk, kvh, hd)
    vc = v.reshape(b, n_chunks, chunk, kvh, hdv)
    q_pos = q_offset + jnp.arange(tq)

    def step(carry, inputs):
        m, l, acc = carry
        kb, vb, c_idx = inputs
        kb = _repeat_kv(kb, n_rep).transpose(0, 2, 3, 1)  # (B,H,hd,chunk)
        vb = _repeat_kv(vb, n_rep).transpose(0, 2, 1, 3)  # (B,H,chunk,hdv)
        s = jnp.einsum(
            "bhqd,bhdc->bhqc", qf, kb.astype(acc_dtype),
            preferred_element_type=acc_dtype,
        )  # (B,H,Tq,chunk)
        kv_pos = c_idx * chunk + jnp.arange(chunk)
        valid = kv_pos[None, :] < kv_valid
        if causal:
            valid = valid & (kv_pos[None, :] <= q_pos[:, None])
        s = jnp.where(valid[None, None], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqc,bhcd->bhqd", p, vb.astype(acc_dtype),
            preferred_element_type=acc_dtype,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, tq), -1e30, acc_dtype)
    l0 = jnp.zeros((b, h, tq), acc_dtype)
    acc0 = jnp.zeros((b, h, tq, hdv), acc_dtype)
    (m, l, acc), _ = jax.lax.scan(
        step,
        (m0, l0, acc0),
        (
            kc.transpose(1, 0, 2, 3, 4),
            vc.transpose(1, 0, 2, 3, 4),
            jnp.arange(n_chunks),
        ),
        unroll=True if unroll else 1,
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B,Tq,H,hdv)


# ---------------------------------------------------------------------------
# GQA self-attention
# ---------------------------------------------------------------------------
def _split_heads(x: jax.Array, n: int) -> jax.Array:
    b, t, _ = x.shape
    return x.reshape(b, t, n, -1)


def _qkv_proj(params, x, cfg: ModelConfig, *, layer=None):
    """Q/K/V projections, head-split, RoPE not yet applied.

    When the param dict carries a fused ``wqkv`` entry
    (:func:`repro.photonic.fuse_qkv_params`) the three projections run as
    ONE engine dispatch — one activation quantization, one fused-epilogue
    GEMM — and the output columns are split back here.  Per-column
    quantization and the K-chunked accumulation are column-independent,
    so under a deterministic channel this is bitwise the three separate
    calls (the noisy channel draws a different, equally valid stream for
    the "attn.wqkv" site).
    """
    h, kv, hd = cfg.n_q_heads, cfg.num_kv_heads, cfg.hd
    if "wqkv" in params:
        y = dense(params["wqkv"], x, cfg, site="attn.wqkv", layer=layer)
        yq, yk, yv = jnp.split(y, (h * hd, (h + kv) * hd), axis=-1)
    else:
        yq = dense(params["wq"], x, cfg, site="attn.wq", layer=layer)
        yk = dense(params["wk"], x, cfg, site="attn.wk", layer=layer)
        yv = dense(params["wv"], x, cfg, site="attn.wv", layer=layer)
    return _split_heads(yq, h), _split_heads(yk, kv), _split_heads(yv, kv)


def _attend(q, k, v, cfg: ModelConfig, *, causal: bool, q_offset: int = 0):
    """The prefill/train attention core behind ``cfg.attn_impl``.

    "chunked" is the jnp online-softmax scan; "flash" dispatches the
    Pallas flash-attention kernel via the ``repro.photonic`` surface
    (RPR003) — same math, different block partition, so the two agree to
    float tolerance rather than bitwise.
    """
    if cfg.attn_impl == "flash":
        from repro.photonic.flash import flash_attention

        return flash_attention(q, k, v, causal=causal, q_offset=q_offset)
    return chunked_attention(
        q, k, v, causal=causal, q_offset=q_offset,
        chunk=cfg.attn_chunk, unroll=cfg.unroll_scans,
        acc_dtype=jnp.float32 if cfg.attn_f32 else jnp.bfloat16,
    )


def gqa_attention(
    params: Dict[str, Any],
    x: jax.Array,  # (B, T, D)
    cfg: ModelConfig,
    *,
    positions: jax.Array,  # (T,)
    causal: bool = True,
    q_offset: int = 0,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    q, k, v = _qkv_proj(params, x, cfg, layer=layer)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    q = cm.with_logical(q, ("batch", None, "heads", None))
    k = cm.with_logical(k, ("batch", None, "kv_heads", None))
    v = cm.with_logical(v, ("batch", None, "kv_heads", None))
    out = _attend(q, k, v, cfg, causal=causal, q_offset=q_offset)
    out = out.reshape(*x.shape[:2], -1)
    return dense(params["wo"], out, cfg, site="attn.wo", layer=layer)


def gqa_prefill(
    params, x, cfg: ModelConfig, *, positions, max_seq: int, layer=None
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Self-attention over the prompt + returns a padded KV cache."""
    b, t, _ = x.shape
    q, k, v = _qkv_proj(params, x, cfg, layer=layer)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    out = _attend(q, k, v, cfg, causal=True)
    out = dense(params["wo"], out.reshape(b, t, -1), cfg, site="attn.wo", layer=layer)
    pad4 = ((0, 0), (0, max_seq - t), (0, 0), (0, 0))
    pad3 = ((0, 0), (0, max_seq - t), (0, 0))
    if cfg.kv_cache_int8:
        qk, sk = _quantize_kv(k)
        qv, sv = _quantize_kv(v)
        cache = {
            "k": jnp.pad(qk, pad4),
            "v": jnp.pad(qv, pad4),
            "k_scale": jnp.pad(sk, pad3),
            "v_scale": jnp.pad(sv, pad3),
        }
    else:
        cache = {"k": jnp.pad(k, pad4), "v": jnp.pad(v, pad4)}
    return out, cache


def _quantize_kv(x):
    """Per-(token, kv-head) symmetric int8 quantization of K/V rows.

    The paper's DPUs consume int8 operands; storing the KV cache at int8
    (+ one f32 scale per token-head) halves serving's dominant HBM stream —
    DESIGN.md §3 beyond-paper extension, exercised as §Perf HC-C."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-12) * (1.0 / 127.0)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _gqa_decode_core(q, kf, vf, valid) -> jax.Array:
    """One query token per row against its float32 KV slab, each group of
    ``R = H // KV`` query heads contracted with its own KV head.

    ``q`` (B, 1, H, hd); ``kf``/``vf`` (B, S, KV, hd) float32; ``valid``
    (B or 1, S) bool.  Query head ``h`` reads KV head ``h // R``, the head
    order ``jnp.repeat(k, R, axis=2)`` gives, but K/V are never widened to
    H heads: the slab is read once per group, not once per query head.
    Both contractions run at ``Precision.HIGHEST``: with R rows per group
    a DEFAULT-precision float32 dot may take a single bf16 MXU pass.
    Returns (B, 1, H, hd) float32.
    """
    b, _, h, hd = q.shape
    kvh = kf.shape[2]
    hi = jax.lax.Precision.HIGHEST
    qg = (q.astype(jnp.float32) * hd ** -0.5).reshape(b, kvh, h // kvh, hd)
    s = jnp.einsum(
        "bkrd,bskd->bkrs", qg, kf, precision=hi, preferred_element_type=jnp.float32
    )
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bkrs,bskd->bkrd", p, vf, precision=hi, preferred_element_type=jnp.float32
    )
    return out.reshape(b, 1, h, vf.shape[-1])


def gqa_decode(
    params,
    x: jax.Array,  # (B, 1, D)
    cache: Dict[str, jax.Array],
    pos: jax.Array,  # scalar int32 — current length
    cfg: ModelConfig,
    *,
    layer: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    b = x.shape[0]
    q, k1, v1 = _qkv_proj(params, x, cfg, layer=layer)
    posv = pos[None] if pos.ndim == 0 else pos
    q = apply_rope(q, posv, cfg.rope_theta)
    k1 = apply_rope(k1, posv, cfg.rope_theta)
    new_cache = {}
    if cfg.kv_cache_int8:
        qk1, sk1 = _quantize_kv(k1)
        qv1, sv1 = _quantize_kv(v1)
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], qk1, pos, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], qv1, pos, 1)
        sk = jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], sk1, pos, 1)
        sv = jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], sv1, pos, 1)
        new_cache = {"k_scale": sk, "v_scale": sv}
        kf = ck.astype(jnp.float32) * sk[..., None]
        vf = cv.astype(jnp.float32) * sv[..., None]
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k1.astype(cache["k"].dtype), pos, 1
        )
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v1.astype(cache["v"].dtype), pos, 1
        )
        kf = ck.astype(jnp.float32)
        vf = cv.astype(jnp.float32)
    ck = cm.with_logical(ck, ("batch", "kv_seq", "kv_heads", None))
    cv = cm.with_logical(cv, ("batch", "kv_seq", "kv_heads", None))

    kv_pos = jnp.arange(ck.shape[1])
    out = _gqa_decode_core(q, kf, vf, (kv_pos <= pos)[None, :])
    out = dense(
        params["wo"], out.reshape(b, 1, -1).astype(x.dtype), cfg,
        site="attn.wo", layer=layer,
    )
    return out, {"k": ck, "v": cv, **new_cache}


def gqa_cache_def(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> Dict[str, Any]:
    kv, hd = cfg.num_kv_heads, cfg.hd
    shape = (batch, max_seq, kv, hd)
    axes = ("batch", "kv_seq", "kv_heads", None)
    if cfg.kv_cache_int8:
        sshape = (batch, max_seq, kv)
        saxes = ("batch", "kv_seq", "kv_heads")
        return {
            "k": (shape, axes, jnp.int8),
            "v": (shape, axes, jnp.int8),
            "k_scale": (sshape, saxes, jnp.float32),
            "v_scale": (sshape, saxes, jnp.float32),
        }
    return {
        "k": (shape, axes, dtype),
        "v": (shape, axes, dtype),
    }


# ---------------------------------------------------------------------------
# Paged GQA (repro.serving): block-table KV access, per-request positions
# ---------------------------------------------------------------------------
def _kv_rows(k, v, cfg: ModelConfig, batch_axis: int):
    """Cache rows (+ int8 scales) for computed K/V, batch axis dropped."""
    if cfg.kv_cache_int8:
        qk, sk = _quantize_kv(k)
        qv, sv = _quantize_kv(v)
        rows = {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    else:
        rows = {"k": k, "v": v}
    return jax.tree.map(lambda r: jnp.squeeze(r, batch_axis), rows)


def _kv_view(g, cfg: ModelConfig):
    """Float K/V view of a gathered cache slab (dequantizing int8 KV)."""
    if cfg.kv_cache_int8:
        kf = g["k"].astype(jnp.float32) * g["k_scale"][..., None]
        vf = g["v"].astype(jnp.float32) * g["v_scale"][..., None]
        return kf, vf
    return g["k"].astype(jnp.float32), g["v"].astype(jnp.float32)


def gqa_prefill_chunk(
    params,
    x: jax.Array,  # (1, tc, D) — one request's chunk
    kv_pool,  # per-layer pool leaves (num_blocks, bs, kv, hd)
    block_table: jax.Array,  # (W,) int32 — the request's table row
    t0: jax.Array,  # scalar int32 — chunk start (flat position)
    cfg: ModelConfig,
    *,
    t_full: int,  # static total prompt length (gather width)
    block_size: int,
    positions,  # (tc,) int32 — t0 + arange(tc)
    layer=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One chunk of a chunked prefill: compute this chunk's K/V, scatter
    them into the paged pool, and attend over cache rows ``[0, t_full)``.

    Feeding ``chunked_attention`` exactly ``t_full`` KV rows reproduces the
    one-shot prefill's block partition (``chunk = min(attn_chunk, tk)``), so
    the float path is bitwise-identical to ``gqa_prefill`` per query; rows
    past the written prefix read as zeros off the null block and sit under
    the causal mask (``exp(-1e30 - m)`` underflows to exactly 0).  When one
    chunk covers the whole prompt the in-chunk K/V are used directly — the
    literal ``gqa_prefill`` computation, bitwise even for int8 KV (which
    otherwise round-trips prior chunks through the quantized pool).
    """
    from repro.serving import kv_cache as kvc

    tc = x.shape[1]
    q, k, v = _qkv_proj(params, x, cfg, layer=layer)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    with jax.named_scope(ATTN_CORE):
        blocks, offsets = kvc.chunk_dest(block_table, t0, tc, block_size)
        kv_pool = kvc.scatter_kv(kv_pool, blocks, offsets, _kv_rows(k, v, cfg, 0))

        if t_full == tc:
            kf, vf = k, v  # single chunk covers the prompt: legacy math exactly
            q_offset = 0
        else:
            kf, vf = _kv_view(kvc.gather_kv(kv_pool, block_table[None], t_full), cfg)
            q_offset = t0
        out = chunked_attention(
            q, kf, vf, causal=True, q_offset=q_offset,
            chunk=cfg.attn_chunk, unroll=cfg.unroll_scans,
            acc_dtype=jnp.float32 if cfg.attn_f32 else jnp.bfloat16,
        )
    out = dense(
        params["wo"], out.reshape(1, tc, -1), cfg, site="attn.wo", layer=layer
    )
    return out, kv_pool


def gqa_decode_paged(
    params,
    x: jax.Array,  # (B, 1, D)
    kv_pool,  # per-layer pool leaves (num_blocks, bs, kv, hd)
    block_table: jax.Array,  # (B, W) int32
    pos: jax.Array,  # (B,) int32 — per-request cache length
    blocks: jax.Array,  # (B,) int32 — precomputed write destinations
    offsets: jax.Array,  # (B,) int32
    cfg: ModelConfig,
    *,
    gather_len: int,
    layer=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``gqa_decode`` generalized to per-request positions over the paged
    pool: scatter the new token's K/V through the block table, gather a
    contiguous ``(B, gather_len)`` view, and attend under a per-row causal
    mask ``kv_pos <= pos[b]``.  The core is :func:`_gqa_decode_core`, the
    one the legacy ``gqa_decode`` calls too, so with uniform ``pos`` both
    compute the same thing (same masked softmax, same int8 round-trip).
    """
    from repro.serving import kv_cache as kvc

    b = x.shape[0]
    q, k1, v1 = _qkv_proj(params, x, cfg, layer=layer)
    q = apply_rope(q, pos[:, None], cfg.rope_theta)
    k1 = apply_rope(k1, pos[:, None], cfg.rope_theta)

    with jax.named_scope(ATTN_CORE):
        kv_pool = kvc.scatter_kv(kv_pool, blocks, offsets, _kv_rows(k1, v1, cfg, 1))
        kf, vf = _kv_view(kvc.gather_kv(kv_pool, block_table, gather_len), cfg)
        kv_pos = jnp.arange(gather_len)
        valid = kv_pos[None, :] <= pos[:, None]
        out = _gqa_decode_core(q, kf, vf, valid)
    out = dense(
        params["wo"], out.reshape(b, 1, -1).astype(x.dtype), cfg,
        site="attn.wo", layer=layer,
    )
    return out, kv_pool


# ---------------------------------------------------------------------------
# Cross-attention (vision / whisper decoder): static memory, no RoPE on kv
# ---------------------------------------------------------------------------
def cross_attention(
    params,
    x: jax.Array,       # (B, T, D)
    memory_kv: Tuple[jax.Array, jax.Array],  # precomputed (B,S,KV,hd) pair
    cfg: ModelConfig,
    *,
    gated: bool = False,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    h = cfg.n_q_heads
    b, t, _ = x.shape
    q = _split_heads(dense(params["wq"], x, cfg, site="attn.wq", layer=layer), h)
    k, v = memory_kv
    out = chunked_attention(
        q, k, v, causal=False, chunk=cfg.attn_chunk, unroll=cfg.unroll_scans,
        acc_dtype=jnp.float32 if cfg.attn_f32 else jnp.bfloat16,
    )
    out = dense(params["wo"], out.reshape(b, t, -1), cfg, site="attn.wo", layer=layer)
    if gated:
        out = jnp.tanh(params["gate"].astype(out.dtype)) * out
    return out


def cross_kv(params, memory: jax.Array, cfg: ModelConfig, layer=None):
    """Precompute cross-attention K/V from encoder/vision states."""
    kv = cfg.num_kv_heads
    k = _split_heads(dense(params["wk"], memory, cfg, site="attn.wk", layer=layer), kv)
    v = _split_heads(dense(params["wv"], memory, cfg, site="attn.wv", layer=layer), kv)
    return k, v


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2)
# ---------------------------------------------------------------------------
def _mla_up_weight(p: Dict[str, Any]) -> jax.Array:
    """Float view of an MLA up-projection weight for the absorbed-decode
    einsums (dequantizing a prepacked weight if needed)."""
    from repro.photonic.packing import PackedDense

    w = p["w"]
    return w.dequant() if isinstance(w, PackedDense) else w


def _mla_scale(cfg: ModelConfig) -> float:
    """Softmax scale: the query head's ``(nope + rope) ** -0.5`` times
    YaRN's attention factor (1 without rope scaling)."""
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return qk**-0.5 * cm.rope_attention_factor(cfg.rope_scaling)


def _mla_qkv(params, x, cfg: ModelConfig, positions, layer=None):
    """Queries split into their nope and rope parts, the RMSNorm'd latent
    ``c_kv`` and the shared rope key ``(B, T, 1, rope)``, rope applied."""
    h = cfg.n_q_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    b, t, _ = x.shape
    inv_freq = cm.rope_inv_freq(rope, cfg.rope_theta, cfg.rope_scaling)
    q = dense(params["wq"], x, cfg, site="attn.wq", layer=layer)
    q = q.reshape(b, t, h, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta, inv_freq)

    dkv = dense(params["wdkv"], x, cfg, site="attn.wdkv", layer=layer)  # (B,T,r+rope)
    c_kv, k_rope = dkv[..., : cfg.kv_lora_rank], dkv[..., cfg.kv_lora_rank:]
    c_kv = cm.rmsnorm(params["kv_norm"], c_kv, cfg.norm_eps)
    k_rope = apply_rope(
        k_rope[:, :, None, :], positions, cfg.rope_theta, inv_freq
    )  # (B,T,1,rope)
    return q_nope, q_rope, c_kv, k_rope


def _mla_expand_kv(params, c_kv, k_rope, cfg: ModelConfig, layer=None):
    h = cfg.n_q_heads
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    b, t, _ = c_kv.shape
    k_nope = dense(params["wuk"], c_kv, cfg, site="attn.wuk", layer=layer)
    k_nope = k_nope.reshape(b, t, h, nope)
    v = dense(params["wuv"], c_kv, cfg, site="attn.wuv", layer=layer)
    v = v.reshape(b, t, h, vd)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, t, h, k_rope.shape[-1]))], -1
    )
    return k, v


def mla_attention(
    params, x, cfg: ModelConfig, *, positions, causal: bool = True, layer=None
) -> jax.Array:
    b, t, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, cfg, positions, layer)
    k, v = _mla_expand_kv(params, c_kv, k_rope, cfg, layer)
    q = jnp.concatenate([q_nope, q_rope], -1)
    out = chunked_attention(
        q, k, v, causal=causal, scale=_mla_scale(cfg),
        chunk=cfg.attn_chunk, unroll=cfg.unroll_scans,
        acc_dtype=jnp.float32 if cfg.attn_f32 else jnp.bfloat16,
    )
    return dense(params["wo"], out.reshape(b, t, -1), cfg, site="attn.wo", layer=layer)


def mla_prefill(params, x, cfg: ModelConfig, *, positions, max_seq: int, layer=None):
    b, t, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, cfg, positions, layer)
    k, v = _mla_expand_kv(params, c_kv, k_rope, cfg, layer)
    q = jnp.concatenate([q_nope, q_rope], -1)
    out = chunked_attention(
        q, k, v, causal=True, scale=_mla_scale(cfg),
        chunk=cfg.attn_chunk, unroll=cfg.unroll_scans,
        acc_dtype=jnp.float32 if cfg.attn_f32 else jnp.bfloat16,
    )
    out = dense(params["wo"], out.reshape(b, t, -1), cfg, site="attn.wo", layer=layer)
    pad = max_seq - t
    cache = {
        "c_kv": jnp.pad(c_kv, ((0, 0), (0, pad), (0, 0))),
        "k_rope": jnp.pad(k_rope[:, :, 0, :], ((0, 0), (0, pad), (0, 0))),
    }
    return out, cache


def _mla_absorbed_core(params, q_nope, q_rope, c, kr, valid, cfg: ModelConfig):
    """Attention against the latent cache with the up-projections absorbed
    into the query and output paths (DeepSeek-V2's serving form): no
    ``(B, S, H, head_dim)`` key or value is ever formed.

    ``q_nope`` (B, 1, H, nope), ``q_rope`` (B, 1, H, rope); ``c`` (B, S, r)
    and ``kr`` (B, S, rope) the latent rows; ``valid`` (B or 1, S) bool.
    Every contraction runs in float32 at ``Precision.HIGHEST``.  Equals
    the expanded form (keys ``c @ wuk`` beside the shared rope key, values
    ``c @ wuv``) by linearity.  Returns (B, 1, H, v_head_dim) float32."""
    h = cfg.n_q_heads
    nope, vd, r = cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    hi = jax.lax.Precision.HIGHEST
    f32 = jnp.float32
    w_uk = _mla_up_weight(params["wuk"]).astype(f32).reshape(r, h, nope)
    w_uv = _mla_up_weight(params["wuv"]).astype(f32).reshape(r, h, vd)
    # absorb W_uk into q:  q_abs[b,h,r] = sum_n q_nope[b,1,h,n] W_uk[r,h,n]
    q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope.astype(f32), w_uk, precision=hi)
    s = jnp.einsum("bqhr,bsr->bhqs", q_abs, c.astype(f32), precision=hi)
    s = s + jnp.einsum(
        "bqhe,bse->bhqs", q_rope.astype(f32), kr.astype(f32), precision=hi
    )
    s = jnp.where(valid[:, None, None, :], s * _mla_scale(cfg), -1e30)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhqs,bsr->bqhr", p, c.astype(f32), precision=hi)
    return jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv, precision=hi)  # absorb W_uv


def mla_decode_absorbed(params, x, cache, pos, cfg: ModelConfig, layer=None):
    """MLA decode with the up-projections ABSORBED into the query/output
    paths against the dense compressed cache (:func:`_mla_absorbed_core`);
    equals :func:`mla_decode` by linearity (tested)."""
    b = x.shape[0]
    posv = pos[None] if pos.ndim == 0 else pos
    q_nope, q_rope, c_kv1, k_rope1 = _mla_qkv(params, x, cfg, posv, layer)
    c = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv1.astype(cache["c_kv"].dtype), pos, 1
    )
    kr = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope1[:, :, 0, :].astype(cache["k_rope"].dtype), pos, 1
    )
    c = cm.with_logical(c, ("batch", "kv_seq", None))
    kr = cm.with_logical(kr, ("batch", "kv_seq", None))
    valid = (jnp.arange(c.shape[1]) <= pos)[None, :]
    out = _mla_absorbed_core(params, q_nope, q_rope, c, kr, valid, cfg)
    out = dense(
        params["wo"], out.reshape(b, 1, -1).astype(x.dtype), cfg,
        site="attn.wo", layer=layer,
    )
    return out, {"c_kv": c, "k_rope": kr}


def mla_decode(params, x, cache, pos, cfg: ModelConfig, layer=None):
    """MLA decode against the *compressed* cache (c_kv + k_rope only)."""
    if cfg.mla_absorb:
        return mla_decode_absorbed(params, x, cache, pos, cfg, layer)
    b = x.shape[0]
    posv = pos[None] if pos.ndim == 0 else pos
    q_nope, q_rope, c_kv1, k_rope1 = _mla_qkv(params, x, cfg, posv, layer)
    c = jax.lax.dynamic_update_slice_in_dim(
        cache["c_kv"], c_kv1.astype(cache["c_kv"].dtype), pos, 1
    )
    kr = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], k_rope1[:, :, 0, :].astype(cache["k_rope"].dtype), pos, 1
    )
    c = cm.with_logical(c, ("batch", "kv_seq", None))
    kr = cm.with_logical(kr, ("batch", "kv_seq", None))
    k, v = _mla_expand_kv(params, c, kr[:, :, None, :], cfg, layer)
    q = jnp.concatenate([q_nope, q_rope], -1)
    qf = q.astype(jnp.float32) * _mla_scale(cfg)
    s = jnp.einsum("bqhd,bshd->bhqs", qf, k.astype(jnp.float32))
    s_max = k.shape[1]
    s = jnp.where((jnp.arange(s_max) <= pos)[None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", p, v.astype(jnp.float32))
    out = dense(
        params["wo"], out.reshape(b, 1, -1).astype(x.dtype), cfg,
        site="attn.wo", layer=layer,
    )
    return out, {"c_kv": c, "k_rope": kr}


def mla_cache_def(cfg: ModelConfig, batch: int, max_seq: int, dtype):
    return {
        "c_kv": ((batch, max_seq, cfg.kv_lora_rank), ("batch", "kv_seq", None), dtype),
        "k_rope": (
            (batch, max_seq, cfg.qk_rope_head_dim),
            ("batch", "kv_seq", None),
            dtype,
        ),
    }


# ---------------------------------------------------------------------------
# Paged MLA (repro.serving): the latent pool, every layer's leaves stacked
# ---------------------------------------------------------------------------
def _latent_rows(c_kv, k_rope):
    """Pool rows of computed latents: ``c_kv`` (N, r), ``k_rope`` (N, 1, rope)."""
    return {"c_kv": c_kv, "k_rope": k_rope[:, 0]}


def mla_prefill_chunk(
    params,
    x: jax.Array,  # (1, tc, D) — one request's chunk
    pool,  # latent pool, leaves (layers, num_blocks, bs, width)
    li: jax.Array,  # scalar int32 — this layer's index in the pool
    block_table: jax.Array,  # (W,) int32
    t0: jax.Array,  # scalar int32 — chunk start
    cfg: ModelConfig,
    *,
    t_full: int,
    block_size: int,
    positions,  # (tc,) int32
    layer=None,
):
    """One chunk of a chunked MLA prefill: scatter the chunk's latent rows
    (``c_kv``, ``k_rope``) into layer ``li`` of the pool, expand the
    prompt's rows ``[0, t_full)`` to keys and values through ``wuk`` /
    ``wuv`` and attend causally, as :func:`gqa_prefill_chunk` does; a chunk
    that covers the whole prompt uses its own rows directly."""
    from repro.serving import kv_cache as kvc

    tc = x.shape[1]
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(params, x, cfg, positions, layer)
    with jax.named_scope(ATTN_CORE):
        blocks, offsets = kvc.chunk_dest(block_table, t0, tc, block_size)
        pool = kvc.scatter_kv(
            pool, blocks, offsets, _latent_rows(c_kv[0], k_rope[0]), layer=li
        )
    if t_full == tc:
        c, kr, q_offset = c_kv, k_rope, 0
    else:
        g = kvc.gather_kv(pool, block_table[None], t_full, layer=li)
        c, kr, q_offset = g["c_kv"], g["k_rope"][:, :, None, :], t0
    k, v = _mla_expand_kv(params, c, kr, cfg, layer)
    with jax.named_scope(ATTN_CORE):
        out = chunked_attention(
            jnp.concatenate([q_nope, q_rope], -1), k, v, causal=True,
            q_offset=q_offset, scale=_mla_scale(cfg),
            chunk=cfg.attn_chunk, unroll=cfg.unroll_scans,
            acc_dtype=jnp.float32 if cfg.attn_f32 else jnp.bfloat16,
        )
    out = dense(params["wo"], out.reshape(1, tc, -1), cfg, site="attn.wo", layer=layer)
    return out, pool


def mla_decode_paged(
    params,
    x: jax.Array,  # (B, 1, D)
    pool,  # latent pool, leaves (layers, num_blocks, bs, width)
    li: jax.Array,  # scalar int32 — this layer's index in the pool
    block_table: jax.Array,  # (B, W) int32
    pos: jax.Array,  # (B,) int32
    blocks: jax.Array,  # (B,) int32 — write destinations
    offsets: jax.Array,  # (B,) int32
    cfg: ModelConfig,
    *,
    gather_len: int,
    layer=None,
):
    """One MLA decode step over the paged latent pool: scatter each row's
    new latent, gather a ``(B, gather_len)`` latent slab through the block
    table and attend in the absorbed form (:func:`_mla_absorbed_core`)
    under the per-row mask ``kv_pos <= pos[b]``."""
    from repro.serving import kv_cache as kvc

    b = x.shape[0]
    q_nope, q_rope, c1, kr1 = _mla_qkv(params, x, cfg, pos[:, None], layer)
    with jax.named_scope(ATTN_CORE):
        pool = kvc.scatter_kv(
            pool, blocks, offsets, _latent_rows(c1[:, 0], kr1[:, 0]), layer=li
        )
        g = kvc.gather_kv(pool, block_table, gather_len, layer=li)
        valid = jnp.arange(gather_len)[None, :] <= pos[:, None]
        out = _mla_absorbed_core(params, q_nope, q_rope, g["c_kv"], g["k_rope"], valid, cfg)
    out = dense(
        params["wo"], out.reshape(b, 1, -1).astype(x.dtype), cfg,
        site="attn.wo", layer=layer,
    )
    return out, pool
