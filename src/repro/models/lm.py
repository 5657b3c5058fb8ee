"""Decoder-only LM covering the dense / MoE / MLA / vision-cross-attn
families (granite, qwen2 x2, deepseek-67b, phi3.5-moe, deepseek-v2-lite,
llama-3.2-vision).

Layers are stacked and scanned (`jax.lax.scan`) with optional remat — the
HLO stays one-layer-sized, which is what makes 512-way SPMD dry-runs
compile fast.  Heterogeneous stacks (vision cross-attn every Nth layer,
DeepSeek's dense first layer) become separate scanned groups (DESIGN.md §5).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models import ffn
from repro.models.common import P, ModelConfig


# ---------------------------------------------------------------------------
# Param-def helpers
# ---------------------------------------------------------------------------
def stack_defs(defs: Any, n: int) -> Any:
    """Prepend a layer dimension to every P in a def tree."""
    if isinstance(defs, P):
        return P((n,) + defs.shape, (None,) + defs.axes, defs.init, defs.fan_axis + 1)
    return {k: stack_defs(v, n) for k, v in defs.items()}


def block_def(cfg: ModelConfig, kind: str = "self") -> Dict[str, Any]:
    d: Dict[str, Any] = {
        "ln1": cm.rmsnorm_def(cfg.d_model), "ln2": cm.rmsnorm_def(cfg.d_model)
    }
    if kind in ("self", "dense_ffn"):
        d["attn"] = attn.mla_def(cfg) if cfg.mla else attn.gqa_def(cfg)
    elif kind == "cross":
        d["attn"] = attn.cross_attn_def(cfg)
        d["gate_ffn"] = P((1,), (None,), init="zeros")
    if kind == "dense_ffn" or (cfg.num_experts == 0) or kind == "cross":
        d["ffn"] = ffn.mlp_def(cfg)
    else:
        d["ffn"] = ffn.moe_def(cfg)
    return d


def _n_cross(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.cross_attn_every if cfg.cross_attn_every else 0


def _n_self(cfg: ModelConfig) -> int:
    """Scanned self-attention layers: all but the cross-attention layers
    and the leading dense-FFN layer (DeepSeek's first)."""
    return cfg.num_layers - _n_cross(cfg) - cfg.first_dense_layers


def lm_def(cfg: ModelConfig) -> Dict[str, Any]:
    defs: Dict[str, Any] = {
        "embed": cm.embed_def(cfg.n_vocab, cfg.d_model),
        "layers": stack_defs(block_def(cfg, "self"), _n_self(cfg)),
        "final_norm": cm.rmsnorm_def(cfg.d_model),
    }
    if cfg.first_dense_layers:
        defs["first_block"] = block_def(cfg, "dense_ffn")
    if cfg.cross_attn_every:
        defs["cross"] = stack_defs(block_def(cfg, "cross"), _n_cross(cfg))
    if not cfg.tie_embeddings:
        defs["lm_head"] = cm.dense_def(cfg.d_model, cfg.n_vocab, (None, "vocab"))
    return defs


# ---------------------------------------------------------------------------
# Blocks (training / full-sequence forward)
# ---------------------------------------------------------------------------
def self_block(params, x, cfg: ModelConfig, positions, layer=None) -> Tuple[
    jax.Array, jax.Array
]:
    h = cm.rmsnorm(params["ln1"], x, cfg.norm_eps)
    if cfg.mla:
        a = attn.mla_attention(params["attn"], h, cfg, positions=positions, layer=layer)
    else:
        a = attn.gqa_attention(params["attn"], h, cfg, positions=positions, layer=layer)
    x = x + a
    x = cm.with_logical(x, ("batch", "seq_sp", None))
    h = cm.rmsnorm(params["ln2"], x, cfg.norm_eps)
    aux = jnp.zeros((), jnp.float32)
    if "router" in params["ffn"]:
        f, aux = ffn.moe(params["ffn"], h, cfg, layer=layer)
    else:
        f = ffn.mlp(params["ffn"], h, cfg, layer=layer)
    x = x + f
    x = cm.with_logical(x, ("batch", "seq_sp", None))
    return x, aux


def cross_block(params, x, memory_kv, cfg: ModelConfig, layer=None) -> jax.Array:
    h = cm.rmsnorm(params["ln1"], x, cfg.norm_eps)
    x = x + attn.cross_attention(
        params["attn"], h, memory_kv, cfg, gated=True, layer=layer
    )
    h = cm.rmsnorm(params["ln2"], x, cfg.norm_eps)
    x = x + jnp.tanh(params["gate_ffn"].astype(x.dtype)) * ffn.mlp(
        params["ffn"], h, cfg, layer=layer
    )
    return cm.with_logical(x, ("batch", "seq_sp", None))


def _stack_len(stacked) -> int:
    return jax.tree.leaves(stacked)[0].shape[0]


def _scan_blocks(body, x, stacked, cfg: ModelConfig, *extra, base=0):
    """Scan ``body(layer_params, layer_idx, x, *extra)`` over a stacked
    layer tree.  The layer index rides the scan xs (``base`` offsets it
    past unscanned blocks) and feeds the photonic engine's site-folded
    noise streams, so same-shaped layers decorrelate (DESIGN.md §9)."""
    body = cm.apply_remat(body, cfg)

    def step(carry, inp):
        layer_params, idx = inp
        x, aux = carry
        x, a = body(layer_params, idx, x, *extra)
        return (x, aux + a), None

    idxs = base + jnp.arange(_stack_len(stacked))
    (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)), (stacked, idxs))
    return x, aux


# ---------------------------------------------------------------------------
# Training forward / loss
# ---------------------------------------------------------------------------
def lm_logits(params, tokens, cfg: ModelConfig, vision: Optional[jax.Array] = None):
    b, t = tokens.shape
    x = cm.embed(params["embed"], tokens, cfg)
    x = cm.with_logical(x, ("batch", "seq_sp", None))
    positions = jnp.arange(t)
    aux = jnp.zeros((), jnp.float32)

    base = 0
    if cfg.first_dense_layers:
        x, a = self_block(params["first_block"], x, cfg, positions, layer=0)
        aux += a
        base = 1

    if cfg.cross_attn_every:
        # groups of (cross_attn_every - 1) self layers + 1 cross layer
        per = cfg.cross_attn_every - 1
        n_groups = _n_cross(cfg)
        self_stack = jax.tree.map(
            lambda p: p.reshape((n_groups, per) + p.shape[1:]), params["layers"]
        )
        # Per-group cross params differ -> compute kv inside the group body.
        def group(carry, inp):
            x, aux = carry
            selfs, crossp, g = inp
            def body(p, idx, x, pos):
                return self_block(p, x, cfg, pos, layer=idx)
            x, a = _scan_blocks(body, x, selfs, cfg, positions, base=base + g * per)
            # Cross blocks fold in a range disjoint from the self-layer
            # indices, so same-site GEMMs never share a noise stream.
            cg = cfg.num_layers + g
            kv = attn.cross_kv(crossp["attn"], vision, cfg, layer=cg)
            cb = cm.apply_remat(
                lambda p, x, k, g: cross_block(p, x, k, cfg, layer=g), cfg
            )
            x = cb(crossp, x, kv, cg)
            return (x, aux + a), None

        (x, aux2), _ = jax.lax.scan(
            group, (x, aux), (self_stack, params["cross"], jnp.arange(n_groups))
        )
        aux = aux2
    else:
        def body(p, idx, x, pos):
            return self_block(p, x, cfg, pos, layer=idx)

        x, a = _scan_blocks(body, x, params["layers"], cfg, positions, base=base)
        aux += a

    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = cm.unembed(params["embed"], x, cfg)
    else:
        logits = cm.dense(params["lm_head"], x, cfg, site="lm_head")
    return logits, aux


def lm_loss(params, batch: Dict[str, jax.Array], cfg: ModelConfig) -> jax.Array:
    logits, aux = lm_logits(params, batch["tokens"], cfg, vision=batch.get("vision"))
    ce = cm.softmax_cross_entropy(logits, batch["labels"], cfg.vocab_size)
    return ce + 0.01 * aux


# ---------------------------------------------------------------------------
# Serving: prefill + decode with per-layer caches
# ---------------------------------------------------------------------------
def _layer_prefill(p, x, cfg, positions, max_seq, layer=None):
    h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.mla:
        a, cache = attn.mla_prefill(
            p["attn"], h, cfg, positions=positions, max_seq=max_seq, layer=layer
        )
    else:
        a, cache = attn.gqa_prefill(
            p["attn"], h, cfg, positions=positions, max_seq=max_seq, layer=layer
        )
    x = x + a
    h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "router" in p["ffn"]:
        f, _ = ffn.moe(p["ffn"], h, cfg, layer=layer)
    else:
        f = ffn.mlp(p["ffn"], h, cfg, layer=layer)
    x = x + f
    return cm.with_logical(x, ("batch", "seq_sp", None)), cache


def _layer_decode(p, x, cache, pos, cfg, layer=None):
    h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
    if cfg.mla:
        a, cache = attn.mla_decode(p["attn"], h, cache, pos, cfg, layer=layer)
    else:
        a, cache = attn.gqa_decode(p["attn"], h, cache, pos, cfg, layer=layer)
    x = x + a
    h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "router" in p["ffn"]:
        f, _ = ffn.moe(p["ffn"], h, cfg, layer=layer)
    else:
        f = ffn.mlp(p["ffn"], h, cfg, layer=layer)
    return x + f, cache


def lm_prefill(
    params,
    tokens: jax.Array,  # (B, T)
    cfg: ModelConfig,
    max_seq: int,
    vision: Optional[jax.Array] = None,
):
    """Run the prompt; returns (last-token logits, cache)."""
    b, t = tokens.shape
    x = cm.embed(params["embed"], tokens, cfg)
    positions = jnp.arange(t)
    caches = {}

    base = 0
    if cfg.first_dense_layers:
        x, c0 = _layer_prefill(
            params["first_block"], x, cfg, positions, max_seq, layer=0
        )
        caches["first"] = c0
        base = 1

    if cfg.cross_attn_every:
        per = cfg.cross_attn_every - 1
        n_groups = _n_cross(cfg)
        self_stack = jax.tree.map(
            lambda p: p.reshape((n_groups, per) + p.shape[1:]), params["layers"]
        )

        def group(x, inp):
            selfs, crossp, g = inp

            def body(x, pi):
                p, idx = pi
                x, c = _layer_prefill(p, x, cfg, positions, max_seq, layer=idx)
                return x, c

            x, cs = jax.lax.scan(body, x, (selfs, base + g * per + jnp.arange(per)))
            cg = cfg.num_layers + g
            kv = attn.cross_kv(crossp["attn"], vision, cfg, layer=cg)
            x = cross_block(crossp, x, kv, cfg, layer=cg)
            return x, (cs, kv)

        x, (self_caches, cross_kvs) = jax.lax.scan(
            group, x, (self_stack, params["cross"], jnp.arange(n_groups))
        )
        # (groups, per, ...) -> flat (layers, ...)
        caches["layers"] = jax.tree.map(
            lambda c: c.reshape((-1,) + c.shape[2:]), self_caches
        )
        caches["cross_kv"] = cross_kvs
    else:
        def body(x, pi):
            p, idx = pi
            x, c = _layer_prefill(p, x, cfg, positions, max_seq, layer=idx)
            return x, c

        n = jax.tree.leaves(params["layers"])[0].shape[0]
        x, layer_caches = jax.lax.scan(
            body, x, (params["layers"], base + jnp.arange(n))
        )
        caches["layers"] = layer_caches

    x = cm.rmsnorm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
    logits = (
        cm.unembed(params["embed"], x, cfg)
        if cfg.tie_embeddings
        else cm.dense(params["lm_head"], x, cfg, site="lm_head")
    )
    caches["pos"] = jnp.array(t, jnp.int32)
    return logits, caches


def lm_decode(params, token: jax.Array, caches, cfg: ModelConfig):
    """One decode step. token: (B, 1) int32. Returns (logits, caches)."""
    pos = caches["pos"]
    x = cm.embed(params["embed"], token, cfg)

    base = 0
    if cfg.first_dense_layers:
        x, c0 = _layer_decode(
            params["first_block"], x, caches["first"], pos, cfg, layer=0
        )
        caches = {**caches, "first": c0}
        base = 1

    if cfg.cross_attn_every:
        per = cfg.cross_attn_every - 1
        n_groups = _n_cross(cfg)
        self_stack = jax.tree.map(
            lambda p: p.reshape((n_groups, per) + p.shape[1:]), params["layers"]
        )
        cache_stack = jax.tree.map(
            lambda c: c.reshape((n_groups, per) + c.shape[1:]), caches["layers"]
        )

        def group(x, inp):
            selfs, cs, crossp, kv, g = inp

            def body(x, pci):
                p, c, idx = pci
                x, c = _layer_decode(p, x, c, pos, cfg, layer=idx)
                return x, c

            x, cs = jax.lax.scan(
                body, x, (selfs, cs, base + g * per + jnp.arange(per))
            )
            x = cross_block(crossp, x, kv, cfg, layer=cfg.num_layers + g)
            return x, cs

        x, new_caches = jax.lax.scan(
            group,
            x,
            (
                self_stack,
                cache_stack,
                params["cross"],
                caches["cross_kv"],
                jnp.arange(n_groups),
            ),
        )
        caches = {
            **caches,
            "layers": jax.tree.map(
                lambda c: c.reshape((-1,) + c.shape[2:]), new_caches
            ),
        }
    else:
        def body(x, pci):
            p, c, idx = pci
            x, c = _layer_decode(p, x, c, pos, cfg, layer=idx)
            return x, c

        n = jax.tree.leaves(params["layers"])[0].shape[0]
        x, new_caches = jax.lax.scan(
            body, x, (params["layers"], caches["layers"], base + jnp.arange(n))
        )
        caches = {**caches, "layers": new_caches}

    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = (
        cm.unembed(params["embed"], x, cfg)
        if cfg.tie_embeddings
        else cm.dense(params["lm_head"], x, cfg, site="lm_head")
    )
    caches = {**caches, "pos": pos + 1}
    return logits, caches


# ---------------------------------------------------------------------------
# Serving over the paged pool (repro.serving): chunked prefill, paged decode
# ---------------------------------------------------------------------------
def paged_pool_def(cfg: ModelConfig, num_blocks: int, block_size: int, dtype):
    """``(shape, axes, dtype)`` leaves of the paged pool: one cache leaf
    per kind of row, every attention layer stacked in front of
    ``(num_blocks, block_size, ...)``.  GQA pools hold K/V of the scanned
    layers; MLA pools hold the latent ``c_kv`` and ``k_rope`` of every
    layer, the leading dense one first.  Cross-attention memory has no
    paged form."""
    if cfg.cross_attn_every or (cfg.first_dense_layers and not cfg.mla):
        raise ValueError(
            "the paged pool holds the caches of a GQA stack or of an MLA stack "
            "with its leading dense layer; serve cross-attention families "
            "through runtime.serve.LegacyEngine"
        )
    if cfg.mla:
        layer = attn.mla_cache_def(cfg, num_blocks, block_size, dtype)
    else:
        layer = attn.gqa_cache_def(cfg, num_blocks, block_size, dtype)
    n = _n_self(cfg) + (cfg.first_dense_layers if cfg.mla else 0)
    return {
        k: ((n,) + shape, (None,) + axes, dt) for k, (shape, axes, dt) in layer.items()
    }


def _paged_head(params, x, cfg: ModelConfig):
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        return cm.unembed(params["embed"], x, cfg)
    return cm.dense(params["lm_head"], x, cfg, site="lm_head")


def _paged_ffn(p, x, cfg: ModelConfig, idx):
    """The FFN half of a paged block: ``(output, counts)``, counts the
    MoE layer's (expert rows, expert rows computed), ``None`` when dense."""
    h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
    if "router" in p["ffn"]:
        return ffn.moe_layer(p["ffn"], h, cfg, layer=idx)
    return ffn.mlp(p["ffn"], h, cfg, layer=idx), None


def _latent_stack(params, x, pool, cfg: ModelConfig, attend):
    """Run the MLA stack over the paged latent pool: the leading dense
    layer (pool layer 0), then one scan over the rest.  The pool rides the
    scan's carry, each layer reading and writing its own rows in place.
    ``attend(p, h, pool, li)`` is the attention half.  Returns ``(x, pool,
    counts)``, counts summed over the MoE layers (``None`` without any)."""

    def block(p, x, pool, li):
        h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
        a, pool = attend(p["attn"], h, pool, li)
        f, c = _paged_ffn(p, x + a, cfg, li)
        return x + a + f, pool, jnp.zeros((2,), jnp.int32) if c is None else c

    counts = jnp.zeros((2,), jnp.int32)
    if cfg.first_dense_layers:
        x, pool, counts = block(params["first_block"], x, pool, jnp.int32(0))

    def body(carry, inp):
        x, pool, counts = carry
        p, li = inp
        x, pool, c = block(p, x, pool, li)
        return (x, pool, counts + c), None

    n = _stack_len(params["layers"])
    idx = cfg.first_dense_layers + jnp.arange(n, dtype=jnp.int32)
    (x, pool, counts), _ = jax.lax.scan(body, (x, pool, counts), (params["layers"], idx))
    return x, pool, counts if cfg.num_experts else None


def _sum_counts(counts):
    return None if counts is None else counts.sum(axis=0)


def lm_prefill_chunk(
    params,
    tokens: jax.Array,  # (1, tc) — one request's chunk
    kv_pool,  # stacked pool: leaves (layers, num_blocks, bs, ...)
    block_table: jax.Array,  # (W,) int32
    t0: jax.Array,  # scalar int32 — chunk start
    cfg: ModelConfig,
    *,
    t_full: int,  # static total prompt length
    block_size: int,
    with_logits: bool,
):
    """One chunked-prefill step: run chunk tokens ``[t0, t0 + tc)`` of a
    single prompt, scattering each layer's K/V (or MLA latents) into the
    paged pool.  Only the prompt-final chunk pays for the LM head
    (``with_logits``); earlier chunks return ``None`` logits.  Returns
    ``(logits, kv_pool, counts)``: ``counts`` int32 (expert rows, expert
    rows computed) over the MoE layers, ``None`` for a dense stack."""
    tc = tokens.shape[1]
    x = cm.embed(params["embed"], tokens, cfg)
    positions = t0 + jnp.arange(tc, dtype=jnp.int32)

    if cfg.mla:
        def attend(p, h, pool, li):
            return attn.mla_prefill_chunk(
                p, h, pool, li, block_table, t0, cfg,
                t_full=t_full, block_size=block_size, positions=positions, layer=li,
            )

        x, kv_pool, counts = _latent_stack(params, x, kv_pool, cfg, attend)
    else:
        def body(x, inp):
            p, pc, idx = inp
            h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
            a, pc = attn.gqa_prefill_chunk(
                p["attn"], h, pc, block_table, t0, cfg,
                t_full=t_full, block_size=block_size, positions=positions, layer=idx,
            )
            x = x + a
            f, c = _paged_ffn(p, x, cfg, idx)
            return cm.with_logical(x + f, ("batch", "seq_sp", None)), (pc, c)

        n = _stack_len(params["layers"])
        x, (kv_pool, counts) = jax.lax.scan(
            body, x, (params["layers"], kv_pool, jnp.arange(n))
        )
        counts = _sum_counts(counts)
    logits = _paged_head(params, x[:, -1:, :], cfg) if with_logits else None
    return logits, kv_pool, counts


def lm_decode_paged(
    params,
    token: jax.Array,  # (B, 1) int32
    kv_pool,  # stacked pool: leaves (layers, num_blocks, bs, ...)
    block_table: jax.Array,  # (B, W) int32
    pos: jax.Array,  # (B,) int32 — per-request cache length
    active: jax.Array,  # (B,) bool
    trash_blocks: jax.Array,  # (B,) int32
    cfg: ModelConfig,
    *,
    gather_len: int,
    block_size: int,
):
    """One decode step over the paged pool with *per-request* positions —
    the continuous-batching decode: rows mid-prefill or without a live
    request redirect their K/V write to a private trash block and their
    (discarded) output attends only to the zero null block.  Returns
    ``(logits (B, 1, V), kv_pool, counts)`` (counts as in
    :func:`lm_prefill_chunk`)."""
    from repro.serving import kv_cache as kvc

    blocks, offsets = kvc.token_dest(block_table, pos, active, trash_blocks, block_size)
    x = cm.embed(params["embed"], token, cfg)

    if cfg.mla:
        def attend(p, h, pool, li):
            return attn.mla_decode_paged(
                p, h, pool, li, block_table, pos, blocks, offsets, cfg,
                gather_len=gather_len, layer=li,
            )

        x, kv_pool, counts = _latent_stack(params, x, kv_pool, cfg, attend)
    else:
        def body(x, inp):
            p, pc, idx = inp
            h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
            a, pc = attn.gqa_decode_paged(
                p["attn"], h, pc, block_table, pos, blocks, offsets, cfg,
                gather_len=gather_len, layer=idx,
            )
            x = x + a
            f, c = _paged_ffn(p, x, cfg, idx)
            return x + f, (pc, c)

        n = _stack_len(params["layers"])
        x, (kv_pool, counts) = jax.lax.scan(
            body, x, (params["layers"], kv_pool, jnp.arange(n))
        )
        counts = _sum_counts(counts)
    return _paged_head(params, x, cfg), kv_pool, counts


# ---------------------------------------------------------------------------
# Cache shape/axes definitions (for dry-run input_specs)
# ---------------------------------------------------------------------------
def lm_cache_def(cfg: ModelConfig, batch: int, max_seq: int, dtype) -> Dict[str, Any]:
    layer_cache = (
        attn.mla_cache_def(cfg, batch, max_seq, dtype)
        if cfg.mla
        else attn.gqa_cache_def(cfg, batch, max_seq, dtype)
    )
    n_self = _n_self(cfg)
    out: Dict[str, Any] = {
        "layers": {
            k: ((n_self,) + shape, (None,) + axes, dt)
            for k, (shape, axes, dt) in layer_cache.items()
        },
        "pos": ((), (), jnp.int32),
    }
    if cfg.first_dense_layers:
        out["first"] = layer_cache
    if cfg.cross_attn_every:
        n_cross = _n_cross(cfg)
        kv_shape = (n_cross, batch, cfg.vision_seq, cfg.num_kv_heads, cfg.hd)
        axes = (None, "batch", None, "kv_heads", None)
        out["cross_kv"] = ((kv_shape, axes, dtype), (kv_shape, axes, dtype))
    return out
