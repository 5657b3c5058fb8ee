"""Feed-forward layers: dense MLP (SwiGLU / GELU) and top-k MoE.

The MoE is dropless: the (token, pick) pairs routed to the experts a chip
holds are sorted by expert into row tiles and run through one grouped GEMM
per site, so no token is dropped whatever the skew.  A layer holds
``num_experts`` of the router's ``num_experts * ep_size`` experts (expert
parallelism over ``ep_size`` chips; 1 holds them all) and computes their
part of the result alone; shared experts (DeepSeek-V2) are a plain dense
MLP.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import common as cm
from repro.models.common import P, ModelConfig, dense, dense_def
from repro.photonic import EpilogueSpec


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------
def mlp_def(cfg: ModelConfig, d_ff: int | None = None) -> Dict[str, Any]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.ffn_act == "swiglu":
        return {
            "wi": dense_def(d, 2 * f, (None, "d_ff")),
            "wo": dense_def(f, d, ("d_ff", None)),
        }
    return {
        "wi": dense_def(d, f, (None, "d_ff")),
        "wo": dense_def(f, d, ("d_ff", None)),
    }


def mlp(params, x: jax.Array, cfg: ModelConfig, layer=None, site="ffn") -> jax.Array:
    if cfg.ffn_act == "swiglu":
        # swiglu splits the GEMM output before gating, so the activation
        # cannot ride the fused epilogue (it is not per-column).
        h = dense(params["wi"], x, cfg, site=f"{site}.wi", layer=layer)
        u, g = jnp.split(h, 2, axis=-1)
        h = u * jax.nn.silu(g)
    else:
        # gelu is elementwise on the GEMM output — fused into the engine
        # epilogue (DESIGN.md §14); digital fallback applies the same op.
        h = dense(
            params["wi"], x, cfg, site=f"{site}.wi", layer=layer,
            epilogue=EpilogueSpec(activation="gelu"),
        )
    return dense(params["wo"], h, cfg, site=f"{site}.wo", layer=layer)


# ---------------------------------------------------------------------------
# Mixture of Experts: dropless, over the experts this chip holds
# ---------------------------------------------------------------------------
def moe_def(cfg: ModelConfig) -> Dict[str, Any]:
    """The router scores all ``num_experts * ep_size`` experts; the layer
    holds ``num_experts`` of them (experts ``0 .. num_experts - 1``), each
    a SwiGLU bank stacked on a leading expert axis."""
    d, f, e = cfg.d_model, cfg.moe_hidden, cfg.num_experts
    defs: Dict[str, Any] = {
        "router": dense_def(d, e * cfg.ep_size, (None, None), init="normal"),
        "experts": {
            "wi": {"w": P((e, d, 2 * f), ("experts", None, None), fan_axis=1)},
            "wo": {"w": P((e, f, d), ("experts", None, None), fan_axis=1)},
        },
    }
    if cfg.num_shared_experts:
        defs["shared"] = mlp_def(cfg, cfg.num_shared_experts * f)
    return defs


def route(params, x: jax.Array, cfg: ModelConfig, layer=None):
    """``(gates, top-k weights, top-k experts)`` of token rows ``x`` (N, D)
    over all ``num_experts * ep_size`` experts: softmax scores, greedy
    top-k, the weights renormalized only with ``norm_topk_prob``, then
    scaled by ``routed_scaling_factor``.

    The router projection carries the site name ``"ffn.router"``: under
    the default :class:`repro.photonic.SitePolicy` it runs *digitally*, in
    float32, even when every other weight GEMM is photonic: expert
    selection is control flow, and analog noise on near-uniform router
    logits flips top-k membership.
    """
    logits = dense(
        params["router"], x.astype(jnp.float32), cfg, site="ffn.router", layer=layer
    )
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    return gates, topv * cfg.routed_scaling_factor, topi


def row_tile(n_tokens: int, cfg: ModelConfig) -> int:
    """Rows per tile of the grouped expert GEMM for ``n_tokens`` token rows:
    the power of two at or above the rows an expert gets on average,
    between 8 and 128.  Each held expert's rows pad to whole tiles."""
    mean = n_tokens * cfg.num_experts_per_tok / (cfg.num_experts * cfg.ep_size)
    return int(min(128, max(8, 1 << max(0, math.ceil(math.log2(max(mean, 1.0)))))))


def _dispatch(topi: jax.Array, held: int, tile_r: int):
    """Sort the (token, pick) pairs routed to held experts by expert into
    row tiles of ``tile_r``, each expert's rows padded to whole tiles.

    Returns ``(dest, row_token, tile_expert, n_tiles, rows)``: each pair's
    row in the buffer (``M``, one past the end, for a pair whose expert is
    not held), each buffer row's token (``N`` for padding), each tile's
    expert, the tiles in use and the pairs routed to held experts.  ``M``
    holds every token routed to ``min(k, held)`` held experts plus a
    partial tile per expert, so no pair is ever dropped."""
    n, k = topi.shape
    m = -(-(n * min(k, held) + held * (tile_r - 1)) // tile_r) * tile_r
    e = topi.reshape(-1)
    key = jnp.where(e < held, e, held)
    order = jnp.argsort(key, stable=True)
    counts = jnp.bincount(key, length=held + 1)[:held]
    padded = -(-counts // tile_r) * tile_r
    first_row = jnp.cumsum(padded) - padded  # each expert's first buffer row
    first_pair = jnp.cumsum(counts) - counts  # ... and first sorted pair
    ks = key[order]
    rank = jnp.arange(n * k) - first_pair[jnp.minimum(ks, held - 1)]
    dest_sorted = jnp.where(ks < held, first_row[jnp.minimum(ks, held - 1)] + rank, m)
    dest = jnp.zeros((n * k,), jnp.int32).at[order].set(dest_sorted.astype(jnp.int32))
    row_token = (
        jnp.full((m,), n, jnp.int32)
        .at[dest]
        .set(jnp.arange(n * k, dtype=jnp.int32) // k, mode="drop")
    )
    ends = jnp.cumsum(padded) // tile_r  # tiles up to and including expert e
    tiles = jnp.arange(m // tile_r)
    tile_expert = jnp.minimum(jnp.searchsorted(ends, tiles, side="right"), held - 1)
    n_tiles = jnp.maximum(ends[-1], 1).astype(jnp.int32)
    return dest, row_token, tile_expert.astype(jnp.int32), n_tiles, counts.sum()


def held_experts(params, x: jax.Array, cfg: ModelConfig, layer=None):
    """Routed part and counts of the MoE layer over token rows ``x``
    (N, D): every token's picks among the experts held here, through one
    grouped GEMM per site (``ffn.experts.wi``, ``ffn.experts.wo``), added
    back with their weights.  No token is dropped, whatever the skew; the
    picks of experts not held here add nothing.

    Returns ``(out (N, D), gates, topi, counts)`` with ``counts`` int32
    ``(expert rows, expert rows computed)``: the pairs routed to held
    experts, and the rows the grouped GEMM computed, padding included."""
    n, d = x.shape
    held, k = cfg.num_experts, cfg.num_experts_per_tok
    gates, topv, topi = route(params, x, cfg, layer)
    tile_r = row_tile(n, cfg)
    dest, row_token, tile_expert, n_tiles, rows = _dispatch(topi, held, tile_r)
    used = (row_token < n)[:, None]
    xb = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[row_token]
    ex = params["experts"]
    h = cm.grouped_dense(
        ex["wi"], xb, cfg, tile_expert, n_tiles, tile_r=tile_r, site="ffn.experts.wi"
    )
    u, g = jnp.split(h, 2, axis=-1)
    # Rows past the tiles in use are undefined: zero them before they can
    # reach the next GEMM's activation scale.
    h = jnp.where(used, u * jax.nn.silu(g), 0).astype(x.dtype)
    y = cm.grouped_dense(
        ex["wo"], h, cfg, tile_expert, n_tiles, tile_r=tile_r, site="ffn.experts.wo"
    )
    y = jnp.concatenate([jnp.where(used, y, 0), jnp.zeros((1, d), y.dtype)])
    picked = y[dest].reshape(n, k, d).astype(jnp.float32)
    out = jnp.einsum("nk,nkd->nd", topv, picked, precision=jax.lax.Precision.HIGHEST)
    counts = jnp.stack([rows, n_tiles * tile_r]).astype(jnp.int32)
    return out.astype(x.dtype), gates, topi, counts


def _moe(params, x: jax.Array, cfg: ModelConfig, layer=None):
    """The MoE layer over ``x`` (B, T, D): the held experts' part plus the
    shared experts; ``held_experts``' gates, picks and counts beside it."""
    b, t, d = x.shape
    out, gates, topi, counts = held_experts(params, x.reshape(b * t, d), cfg, layer)
    out = out.reshape(b, t, d)
    if cfg.num_shared_experts:
        out = out + mlp(params["shared"], x, cfg, layer=layer, site="ffn.shared")
    return out, gates, topi, counts


def moe_layer(params, x: jax.Array, cfg: ModelConfig, layer=None):
    """``(output, counts)`` of the MoE layer, for serving."""
    out, _, _, counts = _moe(params, x, cfg, layer)
    return out, counts


def moe(params, x: jax.Array, cfg: ModelConfig, layer=None) -> Tuple[
    jax.Array, jax.Array
]:
    """Returns (output, aux load-balancing loss) for training.

    Load balance is Switch-style over the router's experts, E * sum_e f_e
    * p_e, with f_e the share of tokens whose first pick is e."""
    out, gates, topi, _ = _moe(params, x, cfg, layer)
    e = cfg.num_experts * cfg.ep_size
    frac = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
    prob = jnp.mean(gates, axis=0)
    return out, e * jnp.sum(frac * prob)
