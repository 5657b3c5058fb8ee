"""Plain float32 reference of the Qwen2 decoder (arXiv:2407.10671).

The published architecture, in straightforward ``jax.numpy``: token
embedding; per layer RMSNorm, Q/K/V projections with bias, rotary
position embedding on half-split head dimensions, grouped-query causal
attention, output projection, RMSNorm, SwiGLU MLP (down(silu(gate) * up));
final RMSNorm and the unembedding tied to the embedding table.  Every
product runs in float32 at ``Precision.HIGHEST``, with no kernel, cache,
batching or quantization.  It imports nothing of the program: the weights
come from ``perfbench/weights.py`` at the run's seed.

``gemm_bits`` is the control: the reference with every projection's
operands rounded to that many bits (per-tensor activations, per-column
weights), which is what a cheaper datapath would do.  The unembedding
stays float32, as the program's is not routed through the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 256  # sequences are padded to a multiple of this: few compiled shapes


def _quantize(x, bits: int, axis):
    qmax = float(2 ** (bits - 1) - 1)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


def _proj(x, w, bits: Optional[int]):
    if bits is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    xq, sx = _quantize(x, bits, None)
    wq, sw = _quantize(w, bits, 0)
    return jnp.matmul(xq, wq, precision=HIGHEST) * sx * sw


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta: float):
    """x: (T, heads, hd); rotate the two halves of each head."""
    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("sizes_items", "bits"))
def _layer(x, lw, *, sizes_items, bits):
    s = dict(sizes_items)
    h, kv = s["num_attention_heads"], s["num_key_value_heads"]
    hd = s["hidden_size"] // h
    eps, theta = s["rms_norm_eps"], s["rope_theta"]
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    t = x.shape[0]

    y = _rmsnorm(x, lw["in_norm"], eps)
    q = (_proj(y, lw["q_w"], bits) + lw["q_b"]).reshape(t, h, hd)
    k = (_proj(y, lw["k_w"], bits) + lw["k_b"]).reshape(t, kv, hd)
    v = (_proj(y, lw["v_w"], bits) + lw["v_b"]).reshape(t, kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    group = h // kv  # query head i reads key/value head i // group
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST).reshape(t, h * hd)
    x = x + _proj(a, lw["o_w"], bits)

    y = _rmsnorm(x, lw["post_norm"], eps)
    g = jax.nn.silu(_proj(y, lw["gate_w"], bits))
    x = x + _proj(g * _proj(y, lw["up_w"], bits), lw["down_w"], bits)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, embed, *, eps):
    y = _rmsnorm(x, final_norm.astype(jnp.float32), eps)
    return jnp.matmul(y, embed.astype(jnp.float32).T, precision=HIGHEST)


def _items(sizes: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in sizes.items() if not isinstance(v, (dict, list))))


def logits(w: dict, tokens: np.ndarray, sizes: dict, start: int, bits=None) -> jax.Array:
    """Float32 logits at positions ``start .. len(tokens) - 1``: the row at
    position ``i`` scores the token that follows ``tokens[: i + 1]``."""
    n = len(tokens)
    padded = np.zeros(-(-n // PAD) * PAD, np.int32)
    padded[:n] = tokens  # causal: the padding after position n-1 is never read
    x = w["embed"][jnp.asarray(padded)].astype(jnp.float32)
    layer_names = [k for k in w if k not in ("embed", "final_norm")]
    items = _items(sizes)
    for i in range(sizes["num_hidden_layers"]):
        lw = {k: w[k][i] for k in layer_names}
        x = _layer(x, lw, sizes_items=items, bits=bits)
    return _head(x[start:n], w["final_norm"], w["embed"], eps=sizes["rms_norm_eps"])


def served_gaps(w, prompt, served, sizes, control_bits: Optional[int] = None) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position.  With ``control_bits`` the token
    judged is not the served one but the one the control (the reference
    at that many bits) puts first at the same position, over the same
    prompt and served tokens."""
    tokens = np.concatenate([np.asarray(prompt, np.int32), np.asarray(served[:-1], np.int32)])
    ref = logits(w, tokens, sizes, len(prompt) - 1)
    if control_bits is None:
        pick = jnp.asarray(np.asarray(served, np.int32))
    else:
        pick = jnp.argmax(logits(w, tokens, sizes, len(prompt) - 1, control_bits), axis=-1)
    best = jnp.max(ref, axis=-1)
    chosen = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
    return np.asarray(best - chosen, np.float64)
