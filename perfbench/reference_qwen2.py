"""The Qwen2 decoder (arXiv:2407.10671) as the benchmark describes it.

An architecture module: the configuration file's ``"reference"`` key names
it, and ``perfbench/spec.py`` loads it by that path.  It holds everything
of the benchmark that is particular to Qwen2:

* the plain float32 reference and ``served_gaps``, which decide ``correct``;
* the published weight layout (``layout``), which ``perfbench/weights.py``
  draws from the seed, and its map to the program's tree (``to_program``);
* the map from published size keys to the program's ``ModelConfig``
  fields (``SIZE_FIELDS``) and the published keys that shape nothing the
  program takes (``UNSHAPED``), which ``perfbench/model.py`` checks;
* the work counts (``gemm_sites``, ``attention_widths``, ``unembed_shape``)
  that ``perfbench/counts.py`` turns into operations and bytes.

The reference is the published architecture, in straightforward
``jax.numpy``: token embedding; per layer RMSNorm, Q/K/V projections with
bias, rotary position embedding on half-split head dimensions,
grouped-query causal attention, output projection, RMSNorm, SwiGLU MLP
(down(silu(gate) * up)); final RMSNorm and the unembedding tied to the
embedding table.  Every product runs in float32 at ``Precision.HIGHEST``,
with no kernel, cache, batching or quantization.  The module imports
nothing of the program: the weights come from ``perfbench/weights.py`` at
the run's seed.

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


# ---------------------------------------------------------------------------
# The published configuration and the program's
# ---------------------------------------------------------------------------
# Published key -> the program's ModelConfig field.
SIZE_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

# Published keys that shape nothing the program takes: names and types of
# the checkpoint, token ids and an initializer (the prompts and weights are
# the benchmark's own draws), the context limit (the mix sets ``max_seq``),
# and settings whose published values the program and the reference both
# implement (SiLU, no dropout, no sliding window).
UNSHAPED = (
    "architectures",
    "model_type",
    "torch_dtype",
    "use_cache",
    "bos_token_id",
    "eos_token_id",
    "initializer_range",
    "max_position_embeddings",
    "hidden_act",
    "attention_dropout",
    "use_sliding_window",
    "sliding_window",
    "max_window_layers",
)


def head_dim(sizes: dict) -> int:
    return sizes.get("head_dim") or sizes["hidden_size"] // sizes["num_attention_heads"]


def layout(sizes: dict) -> dict:
    """The published weights in draw order: name -> (shape, init), with
    init "embed" (0.02 N), "fan:<n>" (N / sqrt n), "bias" (0.1 N) or
    "norm" (1 + 0.1 N).  Layer weights are stacked on a leading axis."""
    L, d = sizes["num_hidden_layers"], sizes["hidden_size"]
    hq = sizes["num_attention_heads"] * head_dim(sizes)
    hkv = sizes["num_key_value_heads"] * head_dim(sizes)
    f, v = sizes["intermediate_size"], sizes["vocab_size"]
    return {
        "embed": ((v, d), "embed"),
        "final_norm": ((d,), "norm"),
        "in_norm": ((L, d), "norm"),
        "post_norm": ((L, d), "norm"),
        "q_w": ((L, d, hq), f"fan:{d}"),
        "q_b": ((L, hq), "bias"),
        "k_w": ((L, d, hkv), f"fan:{d}"),
        "k_b": ((L, hkv), "bias"),
        "v_w": ((L, d, hkv), f"fan:{d}"),
        "v_b": ((L, hkv), "bias"),
        "o_w": ((L, hq, d), f"fan:{hq}"),
        "gate_w": ((L, d, f), f"fan:{d}"),
        "up_w": ((L, d, f), f"fan:{d}"),
        "down_w": ((L, f, d), f"fan:{f}"),
    }


def to_program(w: dict) -> dict:
    """Published layout -> the program's tree (``models/lm.py``): the
    SwiGLU input projection is one ``wi`` bank, up columns then gate."""
    return {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "ln1": {"scale": w["in_norm"]},
            "ln2": {"scale": w["post_norm"]},
            "attn": {
                "wq": {"w": w["q_w"], "b": w["q_b"]},
                "wk": {"w": w["k_w"], "b": w["k_b"]},
                "wv": {"w": w["v_w"], "b": w["v_b"]},
                "wo": {"w": w["o_w"]},
            },
            "ffn": {
                "wi": {"w": jnp.concatenate([w["up_w"], w["gate_w"]], axis=-1)},
                "wo": {"w": w["down_w"]},
            },
        },
    }


# ---------------------------------------------------------------------------
# The work, for ``perfbench/counts.py``
# ---------------------------------------------------------------------------
def gemm_sites(sizes: dict) -> list:
    """(site, K, C, passes, banks, layers) of every GEMM that runs through
    the photonic kernel: Q, K, V, O, the fused gate/up and down
    projections, once per token row, each one weight bank, in every layer."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    hq = sizes["num_attention_heads"] * head_dim(sizes)
    hkv = sizes["num_key_value_heads"] * head_dim(sizes)
    L = sizes["num_hidden_layers"]
    return [
        ("attn.wq", d, hq, 1, 1, L),
        ("attn.wk", d, hkv, 1, 1, L),
        ("attn.wv", d, hkv, 1, 1, L),
        ("attn.wo", hq, d, 1, 1, L),
        ("ffn.wi", d, 2 * f, 1, 1, L),
        ("ffn.wo", f, d, 1, 1, L),
    ]


def attention_widths(sizes: dict) -> list:
    """(QK width, PV width, layers): one query token's Q.K and P.V
    contract query heads x head dim against each position it attends."""
    hq = sizes["num_attention_heads"] * head_dim(sizes)
    return [(hq, hq, sizes["num_hidden_layers"])]


def unembed_shape(sizes: dict) -> tuple:
    """(K, C) of the tied unembedding, which the program does not route
    through the photonic kernel."""
    return sizes["hidden_size"], sizes["vocab_size"]
