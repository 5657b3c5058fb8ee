"""DeepSeek-V2-Lite (arXiv:2405.04434) as the benchmark describes it.

An architecture module: the configuration file's ``"reference"`` key names
it, and ``perfbench/spec.py`` loads it by that path.  It holds everything
of the benchmark that is particular to DeepSeek-V2:

* the plain float32 reference and ``served_gaps``, which decide ``correct``;
* the published weight layout (``layout``), which ``perfbench/weights.py``
  draws from the seed, and its map to the program's tree (``to_program``);
* the map from published size keys to the program's ``ModelConfig``
  fields (``SIZE_FIELDS``) and the published keys that shape nothing the
  program takes (``UNSHAPED``);
* the work counts (``gemm_sites``, ``attention_widths``, ``unembed_shape``).

The reference follows the published model code (``modeling_deepseek.py``
of the source) in straightforward ``jax.numpy``, float32 at
``Precision.HIGHEST``, with no kernel, cache, batching or quantization:

* token embedding; per layer RMSNorm, latent attention, RMSNorm, FFN;
  final RMSNorm and an untied head;
* attention without query compression: ``q = x W_q`` split per head into
  nope and rope parts; ``[c_kv, k_pe] = x W_kv_a``; ``c_kv`` RMSNorm'd
  (``kv_a_layernorm``), then ``W_kv_b`` gives each head's ``k_nope`` and
  ``v``; rotary embedding on ``q_pe`` and the shared ``k_pe`` in the
  published interleaved layout (pairs ``(2i, 2i + 1)`` rotate together),
  with YaRN's inverse frequencies; softmax scale ``(nope + rope) ** -0.5``
  times ``mscale(factor, mscale_all_dim) ** 2``; causal attention, ``W_o``;
* layer 0 (``first_k_dense_replace``) a SwiGLU MLP, ``down(silu(gate) *
  up)``; every later layer a mixture of experts: a float32 softmax router
  over all ``n_routed_experts * ep_size`` experts, greedy top-k, the
  weights renormalised only with ``norm_topk_prob`` and otherwise scaled
  by ``routed_scaling_factor``; each token's held picks ``sum_j w_j
  E_j(x)``, plus the shared experts (one SwiGLU of ``n_shared_experts *
  moe_intermediate_size``).

Where the program departs from these equations:

* Expert parallelism: the configuration holds ``n_routed_experts`` of the
  router's ``n_routed_experts * ep_size`` experts, the chip's share.  The
  program and this reference alike compute the held experts' part and
  leave the rest out (the model-configs guide's cut); the sum over all
  shares, with the shared experts once, is the uncut layer.
* The program applies rope to half-split dimensions; ``to_program``
  permutes the rope columns of ``W_q`` and ``W_kv_a`` so that its half
  ``i`` and ``rope/2 + i`` are the published pair ``(2i, 2i + 1)``.
* The program runs in bf16 with int8 photonic GEMMs (the router in
  float32, digitally), and decodes in the absorbed form: ``W_kv_b`` folds
  into the query and the output, in float32 einsums.  The tolerance of
  the check covers these.

``gemm_bits`` (``control_bits``) is the control: every projection's
operands rounded to that many bits (per-tensor activations, per-column
weights), the router and the head left in float32, as the program's are
not routed through the kernel.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 256  # sequences are padded to a multiple of this: few compiled shapes

# Values the program implements for published keys it takes no field for.
_HIDDEN_ACT = "silu"


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


def _swiglu(x, gate, up, down, bits):
    return _proj(jax.nn.silu(_proj(x, gate, bits)) * _proj(x, up, bits), down, bits)


# ---------------------------------------------------------------------------
# YaRN, as the published rotary embedding computes it
# ---------------------------------------------------------------------------
def _yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(sizes: dict) -> np.ndarray:
    """Inverse frequencies of the rope dimensions: YaRN's blend of the
    interpolated (÷ factor) and the original frequencies, ramped between
    the correction dimensions of ``beta_fast`` and ``beta_slow`` rotations
    over the original context."""
    dim, base = sizes["qk_rope_head_dim"], sizes["rope_theta"]
    rs = sizes["rope_scaling"]
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / (base**exps)
    inter = 1.0 / (rs["factor"] * base**exps)
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(sizes: dict) -> float:
    rs = sizes["rope_scaling"]
    m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    if _yarn_mscale(rs["factor"], rs["mscale"]) / m != 1.0:
        raise ValueError("a rotary cos/sin factor other than 1 is not implemented")
    return (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, inv_freq):
    """x: (T, heads, rope) in the published interleaved layout: pairs
    ``(2i, 2i + 1)`` are gathered into halves, then rotated."""
    t, h, d = x.shape
    x = x.reshape(t, h, d // 2, 2).transpose(0, 1, 3, 2).reshape(t, h, d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([ang, ang], axis=-1)
    cos, sin = jnp.cos(emb)[:, None, :], jnp.sin(emb)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)
    return x * cos + rot * sin


# ---------------------------------------------------------------------------
# The reference
# ---------------------------------------------------------------------------
def _attention(x, lw, s, bits, inv_freq):
    h = s["num_attention_heads"]
    nope, rope, vd = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    r, eps = s["kv_lora_rank"], s["rms_norm_eps"]
    t = x.shape[0]
    y = _rmsnorm(x, lw["attn_norm"], eps)
    q = _proj(y, lw["q_w"], bits).reshape(t, h, nope + rope)
    kv_a = _proj(y, lw["kv_a_w"], bits)
    c = _rmsnorm(kv_a[:, :r], lw["kv_a_norm"], eps)
    k_pe = _rope(kv_a[:, r:].reshape(t, 1, rope), inv_freq)
    kv = _proj(c, lw["kv_b_w"], bits).reshape(t, h, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv_freq)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (t, h, rope))], axis=-1)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * softmax_scale(s)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", p, kv[..., nope:], precision=HIGHEST)
    return x + _proj(a.reshape(t, h * vd), lw["o_w"], bits)


def _moe(y, lw, s, bits):
    """The held experts' part of the routed sum, plus the shared experts."""
    held = s["n_routed_experts"]
    logits = jnp.matmul(y, lw["router_w"], precision=HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(scores, s["num_experts_per_tok"])
    if s["norm_topk_prob"]:
        topv = topv / (topv.sum(-1, keepdims=True) + 1e-20)
    else:
        topv = topv * s["routed_scaling_factor"]
    out = _swiglu(y, lw["shared_gate_w"], lw["shared_up_w"], lw["shared_down_w"], bits)
    for e in range(held):
        weight = jnp.sum(jnp.where(topi == e, topv, 0.0), axis=-1, keepdims=True)
        ye = _swiglu(
            y, lw["expert_gate_w"][e], lw["expert_up_w"][e], lw["expert_down_w"][e], bits
        )
        out = out + weight * ye
    return out


@functools.partial(jax.jit, static_argnames=("sizes_items", "bits", "moe"))
def _layer(x, lw, inv_freq, *, sizes_items, bits, moe):
    s = _static(sizes_items)
    lw = jax.tree.map(lambda a: a.astype(jnp.float32), lw)
    x = _attention(x, lw, s, bits, inv_freq)
    y = _rmsnorm(x, lw["ffn_norm"], s["rms_norm_eps"])
    if moe:
        return x + _moe(y, lw, s, bits)
    return x + _swiglu(y, lw["dense_gate_w"], lw["dense_up_w"], lw["dense_down_w"], bits)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, *, eps):
    y = _rmsnorm(x, final_norm.astype(jnp.float32), eps)
    return jnp.matmul(y, head.astype(jnp.float32), precision=HIGHEST)


def _items(sizes: dict) -> tuple:
    """The flat sizes plus the YaRN group, hashable, for the jitted layer."""
    flat = {k: v for k, v in sizes.items() if not isinstance(v, (dict, list))}
    flat["rope_scaling"] = tuple(sorted(sizes["rope_scaling"].items()))
    return tuple(sorted(flat.items()))


def _static(items: tuple) -> dict:
    s = dict(items)
    s["rope_scaling"] = dict(s["rope_scaling"])
    return s


def logits(w: dict, tokens: np.ndarray, sizes: dict, start: int, bits=None) -> jax.Array:
    """Float32 logits at positions ``start .. len(tokens) - 1``: the row at
    position ``i`` scores the token that follows ``tokens[: i + 1]``.
    Computed a layer at a time, so only one layer's weights are float32."""
    _check(sizes)
    n = len(tokens)
    padded = np.zeros(-(-n // PAD) * PAD, np.int32)
    padded[:n] = tokens  # causal: the padding after position n-1 is never read
    x = w["embed"][jnp.asarray(padded)].astype(jnp.float32)
    items = _items(sizes)
    inv_freq = jnp.asarray(yarn_inv_freq(sizes))
    dense = sizes["first_k_dense_replace"]
    attn_names = ("attn_norm", "q_w", "kv_a_w", "kv_a_norm", "kv_b_w", "o_w", "ffn_norm")
    moe_names = [k for k in w if k.startswith(("router", "expert_", "shared_"))]
    for i in range(sizes["num_hidden_layers"]):
        lw = {k: w[k][i] for k in attn_names}
        if i < dense:
            lw.update({k: w[k] for k in ("dense_gate_w", "dense_up_w", "dense_down_w")})
        else:
            lw.update({k: w[k][i - dense] for k in moe_names})
        x = _layer(x, lw, inv_freq, sizes_items=items, bits=bits, moe=i >= dense)
    head = w["embed"].T if sizes["tie_word_embeddings"] else w["lm_head"]
    return _head(x[start:n], w["final_norm"], head, eps=sizes["rms_norm_eps"])


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
# Published key -> the program's ModelConfig field.  ``ep_size`` is the
# deployment's: the chips that share each MoE layer, whose router scores
# ``n_routed_experts * ep_size`` experts.  Keys whose published value is
# the only one the program implements map to fields that refuse others
# (``scoring_func``, ``topk_method``, ``moe_layer_freq``, ``q_lora_rank``,
# ``attention_bias``, ``first_k_dense_replace``, ``rope_scaling``).
SIZE_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rope_scaling": "rope_scaling",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attention_bias": "qkv_bias",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "first_k_dense_replace": "first_dense_layers",
    "moe_layer_freq": "moe_layer_freq",
    "moe_intermediate_size": "moe_d_ff",
    "n_routed_experts": "num_experts",
    "ep_size": "ep_size",
    "n_shared_experts": "num_shared_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "norm_topk_prob": "norm_topk_prob",
    "routed_scaling_factor": "routed_scaling_factor",
    "scoring_func": "router_scoring",
    "topk_method": "router_topk",
}

# Published keys that shape nothing the program takes: the checkpoint's
# type name, the context limit (the mix sets ``max_seq``; YaRN reads its
# own ``original_max_position_embeddings``), the training-only sequence-wise
# auxiliary loss, the group counts of a top-k method other than greedy
# (``topk_method`` refuses those), and the activation, which ``_check``
# holds to SiLU, the program's SwiGLU.
UNSHAPED = (
    "model_type",
    "max_position_embeddings",
    "seq_aux",
    "n_group",
    "topk_group",
    "hidden_act",
)


def _check(sizes: dict) -> None:
    if sizes.get("hidden_act", _HIDDEN_ACT) != _HIDDEN_ACT:
        raise ValueError(f"hidden_act={sizes['hidden_act']!r}: the program runs SiLU")
    if sizes["first_k_dense_replace"] != 1 or sizes.get("moe_layer_freq", 1) != 1:
        raise ValueError("one leading dense layer, then MoE in every layer, is implemented")


def layout(sizes: dict) -> dict:
    """The published weights in draw order: name -> (shape, init), with
    init "embed" (0.02 N), "fan:<n>" (N / sqrt n) or "norm" (1 + 0.1 N);
    projections as (in, out).  Attention weights are stacked over every
    layer, the dense FFN is layer 0's, and the MoE weights are stacked
    over the later layers, the held experts on a second axis."""
    _check(sizes)
    L, d, v = sizes["num_hidden_layers"], sizes["hidden_size"], sizes["vocab_size"]
    h = sizes["num_attention_heads"]
    nope, rope, vd = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    r, f = sizes["kv_lora_rank"], sizes["intermediate_size"]
    lm = L - sizes["first_k_dense_replace"]
    held, fe = sizes["n_routed_experts"], sizes["moe_intermediate_size"]
    routed = held * sizes["ep_size"]
    fs = fe * sizes["n_shared_experts"]
    out = {
        "embed": ((v, d), "embed"),
        "final_norm": ((d,), "norm"),
        "attn_norm": ((L, d), "norm"),
        "q_w": ((L, d, h * (nope + rope)), f"fan:{d}"),
        "kv_a_w": ((L, d, r + rope), f"fan:{d}"),
        "kv_a_norm": ((L, r), "norm"),
        "kv_b_w": ((L, r, h * (nope + vd)), f"fan:{r}"),
        "o_w": ((L, h * vd, d), f"fan:{h * vd}"),
        "ffn_norm": ((L, d), "norm"),
        "dense_gate_w": ((d, f), f"fan:{d}"),
        "dense_up_w": ((d, f), f"fan:{d}"),
        "dense_down_w": ((f, d), f"fan:{f}"),
        "router_w": ((lm, d, routed), f"fan:{d}"),
        "expert_gate_w": ((lm, held, d, fe), f"fan:{d}"),
        "expert_up_w": ((lm, held, d, fe), f"fan:{d}"),
        "expert_down_w": ((lm, held, fe, d), f"fan:{fe}"),
        "shared_gate_w": ((lm, d, fs), f"fan:{d}"),
        "shared_up_w": ((lm, d, fs), f"fan:{d}"),
        "shared_down_w": ((lm, fs, d), f"fan:{fs}"),
    }
    if not sizes["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), f"fan:{d}")
    return out


def rope_order(rope: int) -> np.ndarray:
    """Published rope column of each program column: the program's halves
    ``i`` and ``rope/2 + i`` are the published pair ``(2i, 2i + 1)``."""
    return np.concatenate([np.arange(0, rope, 2), np.arange(1, rope, 2)])


def to_program(w: dict) -> dict:
    """Published layout -> the program's tree (``models/lm.py``, MLA): rope
    columns permuted to half-split order, ``W_kv_b`` split into ``wuk`` /
    ``wuv`` by head part, each SwiGLU input one ``wi`` bank (up columns
    then gate).  Widths are read off the weights' shapes."""
    r = w["kv_a_norm"].shape[-1]
    rope = w["kv_a_w"].shape[-1] - r
    hv = w["o_w"].shape[-2]  # heads x v_head_dim
    h = (w["q_w"].shape[-1] - w["kv_b_w"].shape[-1] + hv) // rope
    nope = (w["kv_b_w"].shape[-1] - hv) // h
    order = rope_order(rope)
    q = w["q_w"].reshape(*w["q_w"].shape[:-1], h, nope + rope)
    q = jnp.concatenate([q[..., :nope], q[..., nope + order]], axis=-1)
    kv_a = jnp.concatenate([w["kv_a_w"][..., :r], w["kv_a_w"][..., r + order]], axis=-1)
    kv_b = w["kv_b_w"].reshape(*w["kv_b_w"].shape[:-1], h, -1)

    def attn(sl):
        return {
            "wq": {"w": q[sl].reshape(*q[sl].shape[:-2], -1)},
            "wdkv": {"w": kv_a[sl]},
            "kv_norm": {"scale": w["kv_a_norm"][sl]},
            "wuk": {"w": kv_b[sl][..., :nope].reshape(*kv_b[sl].shape[:-2], -1)},
            "wuv": {"w": kv_b[sl][..., nope:].reshape(*kv_b[sl].shape[:-2], -1)},
            "wo": {"w": w["o_w"][sl]},
        }

    def swiglu(gate, up, down):
        return {"wi": {"w": jnp.concatenate([up, gate], axis=-1)}, "wo": {"w": down}}

    first, rest = 0, slice(1, None)
    out = {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "first_block": {
            "ln1": {"scale": w["attn_norm"][first]},
            "ln2": {"scale": w["ffn_norm"][first]},
            "attn": attn(first),
            "ffn": swiglu(w["dense_gate_w"], w["dense_up_w"], w["dense_down_w"]),
        },
        "layers": {
            "ln1": {"scale": w["attn_norm"][rest]},
            "ln2": {"scale": w["ffn_norm"][rest]},
            "attn": attn(rest),
            "ffn": {
                "router": {"w": w["router_w"]},
                "experts": swiglu(w["expert_gate_w"], w["expert_up_w"], w["expert_down_w"]),
                "shared": swiglu(w["shared_gate_w"], w["shared_up_w"], w["shared_down_w"]),
            },
        },
    }
    if "lm_head" in w:
        out["lm_head"] = {"w": w["lm_head"]}
    return out


# ---------------------------------------------------------------------------
# The work, for ``perfbench/counts.py``
# ---------------------------------------------------------------------------
def gemm_sites(sizes: dict) -> list:
    """(site, K, C, passes, banks, layers) of every GEMM that the photonic
    kernel runs in both the prefill and the decode program.  The held
    experts' banks: a token row passes through ``num_experts_per_tok *
    n_routed_experts / (n_routed_experts * ep_size)`` of them on average
    (6 x 8/64 = 0.75), and a call reads all ``n_routed_experts``.  Left
    out: ``W_kv_b`` (``wuk`` / ``wuv``), which prefill runs through the
    kernel but decode, absorbed, runs as float einsums, so the share of
    roofline reads low in prefill and never above the truth; the router
    and the head, which run digitally."""
    d, L = sizes["hidden_size"], sizes["num_hidden_layers"]
    h = sizes["num_attention_heads"]
    nope, rope, vd = sizes["qk_nope_head_dim"], sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    r, f = sizes["kv_lora_rank"], sizes["intermediate_size"]
    dense = sizes["first_k_dense_replace"]
    held, fe, k = sizes["n_routed_experts"], sizes["moe_intermediate_size"], sizes["num_experts_per_tok"]
    passes = k * held / (held * sizes["ep_size"])
    fs = fe * sizes["n_shared_experts"]
    return [
        ("attn.wq", d, h * (nope + rope), 1, 1, L),
        ("attn.wdkv", d, r + rope, 1, 1, L),
        ("attn.wo", h * vd, d, 1, 1, L),
        ("ffn.wi", d, 2 * f, 1, 1, dense),
        ("ffn.wo", f, d, 1, 1, dense),
        ("ffn.shared.wi", d, 2 * fs, 1, 1, L - dense),
        ("ffn.shared.wo", fs, d, 1, 1, L - dense),
        ("ffn.experts.wi", d, 2 * fe, passes, held, L - dense),
        ("ffn.experts.wo", fe, d, passes, held, L - dense),
    ]


def attention_widths(sizes: dict) -> list:
    """(QK width, PV width, layers): a query token contracts heads x (nope
    + rope) against each key and heads x v_head_dim against each value."""
    h = sizes["num_attention_heads"]
    qk = h * (sizes["qk_nope_head_dim"] + sizes["qk_rope_head_dim"])
    return [(qk, h * sizes["v_head_dim"], sizes["num_hidden_layers"])]


def unembed_shape(sizes: dict) -> tuple:
    """(K, C) of the head, which the program does not route through the
    photonic kernel."""
    return sizes["hidden_size"], sizes["vocab_size"]
