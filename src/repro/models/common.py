"""Shared model substrate: config, parameter system, core layers.

Models are pure functions over pytrees.  Each module contributes a *param
definition tree* (nested dicts of :class:`P`) carrying shape + logical
sharding axes + init rule; ``init_tree`` materializes arrays and
``axes_tree`` yields the matching logical-axis tree consumed by
``repro.runtime.sharding``.  One source of truth — params and shardings can
never drift apart.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dpu import DPUConfig

# ---------------------------------------------------------------------------
# Model configuration — one dataclass covers all 10 assigned architectures
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ModelConfig:
    arch_id: str = "custom"
    family: str = "dense"  # dense | vlm | moe | ssm | hybrid | audio

    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 2
    num_kv_heads: int = 2
    d_ff: int = 256
    vocab_size: int = 256
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    ffn_act: str = "swiglu"  # swiglu | gelu

    # MoE ------------------------------------------------------------------
    # The router scores num_experts * ep_size experts; a layer holds
    # num_experts of them (expert parallelism over ep_size chips, this
    # chip's share first) and computes their part of the result alone.
    num_experts: int = 0           # routed experts held (0 = dense FFN)
    ep_size: int = 1               # chips that share each MoE layer
    num_experts_per_tok: int = 2
    num_shared_experts: int = 0
    moe_d_ff: Optional[int] = None  # per-expert hidden (defaults to d_ff)
    norm_topk_prob: bool = True    # renormalize the top-k weights, else
    routed_scaling_factor: float = 1.0  # scale them by this
    router_scoring: str = "softmax"  # the only scoring implemented
    router_topk: str = "greedy"      # the only top-k method implemented
    moe_layer_freq: int = 1          # every layer after the dense ones is MoE
    first_dense_layers: int = 0      # leading dense-FFN layers (0 or 1)

    # MLA (deepseek-v2) ------------------------------------------------------
    mla: bool = False
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None  # query compression: not implemented
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # YaRN rope scaling as published (a dict, kept as sorted items), or None
    rope_scaling: Any = None

    # Vision cross-attention (llama-3.2-vision) ------------------------------
    cross_attn_every: int = 0      # 0 = no cross-attn layers
    vision_seq: int = 1024         # stub patch-embedding sequence length

    # SSM / hybrid -----------------------------------------------------------
    ssm_state_size: int = 64
    ssm_expand: int = 2
    ssm_head_dim: int = 64
    ssm_conv_width: int = 4
    ssm_chunk: int = 256
    attn_every: int = 0            # zamba2: shared attn block every k layers
    slstm_every: int = 0           # xlstm: sLSTM block every k layers

    # Encoder-decoder (whisper) ----------------------------------------------
    encoder_decoder: bool = False
    encoder_layers: int = 0
    decoder_ratio: int = 4         # decoder_len = seq_len // ratio

    # Numerics / execution ---------------------------------------------------
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    remat: bool = True
    remat_policy: str = "full"   # full | dots (save dot outputs) — §Perf knob
    attn_f32: bool = True        # f32 flash-attn accumulators (False: bf16 MXU)
    attn_chunk: int = 512        # KV-block size of chunked attention
    attn_impl: str = "chunked"   # chunked | flash (Pallas kernel, §14 hot path)
    unroll_scans: bool = False   # unroll inner chunk scans (cost-analysis mode)
    logical_rules: Any = None    # per-arch sharding-rule overrides (dict)
    kv_cache_int8: bool = False  # int8 KV cache w/ per-token-head scales
    mla_absorb: bool = False     # MLA decode with absorbed up-projections
    photonic: Optional[DPUConfig] = None
    photonic_backend: str = "ref"    # ref | pallas | exact
    # Which weights execute photonically (when `photonic` is set):
    #   "none"    — photonic config carried but no GEMM routed;
    #   "weights" — every routed GEMM: float weights quantize per call,
    #               prepacked ones (repro.photonic.prepack_params) are the
    #               int8 serving layout.
    photonic_scope: str = "weights"  # none | weights
    # Per-site routing policy (repro.photonic.SitePolicy patterns, matched
    # against dotted site names like "ffn.router" and their last component).
    # The MoE router stays digital by default — expert-routing decisions are
    # control flow; opt it in with photonic_exclude=().
    photonic_include: Tuple[str, ...] = ("*",)
    photonic_exclude: Tuple[str, ...] = ("router",)
    # Bit-sliced execution mode (repro.photonic.slicing): None runs the
    # hardware datapath unchanged; plane bits (int/str/SlicingSpec) run
    # every routed GEMM as plane-pair passes re-referred to the plane
    # full-scale (DESIGN.md §15 — the fidelity lever past ENOB saturation).
    photonic_slicing: Any = None

    # Structural padding applied for mesh divisibility (see pad_for_mesh) ----
    padded_heads: Optional[int] = None
    padded_vocab: Optional[int] = None

    def __post_init__(self):
        scopes = ("none", "weights")
        if self.photonic_scope not in scopes:
            raise ValueError(
                f"photonic_scope={self.photonic_scope!r} is not one of {scopes}"
            )
        backends = ("ref", "pallas", "exact")
        if self.photonic_backend not in backends:
            raise ValueError(
                f"photonic_backend={self.photonic_backend!r} is not one of "
                f"{backends}"
            )
        impls = ("chunked", "flash")
        if self.attn_impl not in impls:
            raise ValueError(
                f"attn_impl={self.attn_impl!r} is not one of {impls}"
            )
        for name, allowed in _IMPLEMENTED_ONLY.items():
            if getattr(self, name) not in allowed:
                raise ValueError(
                    f"{name}={getattr(self, name)!r} is not implemented; the "
                    f"program implements {allowed}"
                )
        if self.first_dense_layers not in (0, 1):
            raise ValueError(
                f"first_dense_layers={self.first_dense_layers} is not 0 or 1"
            )
        if isinstance(self.rope_scaling, dict):
            if self.rope_scaling.get("type") != "yarn":
                raise ValueError(
                    f"rope_scaling {self.rope_scaling!r}: only YaRN is implemented"
                )
            object.__setattr__(
                self, "rope_scaling", tuple(sorted(self.rope_scaling.items()))
            )
        # Eager normalization through THE slicing resolution point
        # (unknown plane widths raise here, not at first GEMM).
        from repro.photonic.slicing import resolve_slicing

        object.__setattr__(
            self, "photonic_slicing", resolve_slicing(self.photonic_slicing)
        )

    @property
    def hd(self) -> int:
        return (
            self.head_dim
            if self.head_dim is not None
            else self.d_model // self.num_heads
        )

    @property
    def n_q_heads(self) -> int:
        return self.padded_heads if self.padded_heads is not None else self.num_heads

    @property
    def n_vocab(self) -> int:
        return self.padded_vocab if self.padded_vocab is not None else self.vocab_size

    @property
    def moe_hidden(self) -> int:
        return self.moe_d_ff if self.moe_d_ff is not None else self.d_ff

    def pad_for_mesh(self, model_axis: int) -> "ModelConfig":
        """Return a config with head/kv/vocab sizes divisible by the TP degree.

        * q-heads padded up (zero-init extras — structural only),
        * kv-heads replicated up to the TP degree when smaller,
        * vocab padded up (masked out of the loss).
        Overheads are counted in EXPERIMENTS.md §Roofline "useful ratio".
        """
        changes: Dict[str, Any] = {}
        if self.num_heads % model_axis:
            changes["padded_heads"] = _round_up(self.num_heads, model_axis)
        kv = self.num_kv_heads
        if kv and kv < model_axis:
            if model_axis % kv:
                raise ValueError(f"cannot replicate kv={kv} onto tp={model_axis}")
            changes["num_kv_heads"] = model_axis
        elif kv % model_axis:
            changes["num_kv_heads"] = _round_up(kv, model_axis)
        if self.vocab_size % model_axis:
            changes["padded_vocab"] = _round_up(self.vocab_size, model_axis)
        return dataclasses.replace(self, **changes) if changes else self


# Settings the program takes from a published configuration but implements
# for one value only: any other value is refused, never ignored.
_IMPLEMENTED_ONLY = {
    "router_scoring": ("softmax",),
    "router_topk": ("greedy",),
    "moe_layer_freq": (1,),
    "q_lora_rank": (None,),
}


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# ---------------------------------------------------------------------------
# Parameter definition system
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class P:
    """A parameter definition: shape + logical axes + init rule."""

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "fan_in"  # fan_in | zeros | ones | embed | normal
    fan_axis: int = 0      # which axis is fan-in for scaling
    dtype: Optional[str] = None  # override model param_dtype ("int8", "float32")

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def init_tree(defs: Any, key: jax.Array, dtype: Any) -> Any:
    """Materialize a nested dict of P into arrays (deterministic per-path)."""
    leaves = []

    def walk(node, path):
        if isinstance(node, P):
            leaves.append((path, node))
        elif isinstance(node, dict):
            for k in sorted(node):
                walk(node[k], path + (k,))
        else:
            raise TypeError(f"bad param def at {path}: {type(node)}")

    walk(defs, ())

    out: Dict[str, Any] = {}
    for path, p in leaves:
        sub = key
        for name in path:
            sub = jax.random.fold_in(sub, _stable_hash(name))
        dt = jnp.dtype(p.dtype) if p.dtype is not None else dtype
        if dt == jnp.int8:
            arr = jax.random.randint(sub, p.shape, -127, 128, jnp.int32).astype(
                jnp.int8
            )
        elif p.init == "zeros":
            arr = jnp.zeros(p.shape, dt)
        elif p.init == "ones":
            arr = jnp.ones(p.shape, dt)
        elif p.init in ("embed", "normal"):
            arr = (jax.random.normal(sub, p.shape) * 0.02).astype(dt)
        else:  # fan_in variance scaling
            fan = max(p.shape[p.fan_axis], 1)
            arr = (jax.random.normal(sub, p.shape) / math.sqrt(fan)).astype(dt)
        node = out
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = arr
    return out


def axes_tree(defs: Any) -> Any:
    """The logical-axis tree matching init_tree's output."""
    if isinstance(defs, P):
        return defs.axes
    return {k: axes_tree(v) for k, v in defs.items()}


def _stable_hash(s: str) -> int:
    h = 2166136261
    for ch in s.encode():
        h = (h ^ ch) * 16777619 & 0xFFFFFFFF
    return h


# ---------------------------------------------------------------------------
# Core layers (functional)
# ---------------------------------------------------------------------------
def dense_def(
    d_in: int,
    d_out: int,
    axes: Tuple[Optional[str], Optional[str]],
    bias: bool = False,
    init: str = "fan_in",
) -> Dict[str, P]:
    d = {"w": P((d_in, d_out), axes, init=init)}
    if bias:
        d["b"] = P((d_out,), (axes[1],), init="zeros")
    return d


def engine_from_model_config(cfg: ModelConfig):
    """The :class:`repro.photonic.PhotonicEngine` a model config implies,
    or ``None`` when no GEMM is photonic (``photonic=None`` or scope
    ``"none"``)."""
    from repro.photonic.engine import engine_for

    if cfg.photonic is None or cfg.photonic_scope == "none":
        return None
    return engine_for(
        cfg.photonic,
        cfg.photonic_backend,
        tuple(cfg.photonic_include),
        tuple(cfg.photonic_exclude),
        slicing=cfg.photonic_slicing,
    )


def dense(
    params: Dict[str, Any],
    x: jax.Array,
    cfg: ModelConfig,
    *,
    site: Optional[str] = None,
    layer: Optional[jax.Array] = None,
    prng_key: Optional[jax.Array] = None,
    epilogue: Any = None,
    slicing: Any = None,
) -> jax.Array:
    """Linear layer; routes through the photonic engine when enabled.

    ``site`` names this GEMM for the engine's routing policy and seed
    derivation ("attn.wq", "ffn.router", "lm_head", ...); ``layer`` is an
    optional (traceable) stack index folded into the noise stream so
    same-shaped layers inside a ``lax.scan`` decorrelate; ``prng_key``
    threads an explicit randomness source end-to-end (a noisy channel
    with neither a key nor ``DPUConfig.noise_seed`` raises the documented
    ``ValueError``).

    ``epilogue=`` takes a bias-free :class:`EpilogueSpec` — the bias
    operand always comes from the param def (``params["b"]``), so the
    spec only selects the activation.  The bias and activation are *not*
    applied here as separate ops: they ride the engine's fused epilogue
    (DESIGN.md §14) so routed GEMMs never materialize the unrescaled or
    pre-activation intermediate (RPR008 enforces this).  Digital
    fallbacks keep the historical op order bit-for-bit.  ``slicing=``
    overrides ``cfg.photonic_slicing`` for this GEMM (bit-sliced
    execution, DESIGN.md §15).

    Under an active tensor-parallel scope
    (``repro.photonic.sharded.tensor_parallel`` / ``manual_tp``) routed
    GEMMs K-shard over the mesh axis: shard-local channel at ``N_local``,
    (site, layer, shard)-folded noise, digital-domain ``psum`` — bitwise
    equal to the single-device path under an ideal channel.
    """
    from repro.photonic import Epilogue, EpilogueSpec
    from repro.photonic import sharded as tp

    activation = None
    if epilogue is not None:
        if not isinstance(epilogue, EpilogueSpec):
            raise TypeError(
                f"dense() takes a bias-free EpilogueSpec (the bias operand "
                f"comes from the param def), got {type(epilogue).__name__}"
            )
        if epilogue.bias:
            raise TypeError(
                "dense() sources its bias from the param def; pass "
                "EpilogueSpec(bias=False, ...)"
            )
        activation = epilogue.activation

    bias = params.get("b")
    ep = Epilogue(EpilogueSpec(bias=bias is not None, activation=activation), bias)
    eng = engine_from_model_config(cfg)
    # The site names the GEMM's ops on the device too (``op_name`` metadata).
    with jax.named_scope(site) if site else contextlib.nullcontext():
        y = tp.maybe_tp_matmul(
            eng,
            params["w"],
            x,
            site=site,
            fold=layer,
            prng_key=prng_key,
            epilogue=ep,
            slicing=slicing,
        )
        if y is None:
            y = _single_device_matmul(
                eng,
                params["w"],
                x,
                site=site,
                layer=layer,
                prng_key=prng_key,
                epilogue=ep,
                slicing=slicing,
            )
    return y


def grouped_dense(
    params: Dict[str, Any],
    x: jax.Array,
    cfg: ModelConfig,
    tile_expert: jax.Array,
    n_tiles: jax.Array,
    *,
    tile_r: int,
    site: str,
) -> jax.Array:
    """Linear layer over a stack of weight banks (``params["w"]``, leading
    bank axis): row tile ``i`` of ``x`` against bank ``tile_expert[i]``,
    for the first ``n_tiles`` tiles; later rows are undefined.  Routes
    through the photonic engine's grouped GEMM when enabled, else the
    digital grouped product.  No bias, no fused activation."""
    from repro.photonic.engine import grouped_digital
    from repro.photonic.packing import PackedDense

    w = params["w"]
    eng = engine_from_model_config(cfg)
    with jax.named_scope(site):
        if eng is None:
            wf = w.dequant() if isinstance(w, PackedDense) else w
            return grouped_digital(x, wf, tile_expert, n_tiles, tile_r)
        return eng.grouped_matmul(
            x, w, tile_expert, n_tiles, tile_r=tile_r, site=site
        ).astype(x.dtype)


def _digital_epilogue(y, ep):
    """Bias/activation for fully digital matmuls — the historical op order
    (bias added in the output dtype, activation from the engine's shared
    table) so non-photonic paths are bitwise-unchanged by fusion."""
    if ep.bias is not None:
        y = y + ep.bias.astype(y.dtype)
    if ep.spec.activation is not None:
        from repro.photonic import ACTIVATIONS

        y = ACTIVATIONS[ep.spec.activation](y)
    return y


def _single_device_matmul(
    eng, w, x, *, site, layer, prng_key, epilogue, slicing=None
):
    """The non-sharded product of :func:`dense`: a prepacked weight
    (:class:`~repro.photonic.PackedDense`) or a float one."""
    from repro.photonic.packing import PackedDense

    if isinstance(w, PackedDense):
        if eng is None:
            return _digital_epilogue(x @ w.dequant().astype(x.dtype), epilogue)
        return eng.matmul(
            x, w, site=site, fold=layer, prng_key=prng_key,
            epilogue=epilogue, slicing=slicing,
        )
    if eng is not None:
        return eng.matmul_float(
            x, w, site=site, fold=layer, prng_key=prng_key,
            epilogue=epilogue, slicing=slicing,
        )
    return _digital_epilogue(x @ w.astype(x.dtype), epilogue)


def rmsnorm_def(d: int) -> Dict[str, P]:
    return {"scale": P((d,), (None,), init="ones")}


def rmsnorm(params, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(dt)


def embed_def(vocab: int, d: int) -> Dict[str, P]:
    return {"table": P((vocab, d), ("vocab", None), init="embed")}


def embed(params, ids: jax.Array, cfg: ModelConfig) -> jax.Array:
    return params["table"].astype(cfg.compute_dtype)[ids]


def unembed(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Logits head (optionally tied to the embedding table)."""
    w = params["table"] if "table" in params else params["w"]
    with jax.named_scope("lm_head"):
        if "table" in params:
            return x @ w.astype(x.dtype).T
        return x @ w.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(head_dim: int, theta: float, scaling=None) -> jax.Array:
    """Rotary inverse frequencies, with YaRN's (arXiv:2309.00071) when
    ``scaling`` holds its published items: dimensions that turn fewer than
    ``beta_slow`` times over the original context are interpolated by
    ``factor``, those that turn more than ``beta_fast`` times are kept, and
    a linear ramp joins the two (DeepSeek-V2's ``yarn_find_correction_range``
    and ``yarn_linear_ramp_mask``)."""
    base = np.arange(0, head_dim, 2, dtype=np.float32) / head_dim
    extra = 1.0 / (theta**base)
    if scaling is None:
        return jnp.asarray(extra, jnp.float32)
    s = dict(scaling)
    factor, orig = s["factor"], s["original_max_position_embeddings"]

    def dim(rotations):
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))) / (
            2 * math.log(theta)
        )

    low = max(math.floor(dim(s["beta_fast"])), 0)
    high = min(math.ceil(dim(s["beta_slow"])), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip(
        (np.arange(head_dim // 2, dtype=np.float32) - low) / (high - low), 0, 1
    )
    keep = 1.0 - ramp
    inter = 1.0 / (factor * theta**base)
    return jnp.asarray(inter * (1 - keep) + extra * keep, jnp.float32)


def rope_attention_factor(scaling) -> float:
    """Factor YaRN puts on the softmax scale (DeepSeek-V2 multiplies its
    ``q_head_dim ** -0.5`` by ``mscale(factor, mscale_all_dim) ** 2``), and
    1 without scaling.  The rotary cos/sin factor, ``mscale(factor,
    mscale) / mscale(factor, mscale_all_dim)``, must be 1: it is when the
    two published values are equal, and any other value is refused."""
    if scaling is None:
        return 1.0
    s = dict(scaling)
    m_all = _yarn_mscale(s["factor"], s.get("mscale_all_dim", 0))
    if _yarn_mscale(s["factor"], s.get("mscale", 1)) != m_all:
        raise ValueError(f"YaRN with a rotary cos/sin factor other than 1: {s}")
    return m_all * m_all


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float, inv_freq=None
) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq).  Rotates the
    two halves of each head; ``inv_freq`` overrides the plain frequencies
    (YaRN, :func:`rope_inv_freq`)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta) if inv_freq is None else inv_freq
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def softmax_cross_entropy(
    logits: jax.Array,  # (B, T, V_padded)
    labels: jax.Array,  # (B, T) int32
    true_vocab: int,
) -> jax.Array:
    """Mean CE in f32; padded vocab columns masked to -inf."""
    v = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if v > true_vocab:
        col = jax.lax.broadcasted_iota(jnp.int32, (v,), 0)
        logits = jnp.where(col < true_vocab, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def apply_remat(fn, cfg: ModelConfig):
    """jax.checkpoint with the configured policy (§Perf knob).

    * "full": save nothing — recompute the whole block in backward.
    * "dots": save dot/matmul outputs — no GEMM recompute (more memory,
      ~25% fewer training FLOPs).
    """
    if not cfg.remat:
        return fn
    policy = None
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint(fn, prevent_cse=False, policy=policy)


# ---------------------------------------------------------------------------
# Sharding-constraint helper (no-op outside a mesh)
# ---------------------------------------------------------------------------
def with_logical(x: jax.Array, axes: Tuple[Optional[str], ...]) -> jax.Array:
    from repro.runtime.sharding import logical_constraint

    return logical_constraint(x, axes)
