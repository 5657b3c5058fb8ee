"""One-time weight prepacking for the weight-stationary photonic engine.

The DPU programs its weight MRR banks once per tile and then streams
inputs (paper §III-A); re-quantizing — and, for the Pallas backend,
re-padding — the *static* weight operand on every forward call is pure
hot-path waste.  :func:`prepack_params` walks a parameter tree against
its definition tree, finds every dense site the engine's policy routes,
and replaces the float weight with a :class:`PackedDense` leaf — the one
int8 weight layout of the repo:

* per-column symmetric int8 quantization (bit-identical to the per-call
  ``quantize_symmetric(w, bits, axis=0)`` it replaces — contraction-axis
  reduction only, so stacked ``(layers, K, C)`` defs pack layerwise),
* for the ``pallas`` backend the weight is stored in the kernel's
  ``(Kp, Cp)`` layout — chunk slots along K
  (:func:`repro.photonic.engine.to_chunk_slots`), columns tile-padded;
  :func:`repro.photonic.engine.pallas_tiling` is activation-independent,
  which is what makes this legal — so decode steps never pad or re-slice
  the weight again.

``PackedDense`` is a registered pytree whose array leaves carry any
leading stack dims — ``jax.lax.scan`` over a stacked layer tree slices
straight through it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.dpu import quantize_symmetric
from repro.photonic.engine import (
    PhotonicEngine,
    logical_weight,
    pallas_tiling,
    to_chunk_slots,
)


@dataclasses.dataclass(frozen=True)
class ReprogramCost:
    """Latency/energy to (re)program one weight tile onto a DPU's rings.

    This is the weight-stationary cost the prepacking below exists to
    amortize: the tile is EO-tuned once (``latency_s``, Table VI) and
    then streamed against for free.  The mapper prices replication with
    it (``AcceleratorConfig.weight_reprogram_cost``) — a row-split
    replica re-programs the full tile chain, so it must stream long
    enough to cover its own reprogramming.
    """

    latency_s: float
    energy_j: float
    rings: int


def reprogram_cost(
    rings: int, *, tune_latency_s: float, tune_power_w_per_ring: float
) -> ReprogramCost:
    """Cost of programming ``rings`` weight rings in one tuning pass.
    (Energy is spelled ``(power x latency) x rings`` to stay bit-identical
    with the legacy simulator's tune-energy accounting.)"""
    return ReprogramCost(
        latency_s=tune_latency_s,
        energy_j=tune_power_w_per_ring * tune_latency_s * rings,
        rings=rings,
    )


@jax.tree_util.register_pytree_node_class
class PackedDense:
    """A prepacked dense weight: int8 slices + per-column dequant scale.

    ``wq``      — int8, ``(..., K, C)`` (raw) or ``(..., Kp, Cp)`` when
                  ``tiling`` is set (the Pallas kernel's chunk-slot
                  layout, see :func:`_kernel_layout`).  With
                  ``shards > 1`` the stored rows are the concatenation of
                  the per-shard banks: ``(..., shards * Kp_local, Cp)``,
                  each bank independently tile-padded for its *local*
                  tiling, so a row-wise ``PartitionSpec`` hands every mesh
                  shard exactly its padded bank.
    ``w_scale`` — float32 ``(..., C)`` per-column symmetric scale.  Always
                  the *global* (full-K) per-column scale — replicated over
                  the mesh; shard partials dequantize consistently.
    ``k, c``    — the *logical* (unpadded, global) contraction/output dims.
    ``tiling``  — ``None`` or the static ``(n_chunk, tile_k, tile_c)``
                  the weight was padded for (shard-local when sharded).
    ``shards``  — K-shard count of the stored layout (1 = unsharded).
    """

    __slots__ = ("wq", "w_scale", "k", "c", "tiling", "shards")

    def __init__(
        self,
        wq,
        w_scale,
        k: int,
        c: int,
        tiling: Optional[Tuple[int, int, int]] = None,
        shards: int = 1,
    ):
        self.wq = wq
        self.w_scale = w_scale
        self.k = k
        self.c = c
        self.tiling = tiling
        self.shards = shards

    def tree_flatten(self):
        return (self.wq, self.w_scale), (self.k, self.c, self.tiling, self.shards)

    @classmethod
    def tree_unflatten(cls, aux, children):
        wq, w_scale = children
        return cls(wq, w_scale, *aux)

    @property
    def k_local(self) -> int:
        """Per-shard logical contraction length."""
        return self.k // self.shards

    def logical_wq(self) -> jax.Array:
        """The int8 weight in its logical ``(..., K, C)`` layout."""
        wq, lead = self.wq, self.wq.shape[:-2]
        wq = wq.reshape(*lead, self.shards, wq.shape[-2] // self.shards, -1)
        wq = logical_weight(wq, self.k_local, self.c, self.tiling)
        return wq.reshape(*lead, self.k, self.c)

    def dequant(self) -> jax.Array:
        """The float32 weight this pack represents (logical K x C)."""
        return (
            self.logical_wq().astype(jnp.float32)
            * self.w_scale.astype(jnp.float32)[..., None, :]
        )

    def __repr__(self):
        return (
            f"PackedDense(k={self.k}, c={self.c}, stored={tuple(self.wq.shape)}, "
            f"tiling={self.tiling}, shards={self.shards})"
        )


def site_name(path: Tuple[str, ...]) -> str:
    """Dotted site name of a dense def at ``path``, normalized to the name
    the model code passes to ``dense(site=...)`` at call time — routing
    decisions made here and there must agree for any policy, not just the
    default.  Wrapper components ("layers", "first_block", "dec_layers",
    "mamba", ...) are stripped by keeping the suffix from the last
    "attn"/"ffn" module component; a trailing "cross" dict (whisper's
    decoder cross-attention) is consumed through the shared attention call
    sites and maps to "attn.<leaf>"; everything else is its leaf name.
    """
    parts = list(path)
    if not parts:
        return "root"
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in ("attn", "ffn"):
            return ".".join(parts[i:])
        if parts[i] == "cross" and i == len(parts) - 2:
            return "attn." + parts[-1]
    return parts[-1]


def _is_dense_def(node: Any) -> bool:
    if not isinstance(node, dict) or "w" not in node:
        return False
    w = node["w"]
    return (not isinstance(w, dict) and hasattr(w, "shape") and len(w.shape) >= 2)


def _kernel_layout(wq: jax.Array, k_local: int, tiling, shards: int = 1):
    """Logical ``(..., shards * k_local, C)`` int8 rows -> the Pallas
    kernel's stored layout: each shard's bank in chunk slots
    (:func:`~repro.photonic.engine.to_chunk_slots`), columns padded to
    whole ``tile_c`` blocks, banks concatenated along K."""
    lead, c = wq.shape[:-2], wq.shape[-1]
    tile_c = tiling[2]
    wq = wq.reshape(*lead, shards, k_local, c)
    wq = to_chunk_slots(wq, k_local, tiling, axis=-2)
    pad = [(0, 0)] * (wq.ndim - 1) + [(0, -(-c // tile_c) * tile_c - c)]
    wq = jnp.pad(wq, pad)
    return wq.reshape(*lead, shards * wq.shape[-2], wq.shape[-1])


def pack_dense(params: dict, engine: PhotonicEngine, *, shards: int = 1) -> dict:
    """Pack one dense-layer param dict ``{"w": float, ["b"]}``.

    The float weight is quantized per column exactly like the per-call
    path (``quantize_symmetric(w, operand_bits, axis=-2)``).

    ``shards > 1`` stores the K-sharded layout: quantization stays global
    (bit-identical scales), then the int8 bank is split into ``shards``
    row blocks of ``K/shards`` and each block is tile-padded for the
    *shard-local* engine (``pallas_tiling`` of ``dpu.shard_local``), so
    the concatenated rows shard contiguously over a mesh axis.
    """
    w = params["w"]
    # No dtype cast: bitwise-identical to the per-call
    # quantize_symmetric(w, operand_bits, axis=0) it replaces.  A stacked
    # weight quantizes one leading slice at a time (the scale reduces over
    # K only, so the bits are the same), which bounds the float
    # temporaries by a slice: a layer's experts, not the stack.
    parts = [
        quantize_symmetric(wi, engine.dpu.operand_bits, axis=-2)
        for wi in (w if w.ndim > 2 else [w])
    ]
    wq = jnp.stack([q for q, _ in parts]) if w.ndim > 2 else parts[0][0]
    s = jnp.stack([sc for _, sc in parts]) if w.ndim > 2 else parts[0][1]
    scale = jnp.squeeze(s, axis=-2)
    k, c = wq.shape[-2], wq.shape[-1]
    if shards > 1 and k % shards:
        raise ValueError(f"K={k} is not divisible by shards={shards}")
    k_local = k // shards
    tiling = None
    if engine.backend == "pallas":
        tile_dpu = engine.dpu.shard_local(k_local) if shards > 1 else engine.dpu
        tiling = pallas_tiling(tile_dpu, k_local, c)
        wq = _kernel_layout(wq, k_local, tiling, shards)
    out = {"w": PackedDense(wq, scale, k, c, tiling, shards)}
    if "b" in params:
        out["b"] = params["b"]
    return out


def fuse_qkv_params(attn: dict, engine: PhotonicEngine) -> dict:
    """Fuse a self-attention dict's ``wq``/``wk``/``wv`` into one ``wqkv``.

    The Q/K/V projections share the streaming activation; as three sites
    they cost three engine dispatches and three activation quantizations
    per token.  Fused into one ``(K, Cq+Ck+Cv)`` bank they cost one —
    ``models/attention.py::_qkv_proj`` splits the output columns back.

    Bitwise contract: per-column quantization, the K-chunked accumulation
    (:func:`~repro.photonic.engine.pallas_tiling` chunks by ``(cfg, K)``
    only) and the fused epilogue are all column-independent, so under a
    deterministic channel the fused call equals the three separate calls
    bit-for-bit, column by column.  Only the *noisy* channel diverges:
    the noise stream is seeded per site ("attn.wqkv" vs three names), a
    different but equally valid draw.

    Accepts prepacked (:class:`PackedDense`, unsharded) or float parts —
    mixed layouts or K-sharded packs are an error.  Biases must be all
    present or all absent (``qkv_bias``).  Leading stack dims pass
    through, so stacked layer trees fuse in one call.
    """
    names = ("wq", "wk", "wv")
    missing = [n for n in names if n not in attn]
    if missing:
        raise KeyError(f"fuse_qkv_params: attention dict lacks {missing}")
    parts = [attn[n] for n in names]
    packed = [isinstance(p["w"], PackedDense) for p in parts]
    if any(packed) and not all(packed):
        raise ValueError("fuse_qkv_params: mixed Q/K/V weight layouts")
    with_bias = ["b" in p for p in parts]
    if any(with_bias) and not all(with_bias):
        raise ValueError("fuse_qkv_params: bias on only some of Q/K/V")

    if all(packed):
        packs = [p["w"] for p in parts]
        if any(pk.shards != 1 for pk in packs):
            raise ValueError("fuse_qkv_params: K-sharded packs not supported")
        k = packs[0].k
        if any(pk.k != k for pk in packs):
            raise ValueError(
                f"fuse_qkv_params: mismatched K {[pk.k for pk in packs]}"
            )
        # Back to each bank's logical layout (drops per-site tile padding),
        # concatenate, re-lay out once for the fused width.
        wq = jnp.concatenate([pk.logical_wq() for pk in packs], axis=-1)
        scale = jnp.concatenate([pk.w_scale for pk in packs], axis=-1)
        c = sum(pk.c for pk in packs)
        tiling = None
        if engine.backend == "pallas":
            tiling = pallas_tiling(engine.dpu, k, c)
            wq = _kernel_layout(wq, k, tiling)
        fused = {"w": PackedDense(wq, scale, k, c, tiling, 1)}
    else:
        # Float weights: per-column quantization at call time is column-
        # independent, so concatenation alone preserves the contract.
        fused = {"w": jnp.concatenate([p["w"] for p in parts], axis=-1)}

    if all(with_bias):
        fused["b"] = jnp.concatenate([p["b"] for p in parts], axis=-1)
    out = {name: val for name, val in attn.items() if name not in names}
    out["wqkv"] = fused
    return out


def prepack_params(
    params: Any,
    defs: Any,
    engine: PhotonicEngine,
    *,
    mesh=None,
    axis: str = "model",
) -> Any:
    """Prepack every policy-routed dense site of a model parameter tree.

    ``defs`` is the matching param-definition tree (``P`` leaves, see
    ``repro.models.common``); it identifies dense sites and their dotted
    names, so routing decisions here agree with the site names the model
    code passes to ``dense(...)`` at call time.  Non-routed sites (e.g.
    the MoE ``router`` under the default policy) are left untouched and
    keep executing digitally.

    With ``mesh`` (and the ``axis`` mesh axis sized > 1) the int8 banks
    are stored in the K-sharded layout and placed with the repo's
    logical-axis sharding rules (``runtime/sharding.py``: weight fan-in
    on the tensor-parallel axis, per-column scales replicated), ready for
    :mod:`repro.photonic.sharded` execution.  Sites whose K does not
    divide the axis fall back to the unsharded layout (and stay on the
    single-device path at call time).
    """
    shards = 1
    if mesh is not None and axis in mesh.shape:
        shards = int(mesh.shape[axis])

    def place(packed: dict) -> dict:
        """device_put the pack onto the mesh via the logical-axis rules."""
        from repro.runtime import sharding as shd

        rules = {"fanin": axis, "out": None}
        pd = packed["w"]
        lead = (None,) * (pd.wq.ndim - 2)
        wq_sh = shd.named_sharding(mesh, pd.wq.shape, lead + ("fanin", "out"), rules)
        sc_sh = shd.named_sharding(
            mesh, pd.w_scale.shape, (None,) * pd.w_scale.ndim, rules
        )
        packed = dict(packed)
        packed["w"] = PackedDense(
            jax.device_put(pd.wq, wq_sh),
            jax.device_put(pd.w_scale, sc_sh),
            pd.k,
            pd.c,
            pd.tiling,
            pd.shards,
        )
        return packed

    def walk(p, d, path):
        if _is_dense_def(d):
            if engine.routes(site_name(path)):
                k = p["w"].shape[-2]
                site_shards = shards if k % shards == 0 else 1
                packed = pack_dense(p, engine, shards=site_shards)
                return place(packed) if site_shards > 1 else packed
            return p
        if isinstance(d, dict):
            return {k: walk(p[k], d[k], path + (k,)) for k in d}
        return p

    return walk(params, defs, ())
