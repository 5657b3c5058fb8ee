"""Architecture registry — uniform interface over the 10 assigned archs.

Every arch exposes:
  * ``param_defs(cfg)``      — parameter definition tree (P leaves)
  * ``loss(params, batch, cfg)``                    — training loss
  * ``prefill(params, batch, cfg, max_seq)``        — (logits, cache)
  * ``decode(params, token, cache, cfg)``           — (logits, cache)
  * ``cache_def(cfg, batch, max_seq, meta, dtype)`` — cache shapes/axes
  * ``batch_spec(cfg, shape)`` / ``decode_spec``    — input ShapeDtypeStructs

`--arch <id>` in the launchers resolves through ``get(name)``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import lm, whisper, xlstm, zamba2
from repro.models.common import ModelConfig

# ---------------------------------------------------------------------------
# Input-shape table (assignment: LM-family shapes)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


@dataclasses.dataclass(frozen=True)
class Arch:
    name: str
    family: str
    config: ModelConfig
    smoke_config: ModelConfig
    param_defs: Callable[[ModelConfig], Any]
    loss: Callable[..., Any]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]
    cache_def: Callable[..., Any]
    skip_shapes: Tuple[str, ...] = ()
    notes: str = ""

    # ---- cost-analysis layer ladder ----------------------------------------
    def ladder(self, cfg: ModelConfig):
        """[(step_name, cfg_overrides, coeff)] such that an additive compile
        metric (FLOPs, bytes) of the full model = sum_i coeff_i * metric_i.

        Needed because XLA's cost_analysis counts `while` (scan) bodies once;
        lowering 0- and 1-group variants with inner scans unrolled recovers
        the exact per-layer cost (see EXPERIMENTS.md §Roofline method).
        """
        u = {"unroll_scans": True}
        L = cfg.num_layers
        if self.family == "audio":
            le, ld = cfg.encoder_layers, cfg.num_layers
            return [
                ("zero", {**u, "encoder_layers": 0, "num_layers": 0}, 1.0 - le - ld),
                ("enc1", {**u, "encoder_layers": 1, "num_layers": 0}, float(le)),
                ("dec1", {**u, "encoder_layers": 0, "num_layers": 1}, float(ld)),
            ]
        if self.family == "vlm":
            g = L // cfg.cross_attn_every
            per = cfg.cross_attn_every
            return [
                ("zero", {**u, "num_layers": 0}, 1.0 - g),
                ("grp1", {**u, "num_layers": per}, float(g)),
            ]
        if self.family == "ssm":
            g = L // cfg.slstm_every
            return [
                ("zero", {**u, "num_layers": 0}, 1.0 - g),
                ("grp1", {**u, "num_layers": cfg.slstm_every}, float(g)),
            ]
        if self.family == "hybrid":
            g = L // cfg.attn_every
            return [
                ("zero", {**u, "num_layers": 0}, 1.0 - g),
                ("grp1", {**u, "num_layers": cfg.attn_every}, float(g)),
            ]
        if cfg.first_dense_layers:  # deepseek-v2: unscanned first block
            return [
                ("l1", {**u, "num_layers": 1}, 2.0 - L),
                ("l2", {**u, "num_layers": 2}, L - 1.0),
            ]
        return [
            ("zero", {**u, "num_layers": 0}, 1.0 - L),
            ("l1", {**u, "num_layers": 1}, float(L)),
        ]

    # ---- input specs -------------------------------------------------------
    def train_batch_spec(self, cfg: ModelConfig, shape: ShapeSpec):
        b, t = shape.global_batch, shape.seq_len
        tok = jax.ShapeDtypeStruct((b, t), jnp.int32)
        spec = {"tokens": tok, "labels": tok}
        axes = {"tokens": ("batch", None), "labels": ("batch", None)}
        if self.family == "vlm":
            spec["vision"] = jax.ShapeDtypeStruct(
                (b, cfg.vision_seq, cfg.d_model), cfg.compute_dtype
            )
            axes["vision"] = ("batch", None, None)
        if self.family == "audio":
            dec = t // cfg.decoder_ratio
            spec = {
                "audio_embed": jax.ShapeDtypeStruct(
                    (b, t, cfg.d_model), cfg.compute_dtype
                ),
                "tokens": jax.ShapeDtypeStruct((b, dec), jnp.int32),
                "labels": jax.ShapeDtypeStruct((b, dec), jnp.int32),
            }
            axes = {
                "audio_embed": ("batch", "seq_sp", None),
                "tokens": ("batch", None),
                "labels": ("batch", None),
            }
        return spec, axes

    def prefill_batch_spec(self, cfg: ModelConfig, shape: ShapeSpec):
        spec, axes = self.train_batch_spec(cfg, shape)
        spec.pop("labels", None)
        axes.pop("labels", None)
        return spec, axes

    def decode_specs(self, cfg: ModelConfig, shape: ShapeSpec):
        """(token spec/axes, cache spec/axes) for one decode step."""
        b = shape.global_batch
        max_seq = (
            shape.seq_len
            if self.family != "audio"
            else shape.seq_len // cfg.decoder_ratio
        )
        meta = {"enc_seq": shape.seq_len}
        cache = self.cache_def(cfg, b, max_seq, meta, cfg.compute_dtype)
        tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
        cache_spec = jax.tree.map(
            lambda leaf: jax.ShapeDtypeStruct(leaf[0], leaf[2]),
            cache,
            is_leaf=_is_cache_leaf,
        )
        cache_axes = jax.tree.map(lambda leaf: leaf[1], cache, is_leaf=_is_cache_leaf)
        return (tok, ("batch", None)), (cache_spec, cache_axes)


def _is_cache_leaf(x):
    return (
        isinstance(x, tuple)
        and len(x) == 3
        and isinstance(x[0], tuple)
        and isinstance(x[1], tuple)
    )


# ---------------------------------------------------------------------------
# Family adapters (uniform call signatures)
# ---------------------------------------------------------------------------
def _lm_loss(params, batch, cfg):
    return lm.lm_loss(params, batch, cfg)


def _lm_prefill(params, batch, cfg, max_seq):
    return lm.lm_prefill(
        params, batch["tokens"], cfg, max_seq, vision=batch.get("vision")
    )


def _lm_decode(params, token, cache, cfg):
    return lm.lm_decode(params, token, cache, cfg)


def _lm_cache_def(cfg, batch, max_seq, meta, dtype):
    return lm.lm_cache_def(cfg, batch, max_seq, dtype)


def _xlstm_prefill(params, batch, cfg, max_seq):
    return xlstm.xlstm_prefill(params, batch["tokens"], cfg)


def _xlstm_cache_def(cfg, batch, max_seq, meta, dtype):
    return xlstm.xlstm_cache_def(cfg, batch, max_seq, dtype)


def _zamba_prefill(params, batch, cfg, max_seq):
    return zamba2.zamba2_prefill(params, batch["tokens"], cfg, max_seq)


def _zamba_cache_def(cfg, batch, max_seq, meta, dtype):
    return zamba2.zamba2_cache_def(cfg, batch, max_seq, dtype)


def _whisper_prefill(params, batch, cfg, max_seq):
    return whisper.whisper_prefill(params, batch, cfg, max_seq)


def _whisper_cache_def(cfg, batch, max_seq, meta, dtype):
    return whisper.whisper_cache_def(cfg, batch, max_seq, meta["enc_seq"], dtype)


_REGISTRY: Dict[str, Arch] = {}


def register(arch: Arch) -> Arch:
    _REGISTRY[arch.name] = arch
    return arch


def get(name: str) -> Arch:
    _ensure_loaded()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def names() -> list:
    _ensure_loaded()
    return sorted(_REGISTRY)


def _ensure_loaded():
    if _REGISTRY:
        return
    import repro.configs.catalog  # noqa: F401  (registers all archs)


# Family -> adapter bundle, used by the config files.
FAMILY_FNS = {
    "dense": (lm.lm_def, _lm_loss, _lm_prefill, _lm_decode, _lm_cache_def),
    "vlm": (lm.lm_def, _lm_loss, _lm_prefill, _lm_decode, _lm_cache_def),
    "moe": (lm.lm_def, _lm_loss, _lm_prefill, _lm_decode, _lm_cache_def),
    "ssm": (
        xlstm.xlstm_def,
        xlstm.xlstm_loss,
        _xlstm_prefill,
        xlstm.xlstm_decode,
        _xlstm_cache_def,
    ),
    "hybrid": (
        zamba2.zamba2_def,
        zamba2.zamba2_loss,
        _zamba_prefill,
        zamba2.zamba2_decode,
        _zamba_cache_def,
    ),
    "audio": (
        whisper.whisper_def,
        whisper.whisper_loss,
        _whisper_prefill,
        whisper.whisper_decode,
        _whisper_cache_def,
    ),
}


def make_arch(
    name: str,
    family: str,
    config: ModelConfig,
    smoke_config: ModelConfig,
    skip_shapes: Tuple[str, ...] = (),
    notes: str = "",
) -> Arch:
    defs, loss, prefill, decode, cache_def = FAMILY_FNS[family]
    return register(
        Arch(
            name=name,
            family=family,
            config=config,
            smoke_config=smoke_config,
            param_defs=defs,
            loss=loss,
            prefill=prefill,
            decode=decode,
            cache_def=cache_def,
            skip_shapes=skip_shapes,
            notes=notes,
        )
    )
