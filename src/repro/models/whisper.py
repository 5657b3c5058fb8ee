"""Whisper-medium backbone (arXiv:2212.04356): transformer encoder-decoder.

Per the assignment, the conv/mel frontend is a STUB — ``input_specs()``
provides precomputed frame embeddings (B, T_enc, D).  The decoder sequence
length is ``seq_len // decoder_ratio`` (DESIGN.md §6).  RoPE replaces the
original learned/sinusoidal positions (deviation noted in DESIGN.md §7).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models import ffn
from repro.models.common import ModelConfig
from repro.models.lm import stack_defs


def _enc_block_def(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "ln1": cm.rmsnorm_def(cfg.d_model),
        "attn": attn.gqa_def(cfg),
        "ln2": cm.rmsnorm_def(cfg.d_model),
        "ffn": ffn.mlp_def(cfg),
    }


def _dec_block_def(cfg: ModelConfig) -> Dict[str, Any]:
    d = _enc_block_def(cfg)
    d["ln_cross"] = cm.rmsnorm_def(cfg.d_model)
    d["cross"] = attn.gqa_def(cfg)
    return d


def whisper_def(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "enc_layers": stack_defs(_enc_block_def(cfg), cfg.encoder_layers),
        "enc_norm": cm.rmsnorm_def(cfg.d_model),
        "embed": cm.embed_def(cfg.n_vocab, cfg.d_model),
        "dec_layers": stack_defs(_dec_block_def(cfg), cfg.num_layers),
        "final_norm": cm.rmsnorm_def(cfg.d_model),
        "lm_head": cm.dense_def(cfg.d_model, cfg.n_vocab, (None, "vocab")),
    }


def encode(params, audio_embed: jax.Array, cfg: ModelConfig) -> jax.Array:
    """audio_embed: (B, T_enc, D) — stubbed conv-frontend output."""
    x = cm.with_logical(audio_embed, ("batch", "seq_sp", None))
    positions = jnp.arange(x.shape[1])

    def body(p, x):
        h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
        x = x + attn.gqa_attention(p["attn"], h, cfg, positions=positions, causal=False)
        h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + ffn.mlp(p["ffn"], h, cfg)
        return cm.with_logical(x, ("batch", "seq_sp", None))

    body = cm.apply_remat(body, cfg)

    def step(x, p):
        return body(p, x), None

    x, _ = jax.lax.scan(step, x, params["enc_layers"])
    x = cm.rmsnorm(params["enc_norm"], x, cfg.norm_eps)
    # Replicate the encoder output across `model` ONCE: every decoder layer's
    # cross-KV projection consumes it inside the decoder scan, and a
    # seq_sp-sharded enc would be re-gathered per layer (24x) — found via the
    # §Perf HC-E probe (whisper prefill was the only collective-bound
    # attention cell).
    return cm.with_logical(x, ("batch", None, None))


def _dec_block(p, x, enc_kv, cfg: ModelConfig, positions):
    h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
    x = x + attn.gqa_attention(p["attn"], h, cfg, positions=positions, causal=True)
    h = cm.rmsnorm(p["ln_cross"], x, cfg.norm_eps)
    x = x + attn.cross_attention(p["cross"], h, enc_kv, cfg)
    h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
    x = x + ffn.mlp(p["ffn"], h, cfg)
    return cm.with_logical(x, ("batch", "seq_sp", None))


def whisper_logits(params, batch, cfg: ModelConfig):
    enc = encode(params, batch["audio_embed"], cfg)
    tokens = batch["tokens"]
    x = cm.embed(params["embed"], tokens, cfg)
    positions = jnp.arange(tokens.shape[1])

    body = cm.apply_remat(lambda p, x, kv: _dec_block(p, x, kv, cfg, positions), cfg)

    def step(x, p):
        kv = attn.cross_kv(p["cross"], enc, cfg)
        return body(p, x, kv), None

    x, _ = jax.lax.scan(step, x, params["dec_layers"])
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return cm.dense(params["lm_head"], x, cfg, site="lm_head"), jnp.zeros(
        (), jnp.float32
    )


def whisper_loss(params, batch, cfg: ModelConfig):
    logits, _ = whisper_logits(params, batch, cfg)
    return cm.softmax_cross_entropy(logits, batch["labels"], cfg.vocab_size)


def whisper_prefill(params, batch, cfg: ModelConfig, max_seq: int):
    """Encode audio + run decoder prompt. batch: {audio_embed, tokens}."""
    enc = encode(params, batch["audio_embed"], cfg)
    tokens = batch["tokens"]
    b, t = tokens.shape
    x = cm.embed(params["embed"], tokens, cfg)
    positions = jnp.arange(t)

    def step(x, p):
        h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
        a, kv_self = attn.gqa_prefill(
            p["attn"], h, cfg, positions=positions, max_seq=max_seq
        )
        x = x + a
        h = cm.rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        kv_cross = attn.cross_kv(p["cross"], enc, cfg)
        x = x + attn.cross_attention(p["cross"], h, kv_cross, cfg)
        h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + ffn.mlp(p["ffn"], h, cfg)
        return x, (kv_self, kv_cross)

    x, (self_caches, cross_kvs) = jax.lax.scan(step, x, params["dec_layers"])
    x = cm.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x, cfg, site="lm_head")
    cache = {
        "self": self_caches,
        "cross": cross_kvs,
        "pos": jnp.array(t, jnp.int32),
    }
    return logits, cache


def whisper_decode(params, token, cache, cfg: ModelConfig):
    x = cm.embed(params["embed"], token, cfg)
    pos = cache["pos"]

    def step(x, inp):
        p, kv_self, kv_cross = inp
        h = cm.rmsnorm(p["ln1"], x, cfg.norm_eps)
        a, kv_self = attn.gqa_decode(p["attn"], h, kv_self, pos, cfg)
        x = x + a
        h = cm.rmsnorm(p["ln_cross"], x, cfg.norm_eps)
        x = x + attn.cross_attention(p["cross"], h, kv_cross, cfg)
        h = cm.rmsnorm(p["ln2"], x, cfg.norm_eps)
        x = x + ffn.mlp(p["ffn"], h, cfg)
        return x, kv_self

    x, new_self = jax.lax.scan(
        step, x, (params["dec_layers"], cache["self"], cache["cross"])
    )
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x, cfg, site="lm_head")
    return logits, {**cache, "self": new_self, "pos": pos + 1}


def whisper_cache_def(cfg: ModelConfig, batch: int, max_seq: int, enc_seq: int, dtype):
    n = cfg.num_layers
    kv, hd = cfg.num_kv_heads, cfg.hd
    self_c = attn.gqa_cache_def(cfg, batch, max_seq, dtype)
    cross_shape = (n, batch, enc_seq, kv, hd)
    cross_axes = (None, "batch", "kv_seq", "kv_heads", None)
    return {
        "self": {
            k: ((n,) + shape, (None,) + axes, dt)
            for k, (shape, axes, dt) in self_c.items()
        },
        "cross": ((cross_shape, cross_axes, dtype), (cross_shape, cross_axes, dtype)),
        "pos": ((), (), jnp.int32),
    }
