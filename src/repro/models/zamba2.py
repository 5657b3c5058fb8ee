"""Zamba2 hybrid (arXiv:2411.15242): a Mamba2 backbone with a single
weight-SHARED attention+MLP block applied every `attn_every` layers.

Simplifications vs the released model (documented in DESIGN.md §7): the
shared block consumes the hidden state only (no concat with the original
embedding) and per-invocation LoRA adapters are omitted; the shared block's
KV caches are per-invocation (stacked), since each invocation attends over
its own inputs.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import common as cm
from repro.models import ffn, ssm
from repro.models.common import ModelConfig
from repro.models.lm import stack_defs


def _n_groups(cfg: ModelConfig) -> int:
    return cfg.num_layers // cfg.attn_every


def zamba2_def(cfg: ModelConfig) -> Dict[str, Any]:
    return {
        "embed": cm.embed_def(cfg.n_vocab, cfg.d_model),
        "mamba": stack_defs(
            stack_defs(ssm.mamba_def(cfg), cfg.attn_every), _n_groups(cfg)
        ),
        "shared": {  # ONE set of weights, applied at every group boundary
            "ln1": cm.rmsnorm_def(cfg.d_model),
            "attn": attn.gqa_def(cfg),
            "ln2": cm.rmsnorm_def(cfg.d_model),
            "ffn": ffn.mlp_def(cfg),
        },
        "final_norm": cm.rmsnorm_def(cfg.d_model),
        "lm_head": cm.dense_def(cfg.d_model, cfg.n_vocab, (None, "vocab")),
    }


def _shared_block(params, x, cfg: ModelConfig, positions):
    h = cm.rmsnorm(params["ln1"], x, cfg.norm_eps)
    x = x + attn.gqa_attention(params["attn"], h, cfg, positions=positions)
    h = cm.rmsnorm(params["ln2"], x, cfg.norm_eps)
    return x + ffn.mlp(params["ffn"], h, cfg)


def zamba2_logits(params, tokens, cfg: ModelConfig):
    b, t = tokens.shape
    x = cm.embed(params["embed"], tokens, cfg)
    x = cm.with_logical(x, ("batch", "seq_sp", None))
    positions = jnp.arange(t)
    shared = params["shared"]  # closed over: same weights every group

    mblk = cm.apply_remat(lambda p, x: ssm.mamba_block(p, x, cfg), cfg)

    def group(x, mparams):
        def inner(x, p):
            x = mblk(p, x)
            return cm.with_logical(x, ("batch", "seq_sp", None)), None

        x, _ = jax.lax.scan(inner, x, mparams)
        x = _shared_block(shared, x, cfg, positions)
        return cm.with_logical(x, ("batch", "seq_sp", None)), None

    x, _ = jax.lax.scan(group, x, params["mamba"])
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return cm.dense(params["lm_head"], x, cfg, site="lm_head"), jnp.zeros(
        (), jnp.float32
    )


def zamba2_loss(params, batch, cfg: ModelConfig):
    logits, _ = zamba2_logits(params, batch["tokens"], cfg)
    return cm.softmax_cross_entropy(logits, batch["labels"], cfg.vocab_size)


def zamba2_prefill(params, tokens, cfg: ModelConfig, max_seq: int):
    b, t = tokens.shape
    x = cm.embed(params["embed"], tokens, cfg)
    positions = jnp.arange(t)
    shared = params["shared"]

    def group(x, mparams):
        def inner(x, p):
            return ssm.mamba_prefill(p, x, cfg)

        x, msts = jax.lax.scan(inner, x, mparams)
        h = cm.rmsnorm(shared["ln1"], x, cfg.norm_eps)
        a, kv = attn.gqa_prefill(
            shared["attn"], h, cfg, positions=positions, max_seq=max_seq
        )
        x = x + a
        h = cm.rmsnorm(shared["ln2"], x, cfg.norm_eps)
        x = x + ffn.mlp(shared["ffn"], h, cfg)
        return x, (msts, kv)

    x, (mamba_states, attn_caches) = jax.lax.scan(group, x, params["mamba"])
    x = cm.rmsnorm(params["final_norm"], x[:, -1:], cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x, cfg, site="lm_head")
    cache = {
        "mamba": mamba_states,
        "attn": attn_caches,
        "pos": jnp.array(t, jnp.int32),
    }
    return logits, cache


def zamba2_decode(params, token, cache, cfg: ModelConfig):
    x = cm.embed(params["embed"], token, cfg)
    pos = cache["pos"]
    shared = params["shared"]

    def group(x, inp):
        mparams, msts, kv = inp

        def inner(x, pst):
            p, st = pst
            return ssm.mamba_decode(p, x, st, cfg)

        x, msts = jax.lax.scan(inner, x, (mparams, msts))
        h = cm.rmsnorm(shared["ln1"], x, cfg.norm_eps)
        a, kv = attn.gqa_decode(shared["attn"], h, kv, pos, cfg)
        x = x + a
        h = cm.rmsnorm(shared["ln2"], x, cfg.norm_eps)
        x = x + ffn.mlp(shared["ffn"], h, cfg)
        return x, (msts, kv)

    x, (mamba_states, attn_caches) = jax.lax.scan(
        group, x, (params["mamba"], cache["mamba"], cache["attn"])
    )
    x = cm.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x, cfg, site="lm_head")
    return logits, {
        "mamba": mamba_states,
        "attn": attn_caches,
        "pos": pos + 1,
    }


def zamba2_cache_def(cfg: ModelConfig, batch: int, max_seq: int, dtype):
    g, per = _n_groups(cfg), cfg.attn_every
    mstate = ssm.mamba_state_def(cfg, batch, dtype)
    acache = attn.gqa_cache_def(cfg, batch, max_seq, dtype)
    return {
        "mamba": {
            k: ((g, per) + shape, (None, None) + axes, dt)
            for k, (shape, axes, dt) in mstate.items()
        },
        "attn": {
            k: ((g,) + shape, (None,) + axes, dt)
            for k, (shape, axes, dt) in acache.items()
        },
        "pos": ((), (), jnp.int32),
    }
