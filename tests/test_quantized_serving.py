"""int8 serving paths: int8 KV cache and prepacked int8 (photonic) weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dpu import DPUConfig
from repro.models import registry
from repro.models.common import engine_from_model_config, init_tree
from repro.photonic import PackedDense, prepack_params


def _roundtrip(arch, cfg, params, toks, T):
    logits, cache = arch.prefill(params, {"tokens": toks[:, : T - 4]}, cfg, T)
    outs = [logits]
    for i in range(T - 4, T):
        logits, cache = arch.decode(params, toks[:, i : i + 1], cache, cfg)
        outs.append(logits)
    return jnp.concatenate(outs, axis=1)


@pytest.mark.parametrize("name", ["granite-3-8b", "zamba2-2.7b", "whisper-medium"])
def test_int8_kv_cache_close_to_f32(name):
    arch = registry.get(name)
    cfg = dataclasses.replace(arch.smoke_config, remat=False)
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    rng = np.random.default_rng(0)
    B, T = 2, 16
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    audio = jnp.asarray(rng.normal(size=(B, T, cfg.d_model)), jnp.float32)

    def run(c):
        batch = {"tokens": toks[:, : T - 4]}
        if arch.family == "audio":
            batch["audio_embed"] = audio
        logits, cache = arch.prefill(params, batch, c, T)
        outs = [logits]
        for i in range(T - 4, T):
            logits, cache = arch.decode(params, toks[:, i : i + 1], cache, c)
            outs.append(logits)
        return jnp.concatenate(outs, axis=1)

    f32 = run(cfg)
    i8 = run(dataclasses.replace(cfg, kv_cache_int8=True))
    rel = float(jnp.linalg.norm(i8 - f32) / jnp.linalg.norm(f32))
    agree = float(jnp.mean(jnp.argmax(i8, -1) == jnp.argmax(f32, -1)))
    assert rel < 0.05, (name, rel)
    assert agree >= 0.9, (name, agree)


def test_int8_weight_storage_close_to_float():
    arch = registry.get("qwen2-0.5b")
    cfg = dataclasses.replace(arch.smoke_config, remat=False)
    cfg_q = dataclasses.replace(
        cfg,
        photonic=DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0),
        photonic_backend="ref",
    )
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    defs_q = arch.param_defs(cfg_q)
    params_q = prepack_params(params, defs_q, engine_from_model_config(cfg_q))
    # int8 weights exist, as prepacked leaves with their scales
    packs = jax.tree_util.tree_leaves(
        params_q, is_leaf=lambda l: isinstance(l, PackedDense)
    )
    packs = [p for p in packs if isinstance(p, PackedDense)]
    assert packs and all(p.wq.dtype == jnp.int8 for p in packs)

    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    f = _roundtrip(arch, cfg, params, toks, 16)
    q = _roundtrip(arch, cfg_q, params_q, toks, 16)
    rel = float(jnp.linalg.norm(q - f) / jnp.linalg.norm(f))
    agree = float(jnp.mean(jnp.argmax(q, -1) == jnp.argmax(f, -1)))
    assert rel < 0.2, rel
    assert agree >= 0.75, agree


def test_mla_absorbed_decode_exact():
    """Weight-absorbed MLA decode == naive MLA decode (linear identity)."""
    arch = registry.get("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(arch.smoke_config, remat=False)
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)

    a = _roundtrip(arch, cfg, params, toks, 16)
    b = _roundtrip(arch, dataclasses.replace(cfg, mla_absorb=True), params, toks, 16)
    assert float(jnp.abs(a - b).max()) < 1e-4


def test_int8_cache_def_shapes():
    from repro.models import attention as attn

    arch = registry.get("granite-3-8b")
    cfg = dataclasses.replace(arch.smoke_config, kv_cache_int8=True)
    d = attn.gqa_cache_def(cfg, 4, 32, jnp.bfloat16)
    assert d["k"][2] == jnp.int8
    assert d["k_scale"][0] == (4, 32, cfg.num_kv_heads)
