"""repro.photonic engine + packing: weight-stationary prepacked GEMM
routing (DESIGN.md §9 contracts)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dpu import DPUConfig
from repro.kernels.photonic_gemm.ref import exact_int_gemm
from repro.models import registry
from repro.models.common import (
    ModelConfig,
    dense,
    engine_from_model_config,
    init_tree,
)
from repro.noise import build_channel_model
from repro.photonic import (
    PackedDense,
    SitePolicy,
    engine_for,
    pack_dense,
    prepack_params,
)


def _noisy_dpu(noise_seed=3, n=21):
    ch = build_channel_model("SMWA", n=n, bits=4, datarate_gs=5.0)
    return DPUConfig(
        organization="SMWA", bits=4, dpe_size=n, channel=ch, noise_seed=noise_seed
    )


def _det_dpu(n=21):
    """Deterministic analog stages only (crosstalk/filter/ADC, no detector
    noise) — bitwise across backends per DESIGN.md §8."""
    ch = build_channel_model("SMWA", n=n, bits=4, datarate_gs=5.0)
    ch = dataclasses.replace(ch, detector_sigma_lsb=0.0)
    return DPUConfig(organization="SMWA", bits=4, dpe_size=n, channel=ch)


RNG = np.random.default_rng(0)
X = jnp.asarray(RNG.normal(size=(4, 200)), jnp.float32)
W = jnp.asarray(RNG.normal(size=(200, 96)), jnp.float32)


# ---------------------------------------------------------------------------
# Prepacked == per-call quantization (both backends, all channel kinds)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["ref", "pallas", "exact"])
@pytest.mark.parametrize("kind", ["ideal", "det", "noisy"])
def test_prepack_bitwise_equals_per_call(backend, kind):
    if kind == "noisy" and backend == "exact":
        pytest.skip("exact backend ignores the channel by design")
    dpu = {
        "ideal": DPUConfig(organization="SMWA", bits=4, dpe_size=21),
        "det": _det_dpu(),
        "noisy": _noisy_dpu(),
    }[kind]
    eng = engine_for(dpu, backend)
    packed = pack_dense({"w": W}, eng)["w"]
    a = eng.matmul_float(X, W, site="s", fold=2)
    b = eng.matmul(X, packed, site="s", fold=2)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prepack_ideal_engine_equals_exact_int_gemm():
    """Ideal-channel engine output == exact integer GEMM of the quantized
    operands, through the packed path, on both backends."""
    from repro.core.dpu import quantize_symmetric

    dpu = DPUConfig(organization="SMWA", bits=4, dpe_size=21)
    xq, sx = quantize_symmetric(X, 8)
    wq, sw = quantize_symmetric(W, 8, axis=0)
    gold = np.asarray(exact_int_gemm(xq, wq), np.float32) * np.asarray(
        sx
    ) * np.asarray(sw)
    for backend in ("ref", "pallas"):
        eng = engine_for(dpu, backend)
        packed = pack_dense({"w": W}, eng)["w"]
        y = eng.matmul(X, packed, site="s")
        np.testing.assert_allclose(np.asarray(y), gold, rtol=0, atol=0)


@pytest.mark.parametrize("n", [17, 42, 128])
def test_chunk_slot_layout_roundtrips_and_dequantizes(n):
    """Analog tilings store each DPE chunk in its own 128-lane slot; the
    stored pack reads back as the logical weight, bit for bit."""
    from repro.core.dpu import quantize_symmetric
    from repro.photonic.engine import chunk_lanes

    eng = engine_for(_det_dpu(n), "pallas")
    packed = pack_dense({"w": W}, eng)["w"]
    n_chunk, tile_k, _ = packed.tiling
    assert n_chunk == n and tile_k % 128 == 0 and chunk_lanes(n) % 128 == 0
    slots = packed.wq.reshape(-1, chunk_lanes(n), packed.wq.shape[1])
    assert not np.asarray(slots[:, n:, :]).any()  # slot padding is zero
    wq, sw = quantize_symmetric(W, 8, axis=0)
    np.testing.assert_array_equal(np.asarray(packed.logical_wq()), np.asarray(wq))
    np.testing.assert_array_equal(
        np.asarray(packed.dequant()), np.asarray(wq, np.float32) * np.asarray(sw)
    )


def test_prepack_pallas_layout_is_tile_padded():
    eng = engine_for(DPUConfig(organization="SMWA", bits=4, dpe_size=21), "pallas")
    packed = pack_dense({"w": W}, eng)["w"]
    assert packed.tiling is not None
    n_chunk, tile_k, tile_c = packed.tiling
    kp, cp = packed.wq.shape
    assert kp % tile_k == 0 and cp % tile_c == 0
    assert (kp, cp) != (packed.k, packed.c)  # genuinely padded for this shape
    # raw layout for the oracle backend
    raw = pack_dense({"w": W}, engine_for(DPUConfig(dpe_size=21), "ref"))["w"]
    assert raw.tiling is None and raw.wq.shape == (200, 96)


def test_prepack_reuses_existing_int8_layout_bitwise():
    """Prepacking a model's float weights gives exactly the int8 values and
    per-column scales of ``quantize_symmetric(w, bits, axis=0)`` — layer
    by layer through a stacked tree, bit for bit (only the layout is the
    pack's own)."""
    from repro.core.dpu import quantize_symmetric

    arch = registry.get("qwen2-0.5b")
    mcfg = dataclasses.replace(
        arch.smoke_config,
        remat=False,
        photonic=DPUConfig(dpe_size=21),
        photonic_backend="ref",
    )
    defs = arch.param_defs(mcfg)
    params = init_tree(defs, jax.random.PRNGKey(0), mcfg.param_dtype)
    eng = engine_from_model_config(mcfg)
    packed = prepack_params(params, defs, eng)

    w = params["layers"]["attn"]["wq"]["w"]  # (layers, K, C)
    leaf_p = packed["layers"]["attn"]["wq"]["w"]
    assert isinstance(leaf_p, PackedDense) and leaf_p.wq.dtype == jnp.int8
    assert leaf_p.tiling is None and leaf_p.wq.shape == w.shape
    for i in range(w.shape[0]):
        wq, sw = quantize_symmetric(w[i], eng.dpu.operand_bits, axis=0)
        np.testing.assert_array_equal(np.asarray(wq), np.asarray(leaf_p.wq[i]))
        np.testing.assert_array_equal(
            np.asarray(sw[0]), np.asarray(leaf_p.w_scale[i])
        )


# ---------------------------------------------------------------------------
# Site policy: routing + the MoE router bugfix
# ---------------------------------------------------------------------------
def test_site_policy_matching():
    pol = SitePolicy()
    assert pol.routes("attn.wq") and pol.routes("lm_head") and pol.routes(None)
    assert not pol.routes("ffn.router")
    assert not pol.routes("router")
    assert SitePolicy(exclude=()).routes("ffn.router")  # documented opt-in
    assert not SitePolicy(include=("attn.*",)).routes("ffn.wi")
    assert SitePolicy(include=("attn.*",)).routes("attn.wq")


def test_router_site_stays_digital_under_noise():
    """dense(site='ffn.router') must equal the exact digital matmul even
    with a ferociously noisy analog channel configured (satellite bugfix:
    expert routing decisions are control flow)."""
    ch = dataclasses.replace(
        build_channel_model("SMWA", n=21, bits=4, datarate_gs=5.0),
        detector_sigma_lsb=500.0,
    )
    cfg = ModelConfig(
        photonic=DPUConfig(dpe_size=21, channel=ch, noise_seed=0),
        photonic_backend="ref",
    )
    params = {"w": W}
    y = dense(params, X, cfg, site="ffn.router")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(X @ W))
    # ...and a routed site under the same channel is genuinely perturbed
    y2 = dense(params, X, cfg, site="ffn.wi")
    assert not np.array_equal(np.asarray(y2), np.asarray(X @ W))
    # opt-in: clearing the exclusion routes the router photonically
    cfg_in = dataclasses.replace(cfg, photonic_exclude=())
    y3 = dense(params, X, cfg_in, site="ffn.router")
    assert not np.array_equal(np.asarray(y3), np.asarray(X @ W))


def test_moe_router_excluded_end_to_end():
    """A full MoE forward picks identical experts with and without an
    (ideal) photonic engine only because the router runs digitally."""
    from repro.models import ffn

    cfg = ModelConfig(
        d_model=32,
        d_ff=64,
        num_experts=4,
        num_experts_per_tok=2,
        photonic=DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0),
        photonic_backend="ref",
    )
    defs = ffn.moe_def(cfg)
    params = init_tree(defs, jax.random.PRNGKey(1), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(2, 8, 32)), jnp.float32)
    logits_digital = x.astype(jnp.float32) @ params["router"]["w"]
    logits_engine = dense(
        params["router"], x.astype(jnp.float32), cfg, site="ffn.router"
    )
    np.testing.assert_array_equal(np.asarray(logits_engine), np.asarray(logits_digital))
    # the full MoE layer still runs (photonic experts, digital router)
    out, aux = ffn.moe(params, x, cfg)
    assert bool(jnp.all(jnp.isfinite(out))) and bool(jnp.isfinite(aux))


# ---------------------------------------------------------------------------
# Scope validation (satellite bugfix)
# ---------------------------------------------------------------------------
def test_model_config_validates_scope_and_backend():
    with pytest.raises(ValueError, match="photonic_scope"):
        ModelConfig(photonic_scope="weights_int4")
    with pytest.raises(ValueError, match="photonic_backend"):
        ModelConfig(photonic_backend="cuda")
    for scope in ("none", "weights"):
        ModelConfig(photonic_scope=scope)  # documented values accepted
    # int8 weights exist only as prepacked PackedDense leaves, not as a
    # scope of the param tree.
    with pytest.raises(ValueError, match="photonic_scope"):
        ModelConfig(photonic_scope="weights_int8")
    assert (
        engine_from_model_config(
            ModelConfig(photonic=DPUConfig(dpe_size=8), photonic_scope="none")
        )
        is None
    )


# ---------------------------------------------------------------------------
# PRNG-key threading (satellite bugfix)
# ---------------------------------------------------------------------------
def test_int8_branch_threads_prng_key_end_to_end():
    """The prepacked (int8) dense branch accepts prng_key (same key =>
    bitwise equal; different key => different) and raises the documented
    ValueError when a noisy channel has no randomness source at all."""
    ch = build_channel_model("SMWA", n=21, bits=4, datarate_gs=5.0)
    cfg = ModelConfig(
        photonic=DPUConfig(dpe_size=21, channel=ch),  # NO noise_seed
        photonic_backend="ref",
    )
    wq, sw = (
        jnp.asarray(RNG.integers(-127, 128, (200, 96)), jnp.int8),
        jnp.asarray(RNG.uniform(0.005, 0.02, (96,)), jnp.float32),
    )
    params = {"w": PackedDense(wq, sw, 200, 96)}
    k1, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    a = dense(params, X, cfg, site="ffn.wi", prng_key=k1)
    b = dense(params, X, cfg, site="ffn.wi", prng_key=k1)
    c = dense(params, X, cfg, site="ffn.wi", prng_key=k2)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    with pytest.raises(ValueError, match="randomness source"):
        dense(params, X, cfg, site="ffn.wi")


# ---------------------------------------------------------------------------
# Seed decorrelation: by site, and by layer inside a lax.scan stack
# ---------------------------------------------------------------------------
def test_sites_decorrelate_same_operands():
    """Identical operands + one noise_seed: different sites must draw
    different noise (content tweak alone cannot separate them)."""
    eng = engine_for(_noisy_dpu(), "ref")
    a = eng.matmul_float(X, W, site="attn.wk")
    b = eng.matmul_float(X, W, site="attn.wv")
    c = eng.matmul_float(X, W, site="attn.wk")
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_scan_stack_layers_decorrelate_via_site_folded_seeds():
    """Regression: same-shaped layers inside a lax.scan stack with
    IDENTICAL weights and inputs (content hash collision by construction)
    still draw decorrelated noise, because the layer index is folded into
    the site seed by the model stack."""
    eng = engine_for(_noisy_dpu(), "ref")
    w3 = jnp.broadcast_to(W, (3,) + W.shape)  # identical weights per layer

    def body(c, inp):
        w, idx = inp
        y = eng.matmul_float(X, w, site="ffn.wi", fold=idx)
        return c, y

    _, ys = jax.lax.scan(body, 0, (w3, jnp.arange(3)))
    noise = np.asarray(ys) - np.asarray(X @ W)
    assert not np.array_equal(noise[0], noise[1])
    assert not np.array_equal(noise[1], noise[2])

    # without the fold the three layers would collide bitwise
    def body_nofold(c, w):
        return c, eng.matmul_float(X, w, site="ffn.wi")

    _, ys0 = jax.lax.scan(body_nofold, 0, w3)
    np.testing.assert_array_equal(np.asarray(ys0[0]), np.asarray(ys0[1]))


def test_model_scan_layers_get_layer_folded_noise():
    """End-to-end regression: an LM whose scanned layers have ZERO weights
    everywhere (residual stream frozen, every layer sees identical
    operands — the content tweak cannot separate them) still decorrelates
    per-layer analog noise, because lm.py folds the scan index into the
    engine seed.  Observed through the residual stream: with N identical
    noise draws the layer contributions would add coherently; decorrelated
    draws partially cancel.  We check bit-level: two runs are reproducible,
    and a 2-layer stack differs from 2x the 1-layer contribution."""
    from repro.models import lm

    arch = registry.get("qwen2-0.5b")

    def build(num_layers):
        cfg = dataclasses.replace(
            arch.smoke_config,
            remat=False,
            num_layers=num_layers,
            photonic=_noisy_dpu(noise_seed=11),
            photonic_backend="ref",
        )
        params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
        # zero all layer weights: every layer computes pure noise on top of
        # an unchanged residual stream -> identical operands at every layer
        params["layers"] = jax.tree.map(jnp.zeros_like, params["layers"])
        return cfg, params

    toks = jnp.asarray(RNG.integers(0, 256, (1, 8)), jnp.int32)
    cfg2, params2 = build(2)
    l2a, _ = lm.lm_logits(params2, toks, cfg2)
    l2b, _ = lm.lm_logits(params2, toks, cfg2)
    np.testing.assert_array_equal(np.asarray(l2a), np.asarray(l2b))  # determinism

    # layer 0 vs layer 1 noise: recompute each layer's additive contribution
    # directly through the engine (zero weights => output is noise only)
    eng = engine_from_model_config(cfg2)
    d = cfg2.d_model
    h = jnp.zeros((1, 8, d), jnp.float32)
    w0 = jnp.zeros((d, 2 * cfg2.d_ff), jnp.float32)
    n0 = eng.matmul_float(h, w0, site="ffn.wi", fold=0)
    n1 = eng.matmul_float(h, w0, site="ffn.wi", fold=1)
    assert not np.array_equal(np.asarray(n0), np.asarray(n1))


# ---------------------------------------------------------------------------
# Serving: prepack-at-construction + zero weight-quantization decode
# ---------------------------------------------------------------------------
def _weight_round_count(fn, *args, min_size):
    from repro.photonic.engine import count_weight_round_ops

    return count_weight_round_ops(jax.make_jaxpr(fn)(*args).jaxpr, min_size)


def test_serve_engine_prepacks_and_decode_has_zero_weight_quant_ops():
    from repro.runtime import serve

    arch = registry.get("granite-3-8b")
    cfg = dataclasses.replace(
        arch.smoke_config,
        remat=False,
        photonic=DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0),
        photonic_backend="ref",
    )
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    eng = serve.Engine(arch, cfg, params, serve.ServeConfig(batch_size=2, max_seq=32))
    assert eng.photonic is not None

    def has_packed(node):
        if isinstance(node, PackedDense):
            return True
        if isinstance(node, dict):
            return any(has_packed(v) for v in node.values())
        return False

    assert has_packed(eng.params), "serve.Engine did not prepack weights"

    # decode jaxpr: zero round ops over weight-sized arrays after prepack
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)), jnp.int32)
    _, cache = arch.prefill(eng.params, {"tokens": toks}, cfg, 32)
    tok = toks[:, :1]
    min_w = cfg.d_model * cfg.d_ff // 2
    n_packed = _weight_round_count(
        lambda p, t, c: arch.decode(p, t, c, cfg), eng.params, tok, cache,
        min_size=min_w,
    )
    n_percall = _weight_round_count(
        lambda p, t, c: arch.decode(p, t, c, cfg), params, tok, cache,
        min_size=min_w,
    )
    assert n_packed == 0, f"{n_packed} weight-sized rounds survived prepack"
    assert n_percall > 0

    # and the engine still serves correctly
    reqs = [serve.Request(uid=0, prompt=np.arange(6, dtype=np.int32), max_new_tokens=4)]
    eng.run(reqs)
    assert len(reqs[0].output) >= 4


def test_serve_prepacked_outputs_match_per_call():
    """serve.Engine with prepacking produces the same tokens as the same
    engine forced onto the per-call-quantization path."""
    from repro.runtime import serve

    arch = registry.get("qwen2-0.5b")
    cfg = dataclasses.replace(
        arch.smoke_config,
        remat=False,
        photonic=DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0),
        photonic_backend="ref",
    )
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    prompts = [np.arange(5, dtype=np.int32) + i for i in range(3)]

    def run_serve(force_per_call):
        eng = serve.Engine(
            arch, cfg, params, serve.ServeConfig(batch_size=2, max_seq=32)
        )
        if force_per_call:
            eng.params = params  # bypass the prepack done at construction
        reqs = [
            serve.Request(uid=i, prompt=pr, max_new_tokens=4)
            for i, pr in enumerate(prompts)
        ]
        eng.run(reqs)
        return [r.output for r in reqs]

    assert run_serve(False) == run_serve(True)


# ---------------------------------------------------------------------------
# Legacy API stability
# ---------------------------------------------------------------------------
def test_legacy_photonic_gemm_matches_oracle_composition():
    """matmul_float (site=None) == quantize ∘ int_gemm ∘ dequantize with
    the unfolded seed derivation — the float entry point is the integer
    datapath between the two digital shoulders, bit for bit, noise
    included."""
    from repro.core.dpu import quantize_symmetric

    eng = engine_for(_noisy_dpu(noise_seed=9), "ref")
    y = eng.matmul_float(X, W)
    xq, sx = quantize_symmetric(X, 8)
    wq, sw = quantize_symmetric(W, 8, axis=0)
    gold = (
        np.asarray(eng.int_gemm(xq, wq), np.float32)
        * np.asarray(sx)
        * np.asarray(sw)
    )
    np.testing.assert_array_equal(np.asarray(y), gold.astype(np.float32))


def test_all_archs_smoke_with_engine_routed_photonic():
    """All ten architectures run a photonic-routed forward + decode step
    (ideal channel: engine output must match the digital int8 pipeline
    closely; attention + FFN + lm_head sites all engine-routed)."""
    rng = np.random.default_rng(0)
    for name in registry.names():
        arch = registry.get(name)
        cfg = dataclasses.replace(
            arch.smoke_config,
            remat=False,
            photonic=DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0),
            photonic_backend="ref",
        )
        params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
        B, T = 2, 8
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
        batch = {"tokens": toks}
        if arch.family == "vlm":
            batch["vision"] = jnp.asarray(
                rng.normal(size=(B, cfg.vision_seq, cfg.d_model)), jnp.float32
            )
        if arch.family == "audio":
            batch["audio_embed"] = jnp.asarray(
                rng.normal(size=(B, 2 * T, cfg.d_model)), jnp.float32
            )
        logits, cache = arch.prefill(params, batch, cfg, T + 4)
        assert bool(jnp.all(jnp.isfinite(logits))), name
        logits, cache = arch.decode(params, toks[:, :1], cache, cfg)
        assert bool(jnp.all(jnp.isfinite(logits))), name


# ---------------------------------------------------------------------------
# Review regressions: site-name agreement, absorbed MLA, legacy tile_c
# ---------------------------------------------------------------------------
def _derived_sites(defs, path=()):
    from repro.photonic.packing import _is_dense_def, site_name

    out = set()
    if _is_dense_def(defs):
        out.add(site_name(path))
    elif isinstance(defs, dict):
        for k, v in defs.items():
            out |= _derived_sites(v, path + (k,))
    return out


@pytest.mark.parametrize("name", registry.names())
def test_prepack_site_names_agree_with_call_time_sites(name):
    """Routing must agree between prepack time (names derived from the def
    tree) and call time (names passed to dense(site=...)) for ANY policy —
    so the two name sets must be identical per architecture."""
    from repro.photonic.engine import PhotonicEngine

    arch = registry.get(name)
    cfg = dataclasses.replace(
        arch.smoke_config,
        remat=False,
        photonic=DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0),
        photonic_backend="ref",
    )
    derived = _derived_sites(arch.param_defs(cfg))

    recorded = set()
    orig_float, orig_packed = PhotonicEngine.matmul_float, PhotonicEngine.matmul
    orig_grouped = PhotonicEngine.grouped_matmul

    def rec_float(self, x, w, *, site=None, **kw):
        recorded.add(site)
        return orig_float(self, x, w, site=site, **kw)

    def rec_packed(self, x, packed, *, site=None, **kw):
        recorded.add(site)
        return orig_packed(self, x, packed, site=site, **kw)

    def rec_grouped(self, x, w, *args, site=None, **kw):
        recorded.add(site)
        return orig_grouped(self, x, w, *args, site=site, **kw)

    PhotonicEngine.matmul_float = rec_float
    PhotonicEngine.matmul = rec_packed
    PhotonicEngine.grouped_matmul = rec_grouped
    try:
        rng = np.random.default_rng(0)
        params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
        B, T = 1, 8
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        if arch.family == "vlm":
            batch["vision"] = jnp.asarray(
                rng.normal(size=(B, cfg.vision_seq, cfg.d_model)), jnp.float32
            )
        if arch.family == "audio":
            batch["audio_embed"] = jnp.asarray(
                rng.normal(size=(B, 2 * T, cfg.d_model)), jnp.float32
            )
        arch.loss(params, batch, cfg)
        pb = {k: v for k, v in batch.items() if k != "labels"}
        _, cache = arch.prefill(params, pb, cfg, T + 2)
        arch.decode(params, toks[:, :1], cache, cfg)
    finally:
        PhotonicEngine.matmul_float = orig_float
        PhotonicEngine.matmul = orig_packed
        PhotonicEngine.grouped_matmul = orig_grouped

    recorded.discard(None)
    assert recorded == derived, (
        name,
        sorted(recorded - derived),
        sorted(derived - recorded),
    )


def test_serve_prepack_preserves_absorbed_mla_decode_bitwise():
    """mla_absorb decode consumes wuk/wuv as raw floats; serve.Engine must
    leave them unpacked so prepacked decode stays bitwise-equal."""
    from repro.runtime import serve

    arch = registry.get("deepseek-v2-lite-16b")
    cfg = dataclasses.replace(
        arch.smoke_config,
        remat=False,
        mla_absorb=True,
        photonic=DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0),
        photonic_backend="ref",
    )
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    eng = serve.Engine(arch, cfg, params, serve.ServeConfig(batch_size=1, max_seq=16))
    assert not isinstance(eng.params["layers"]["attn"]["wuk"]["w"], PackedDense)
    assert isinstance(eng.params["layers"]["attn"]["wq"]["w"], PackedDense)

    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    _, cache_a = arch.prefill(params, {"tokens": toks}, cfg, 16)
    _, cache_b = arch.prefill(eng.params, {"tokens": toks}, cfg, 16)
    la, _ = arch.decode(params, toks[:, :1], cache_a, cfg)
    lb, _ = arch.decode(eng.params, toks[:, :1], cache_b, cfg)
    np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def test_legacy_tile_c_parameter_honored():
    """int_gemm(tile_c=256) honours the caller's column tile (values
    above 128 are legal for the per-call pallas path)."""
    rng = np.random.default_rng(2)
    xq = jnp.asarray(rng.integers(-127, 128, (8, 256)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (256, 256)), jnp.int8)
    cfg = DPUConfig(organization="SMWA", bits=4, dpe_size=64)
    gold = exact_int_gemm(xq, wq)
    out = engine_for(cfg, "pallas").int_gemm(xq, wq, tile_c=256)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(gold))
