"""PR-8 fused hot path (DESIGN.md §14): in-kernel quant + GEMM epilogue,
fused-QKV attention, the dispatch-count summary, and the launch profile.

The load-bearing contracts:

* a fused-epilogue GEMM is bitwise-equal to the unfused composition
  (explicit quantize → ``int_gemm`` → digital rescale) under an ideal
  channel, on both backends, eager and jitted — including the tiling
  edge cases (non-divisible K/C, ``tile_c > 128``, R=1 decode rows);
* one fused-QKV GEMM (``fuse_qkv_params``) is bitwise-equal to the three
  separate projections, for both weight layouts the packer accepts;
* ``hlo_analysis.dispatch_summary`` proves the fusion *structurally*:
  the fused module's entry op sequence is strictly shorter.
"""

import dataclasses
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dpu import DPUConfig, quantize_symmetric
from repro.launch import hlo_analysis, profile
from repro.models import attention as attn
from repro.models.common import ModelConfig, init_tree
from repro.photonic import (
    ACTIVATIONS,
    Epilogue,
    EpilogueArgs,
    EpilogueSpec,
    PackedDense,
    engine_for,
    fuse_qkv_params,
    pack_dense,
)

DPU = DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0)
RNG = np.random.default_rng(0)


def _arr(*shape, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, jnp.float32)


def _manual_unfused(eng, x, pk, bias=None, activation=None):
    """The pre-fusion composition, op for op — the bitwise oracle."""
    xq, sx = quantize_symmetric(x, eng.dpu.operand_bits)
    acc = eng.int_gemm(xq, pk.wq, logical_kc=(pk.k, pk.c), tiling=pk.tiling)
    y = acc.astype(jnp.float32) * sx * pk.w_scale.astype(jnp.float32)[None, :]
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    if activation is not None:
        y = ACTIVATIONS[activation](y)
    return y


# ---------------------------------------------------------------------------
# Fused epilogue == unfused composition (tiling edge cases, both backends)
# ---------------------------------------------------------------------------
class TestFusedEpilogueBitwise:
    # r=1 is the decode row; 100/130/257 are deliberately non-divisible
    # by every tile size in play; c=384 forces multiple column tiles.
    @pytest.mark.parametrize("r,k,c", [(1, 64, 64), (3, 100, 257), (8, 130, 384)])
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("jitted", [False, True])
    def test_fused_matmul_matches_manual(self, r, k, c, backend, jitted):
        eng = engine_for(DPU, backend)
        pk = pack_dense({"w": _arr(k, c, scale=k**-0.5)}, eng)["w"]
        x = _arr(r, k)
        fused = lambda x: eng.matmul(x, pk, site="s")  # noqa: E731
        manual = lambda x: _manual_unfused(eng, x, pk)  # noqa: E731
        if jitted:
            fused, manual = jax.jit(fused), jax.jit(manual)
        np.testing.assert_array_equal(np.asarray(fused(x)), np.asarray(manual(x)))

    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("activation", [None, "gelu", "silu"])
    def test_bias_activation_ride_epilogue(self, backend, activation):
        eng = engine_for(DPU, backend)
        pk = pack_dense({"w": _arr(100, 130, scale=0.1)}, eng)["w"]
        b, x = _arr(130, scale=0.02), _arr(3, 100)
        ep = Epilogue(EpilogueSpec(bias=True, activation=activation), b)
        fused = jax.jit(lambda x: eng.matmul(x, pk, site="s", epilogue=ep))
        manual = jax.jit(lambda x: _manual_unfused(eng, x, pk, b, activation))
        np.testing.assert_allclose(
            np.asarray(fused(x)), np.asarray(manual(x)), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.parametrize("r,k,c", [(1, 64, 64), (5, 100, 257)])
    def test_pallas_matches_ref_bitwise(self, r, k, c):
        x, w, b = _arr(r, k), _arr(k, c, scale=0.1), _arr(c, scale=0.02)
        outs = {}
        for backend in ("ref", "pallas"):
            eng = engine_for(DPU, backend)
            # same float weight packed per backend: layouts differ
            # (pallas pads to its tiling), values must not
            pk = pack_dense({"w": w}, eng)["w"]
            ep = Epilogue(EpilogueSpec(bias=True), b)
            outs[backend] = np.asarray(eng.matmul(x, pk, site="s", epilogue=ep))
        np.testing.assert_array_equal(outs["ref"], outs["pallas"])

    @pytest.mark.parametrize("with_epilogue", [False, True])
    def test_tile_c_above_128(self, with_epilogue):
        """int_gemm honours a caller tile_c above 128 (legal, layout-only)."""
        k, c = 96, 200
        w = _arr(k, c, scale=0.1)
        wq = jnp.round(jnp.clip(w * 10, -7, 7)).astype(jnp.int8)
        x = _arr(4, k)
        xq, sx = quantize_symmetric(x, DPU.operand_bits)
        args = None
        if with_epilogue:
            args = EpilogueArgs(
                spec=EpilogueSpec(), x_scale=sx, w_scale=jnp.full((c,), 0.1)
            )
        ref = engine_for(DPU, "ref").int_gemm(
            xq, wq, epilogue=args
        )
        for tile_c in (128, 256):
            out = engine_for(DPU, "pallas").int_gemm(
                xq, wq, tile_c=tile_c, epilogue=args
            )
            np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_float_activations_need_epilogue(self):
        eng = engine_for(DPU, "ref")
        wq = jnp.ones((8, 8), jnp.int8)
        with pytest.raises(TypeError, match="EpilogueArgs"):
            eng.int_gemm(_arr(2, 8), wq)


# ---------------------------------------------------------------------------
# fuse_qkv_params — one QKV bank == three separate projections
# ---------------------------------------------------------------------------
def _qkv_params(d, eng=None, bias=False):
    params = {}
    for name in ("wq", "wk", "wv"):
        w = _arr(d, d, scale=d**-0.5)
        if eng is not None:
            p = pack_dense({"w": w}, eng)
        else:
            p = {"w": w}
        if bias:
            p["b"] = _arr(d, scale=0.02)
        params[name] = p
    params["wo"] = {"w": _arr(d, d, scale=d**-0.5)}
    return params


class TestFuseQKV:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("bias", [False, True])
    def test_packed_layout_bitwise(self, backend, bias):
        d, eng = 48, engine_for(DPU, backend)
        params = _qkv_params(d, eng=eng, bias=bias)
        fused = fuse_qkv_params(params, eng)
        assert "wqkv" in fused and "wq" not in fused and "wo" in fused
        x = _arr(3, d)

        def ep(p):
            return Epilogue(EpilogueSpec(bias=bias), p.get("b"))

        y = eng.matmul(
            x, fused["wqkv"]["w"], site="attn.wqkv", epilogue=ep(fused["wqkv"])
        )
        parts = []
        for name in ("wq", "wk", "wv"):
            p = params[name]
            parts.append(
                eng.matmul(x, p["w"], site=f"attn.{name}", epilogue=ep(p))
            )
        np.testing.assert_array_equal(
            np.asarray(y), np.asarray(jnp.concatenate(parts, axis=-1))
        )

    def test_int8_stored_layout(self):
        """The int8 layout the packer fuses is the prepacked one: the fused
        bank holds the three banks' logical int8 columns and per-column
        scales side by side, re-laid out once for the fused width."""
        d = 32
        eng = engine_for(DPU, "pallas")
        params = _qkv_params(d, eng=eng)
        fused = fuse_qkv_params(params, eng)["wqkv"]["w"]
        assert isinstance(fused, PackedDense) and fused.wq.dtype == jnp.int8
        assert fused.w_scale.shape == (3 * d,)
        packs = [params[n]["w"] for n in ("wq", "wk", "wv")]
        np.testing.assert_array_equal(
            np.asarray(fused.logical_wq()),
            np.asarray(jnp.concatenate([pk.logical_wq() for pk in packs], -1)),
        )
        np.testing.assert_array_equal(
            np.asarray(fused.w_scale),
            np.asarray(jnp.concatenate([pk.w_scale for pk in packs])),
        )

    def test_float_layout(self):
        d = 32
        params = _qkv_params(d)
        fused = fuse_qkv_params(params, engine_for(DPU, "ref"))
        assert fused["wqkv"]["w"].shape == (d, 3 * d)

    def test_mixed_layouts_rejected(self):
        eng = engine_for(DPU, "ref")
        params = _qkv_params(32, eng=eng)
        params["wk"] = {"w": _arr(32, 32)}  # float amid packed
        with pytest.raises(ValueError, match="mix"):
            fuse_qkv_params(params, eng)

    def test_partial_bias_rejected(self):
        eng = engine_for(DPU, "ref")
        params = _qkv_params(32, eng=eng, bias=True)
        del params["wk"]["b"]
        with pytest.raises(ValueError, match="bias"):
            fuse_qkv_params(params, eng)

    def test_missing_projection_rejected(self):
        eng = engine_for(DPU, "ref")
        params = _qkv_params(32, eng=eng)
        del params["wv"]
        with pytest.raises(KeyError, match="wv"):
            fuse_qkv_params(params, eng)

    def test_model_qkv_proj_uses_fused_bank(self):
        """gqa_attention with a fused bank == with separate projections."""
        cfg = ModelConfig(
            d_model=32, num_heads=4, num_kv_heads=4, num_layers=1,
            photonic=DPU, photonic_backend="ref",
        )
        params = init_tree(attn.gqa_def(cfg), jax.random.PRNGKey(0), jnp.float32)
        eng = engine_for(DPU, "ref")
        fused = fuse_qkv_params(params, eng)
        x = _arr(1, 4, 32)
        pos = jnp.arange(4)
        y_sep = attn.gqa_attention(params, x, cfg, positions=pos)
        y_fused = attn.gqa_attention(fused, x, cfg, positions=pos)
        np.testing.assert_array_equal(np.asarray(y_sep), np.asarray(y_fused))


# ---------------------------------------------------------------------------
# attn_impl routing (flash prototype behind the config switch)
# ---------------------------------------------------------------------------
class TestAttnImpl:
    def test_flash_agrees_with_chunked(self):
        cfg = ModelConfig(d_model=32, num_heads=2, num_kv_heads=2, num_layers=1)
        params = init_tree(attn.gqa_def(cfg), jax.random.PRNGKey(1), jnp.float32)
        x = _arr(1, 16, 32, scale=0.5)
        pos = jnp.arange(16)
        y_ch = attn.gqa_attention(params, x, cfg, positions=pos)
        cfg_fl = dataclasses.replace(cfg, attn_impl="flash")
        y_fl = attn.gqa_attention(params, x, cfg_fl, positions=pos)
        np.testing.assert_allclose(
            np.asarray(y_ch), np.asarray(y_fl), rtol=2e-5, atol=2e-5
        )

    def test_invalid_attn_impl_rejected(self):
        with pytest.raises(ValueError, match="attn_impl"):
            ModelConfig(attn_impl="paged-flash")

    def test_flash_reexport_surface(self):
        # models/ must reach flash via repro.photonic (RPR003); the
        # re-export is the same callable as the kernel op.
        from repro.kernels.flash_attention.ops import flash_attention as raw
        from repro.photonic.flash import flash_attention

        assert flash_attention is raw


# ---------------------------------------------------------------------------
# dispatch_summary — the structural fusion check (satellite b)
# ---------------------------------------------------------------------------
class TestDispatchSummary:
    def test_counts_entry_ops_not_bookkeeping(self):
        f = jax.jit(lambda x, w: jax.nn.gelu(x @ w))
        x, w = _arr(8, 16), _arr(16, 4)
        hlo = f.lower(x, w).compile().as_text()
        s = hlo_analysis.dispatch_summary(hlo)
        assert s["entry_computation"] is not None
        assert 1 <= s["dispatch_count"] <= 4
        assert s["entry_fusions"] >= 1
        assert "parameter" not in s["entry_ops_by_kind"]
        assert s["total_ops_loop_adjusted"] >= s["dispatch_count"]

    def test_fused_entry_sequence_strictly_shorter(self):
        """The benchmark's structural claim, as a contract test: the
        fused hot path compiles to fewer entry dispatches than the
        legacy shoulder-op composition."""
        eng = engine_for(DPU, "ref")
        pks = [pack_dense({"w": _arr(48, 48, scale=0.1)}, eng)["w"] for _ in range(3)]
        bs = [_arr(48, scale=0.02) for _ in range(3)]

        def legacy(x):
            outs = [
                _manual_unfused(eng, x, pk, b) for pk, b in zip(pks, bs)
            ]
            return jnp.concatenate(outs, axis=-1)

        pk_f = pack_dense(
            {"w": jnp.concatenate([pk.dequant() for pk in pks], axis=-1)}, eng
        )["w"]
        b_f = jnp.concatenate(bs)

        def fused(x):
            ep = Epilogue(EpilogueSpec(bias=True), b_f)
            return eng.matmul(x, pk_f, site="s", epilogue=ep)

        x = _arr(1, 48)
        counts = {}
        for name, fn in (("legacy", legacy), ("fused", fused)):
            hlo = jax.jit(fn).lower(x).compile().as_text()
            counts[name] = hlo_analysis.dispatch_summary(hlo)["dispatch_count"]
        assert counts["fused"] < counts["legacy"], counts


# ---------------------------------------------------------------------------
# launch profile
# ---------------------------------------------------------------------------
class TestLaunchProfile:
    def test_merge_user_flags_win(self, monkeypatch):
        monkeypatch.setenv(
            "XLA_FLAGS", "--xla_cpu_parallel_codegen_split_count=2 --xla_foo=1"
        )
        merged = profile._merge_xla_flags(
            ["--xla_cpu_parallel_codegen_split_count=8", "--xla_bar=0"]
        )
        opts = dict(o.split("=", 1) for o in merged.split())
        # the user's value survives; non-conflicting defaults are appended
        assert opts["--xla_cpu_parallel_codegen_split_count"] == "2"
        assert opts["--xla_foo"] == "1"
        assert opts["--xla_bar"] == "0"

    @pytest.fixture()
    def restore_cache_config(self):
        import jax

        keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")
        saved = {k: getattr(jax.config, k) for k in keys}
        yield
        for k, v in saved.items():
            jax.config.update(k, v)

    @pytest.mark.parametrize("env_set", [True, False], ids=["env", "checkout"])
    def test_apply_returns_describe(
        self, tmp_path, monkeypatch, restore_cache_config, env_set
    ):
        monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
        monkeypatch.delenv("TF_CPP_MIN_LOG_LEVEL", raising=False)
        if env_set:
            want = str(tmp_path / "cache")
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        else:
            # Unset: one fixed directory inside the checkout, never a
            # temporary or per-process one.
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
        desc = profile.apply()
        # user-set option preserved, curated defaults appended
        assert "--xla_force_host_platform_device_count=4" in desc["xla_flags"]
        assert "--xla_cpu_parallel_codegen_split_count" in desc["xla_flags"]
        assert desc["jax_compilation_cache_dir"] == want
        assert os.path.isdir(want)
        assert desc["tf_cpp_min_log_level"] == "3"
        assert profile.child_env({})["JAX_COMPILATION_CACHE_DIR"] == want

    def test_child_env_injects_cache_and_tcmalloc(self, monkeypatch):
        monkeypatch.delenv("LD_PRELOAD", raising=False)
        env = profile.child_env({"PATH": "/usr/bin"})
        assert env["PATH"] == "/usr/bin"
        assert "JAX_COMPILATION_CACHE_DIR" in env
        lib = profile.find_tcmalloc()
        if lib is not None:
            assert lib in env.get("LD_PRELOAD", "")
        else:
            assert "LD_PRELOAD" not in env

    def test_benchmark_json_records_profile(self):
        # The smoke harness records the profile into the committed JSON;
        # keep the schema keys stable (CI greps them).
        desc = profile.describe()
        for key in (
            "tcmalloc_found", "tcmalloc_active", "xla_flags",
            "jax_compilation_cache_dir", "tf_cpp_min_log_level",
        ):
            assert key in desc
