"""PR-9 execution-mode + unified-GEMM-surface contracts (DESIGN.md §15).

Bit-sliced execution: int operands decomposed into ``plane_bits``-wide
signed-magnitude planes, each plane pair run through the analog channel
re-referred to the plane full-scale, recombined with exact digital
shifts.  Contracts under test:

1. ideal channel  => bit-identical to the unsliced exact GEMM, on both
   analog backends, eager and jit;
2. noisy channel  => deterministic per (engine, seed, site, fold, shard,
   plane) with decorrelated per-plane streams;
3. the ``epilogue=`` / ``slicing=`` surface is bitwise-identical to the
   unfused composition it replaces: exact integer GEMM, then
   ``apply_epilogue`` on the accumulator.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from repro.core.dpu import DPUConfig, quantize_symmetric
from repro.kernels.photonic_gemm.epilogue import apply_epilogue
from repro.kernels.photonic_gemm.ref import exact_int_gemm
from repro.models.common import ModelConfig, dense
from repro.noise import build_channel_model
from repro.photonic import (
    Epilogue,
    EpilogueSpec,
    PhotonicEngine,
    SlicingSpec,
    engine_for,
    pack_dense,
    resolve_slicing,
)

RNG = np.random.default_rng(7)
XQ = jnp.asarray(RNG.integers(-127, 128, (5, 40), dtype=np.int8))
WQ = jnp.asarray(RNG.integers(-127, 128, (40, 9), dtype=np.int8))
X = jnp.asarray(RNG.normal(size=(4, 40)), jnp.float32)
W = jnp.asarray(RNG.normal(size=(40, 24)), jnp.float32)
B = jnp.asarray(RNG.normal(size=(24,)), jnp.float32)


def _ideal_dpu(n=16):
    return DPUConfig(organization="SMWA", bits=4, dpe_size=n)


def _unfused_gold(spec, bias):
    """The float GEMM of ``X @ W`` spelled out: per-tensor / per-column
    int8 quantization, the exact integer GEMM, then ``apply_epilogue``
    (jitted, the compilation regime every engine backend applies its
    epilogue in)."""
    xq, sx = quantize_symmetric(X, 8)
    wq, sw = quantize_symmetric(W, 8, axis=0)
    epi = jax.jit(apply_epilogue, static_argnames="spec")
    return epi(exact_int_gemm(xq, wq), sx, sw.reshape(-1), bias, spec)


def _noisy_dpu(n=16, platform="SIN", seed=11):
    ch = build_channel_model(
        "SMWA", n=n, bits=4, datarate_gs=5.0, platform=platform
    )
    return DPUConfig(
        organization="SMWA", bits=4, dpe_size=n, channel=ch, noise_seed=seed
    )


# ---------------------------------------------------------------------------
# Contract 1: ideal channel => sliced == exact, bitwise
# ---------------------------------------------------------------------------
class TestIdealBitwise:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("plane_bits", [2, 4])
    @pytest.mark.parametrize("jitted", [False, True])
    def test_sliced_equals_exact(self, backend, plane_bits, jitted):
        eng = engine_for(_ideal_dpu(), backend, slicing=plane_bits)
        fn = eng.int_gemm
        if jitted:
            fn = jax.jit(lambda a, b: eng.int_gemm(a, b))
        out = fn(XQ, WQ)
        gold = exact_int_gemm(XQ, WQ)
        assert out.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out), np.asarray(gold))

    def test_per_call_slicing_override(self):
        eng = engine_for(_ideal_dpu(), "ref")
        out = eng.int_gemm(XQ, WQ, slicing=2)
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(exact_int_gemm(XQ, WQ))
        )
        # "none" forces the unsliced path on a sliced engine.
        sliced = eng.with_slicing(2)
        out2 = sliced.int_gemm(XQ, WQ, slicing="none")
        np.testing.assert_array_equal(
            np.asarray(out2), np.asarray(exact_int_gemm(XQ, WQ))
        )

    def test_exact_backend_ignores_slicing(self):
        a = engine_for(_ideal_dpu(), "exact").int_gemm(XQ, WQ)
        b = engine_for(_ideal_dpu(), "exact", slicing=2).int_gemm(XQ, WQ)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_sliced_matmul_with_epilogue_equals_exact_shim(self, backend):
        """Full float surface: sliced ideal engine with the epilogue ==
        the exact engine with the same epilogue."""
        eng = engine_for(_ideal_dpu(), backend, slicing=2)
        gold_eng = engine_for(_ideal_dpu(), "exact")
        ep = Epilogue(EpilogueSpec(bias=True, activation="gelu"), B)
        out = eng.matmul_float(X, W, site="s", epilogue=ep)
        gold = gold_eng.matmul_float(X, W, site="s", epilogue=ep)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(gold))


# ---------------------------------------------------------------------------
# Contract 2: noisy channel => deterministic, decorrelated planes
# ---------------------------------------------------------------------------
class TestNoisyDeterminism:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_same_seed_same_result(self, backend):
        eng = engine_for(_noisy_dpu(), backend, slicing=2)
        a = eng.int_gemm(XQ, WQ, site="s", fold=1)
        b = eng.int_gemm(XQ, WQ, site="s", fold=1)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        jit_out = jax.jit(lambda x, w: eng.int_gemm(x, w, site="s", fold=1))(
            XQ, WQ
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(jit_out))

    def test_different_seed_differs(self):
        a = engine_for(_noisy_dpu(seed=11), "ref", slicing=2).int_gemm(XQ, WQ)
        b = engine_for(_noisy_dpu(seed=12), "ref", slicing=2).int_gemm(XQ, WQ)
        assert not np.array_equal(np.asarray(a), np.asarray(b))

    def test_plane_stream_decorrelated(self):
        """The plane index perturbs the stream seed: the same GEMM seeded
        at different plane indices draws different noise."""
        eng = engine_for(_noisy_dpu(), "ref")
        a = eng.int_gemm(XQ, WQ, site="s", plane=0)
        b = eng.int_gemm(XQ, WQ, site="s", plane=1)
        c = eng.int_gemm(XQ, WQ, site="s")  # no plane stream at all
        assert not np.array_equal(np.asarray(a), np.asarray(b))
        assert not np.array_equal(np.asarray(a), np.asarray(c))

    def test_sliced_noise_is_smaller(self):
        """The physics the mode buys: per-plane passes see the detector
        sigma re-referred to the plane full-scale, so the sliced result
        lands closer to exact than the unsliced one."""
        gold = np.asarray(exact_int_gemm(XQ, WQ), np.float64)
        base = engine_for(_noisy_dpu(platform="SOI"), "ref")
        err_full = np.abs(np.asarray(base.int_gemm(XQ, WQ), np.float64) - gold)
        err_sliced = np.abs(
            np.asarray(base.with_slicing(2).int_gemm(XQ, WQ), np.float64) - gold
        )
        assert err_sliced.mean() < err_full.mean()


# ---------------------------------------------------------------------------
# Contract 3: the epilogue= surface == the unfused composition, bitwise
# ---------------------------------------------------------------------------
class TestUnifiedSurface:
    @pytest.mark.parametrize("backend", ["ref", "pallas", "exact"])
    def test_epilogue_keyword_equals_legacy_shim(self, backend):
        """``epilogue=`` on the per-call and prepacked float entry points
        == exact integer GEMM + ``apply_epilogue``, bit for bit."""
        spec = EpilogueSpec(bias=True, activation="gelu")
        gold = np.asarray(_unfused_gold(spec, B))
        eng = engine_for(_ideal_dpu(), backend)
        ep = Epilogue(spec, B)
        a = eng.matmul_float(X, W, site="s", epilogue=ep)
        np.testing.assert_array_equal(np.asarray(a), gold)
        packed = pack_dense({"w": W}, eng)["w"]
        c = eng.matmul(X, packed, site="s", epilogue=ep)
        np.testing.assert_array_equal(np.asarray(c), gold)

    def test_bias_free_spec_accepted(self):
        eng = engine_for(_ideal_dpu(), "ref")
        spec = EpilogueSpec(activation="gelu")
        a = eng.matmul_float(X, W, site="s", epilogue=spec)
        b = eng.matmul_float(X, W, site="s", epilogue=Epilogue(spec, None))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(_unfused_gold(spec, None))
        )

    def test_mixing_epilogue_and_legacy_raises(self):
        """``dense`` takes only a bias-free spec: its bias operand comes
        from the param def, so a spec asking for one, or an ``Epilogue``
        tuple carrying one, is rejected eagerly."""
        cfg = ModelConfig(photonic=_ideal_dpu(), photonic_backend="ref")
        params = {"w": W, "b": B}
        with pytest.raises(TypeError, match="param def"):
            dense(params, X, cfg, site="s", epilogue=EpilogueSpec(bias=True))
        with pytest.raises(TypeError, match="EpilogueSpec"):
            dense(
                params,
                X,
                cfg,
                site="s",
                epilogue=Epilogue(EpilogueSpec(bias=True), B),
            )
        # The accepted spelling: the activation from the spec, the bias
        # from the param def.
        y = dense(params, X, cfg, site="s", epilogue=EpilogueSpec(activation="gelu"))
        gold = engine_for(_ideal_dpu(), "ref").matmul_float(
            X, W, site="s", epilogue=Epilogue(EpilogueSpec(True, "gelu"), B)
        )
        np.testing.assert_array_equal(np.asarray(y), np.asarray(gold))

    def test_epilogue_validation(self):
        eng = engine_for(_ideal_dpu(), "ref")
        with pytest.raises(TypeError, match="bias"):
            eng.matmul_float(X, W, epilogue=EpilogueSpec(bias=True))
        with pytest.raises(TypeError, match="disagrees"):
            eng.matmul_float(X, W, epilogue=Epilogue(EpilogueSpec(bias=True), None))
        with pytest.raises(TypeError, match="EpilogueSpec"):
            eng.matmul_float(X, W, epilogue="gelu")

    def test_model_config_resolves_slicing_eagerly(self):
        cfg = ModelConfig(photonic=_ideal_dpu(), photonic_slicing="2")
        assert cfg.photonic_slicing == SlicingSpec(2)
        assert ModelConfig(photonic=_ideal_dpu()).photonic_slicing is None
        with pytest.raises(ValueError):
            ModelConfig(photonic=_ideal_dpu(), photonic_slicing="both")


# ---------------------------------------------------------------------------
# Mode resolution + structured describe()
# ---------------------------------------------------------------------------
class TestResolveSlicing:
    def test_round_trips(self):
        assert resolve_slicing(None) is None
        assert resolve_slicing("none") is None
        assert resolve_slicing(" off ") is None
        assert resolve_slicing("") is None
        assert resolve_slicing(2) == SlicingSpec(2)
        assert resolve_slicing("4") == SlicingSpec(4)
        spec = SlicingSpec(2)
        assert resolve_slicing(spec) is spec

    def test_invalid_rejected(self):
        with pytest.raises(ValueError):
            resolve_slicing(True)  # bool is an int; rejected explicitly
        with pytest.raises(ValueError):
            resolve_slicing(3)  # planes must tile operand widths
        with pytest.raises(ValueError):
            resolve_slicing("both")
        with pytest.raises(ValueError):
            SlicingSpec(plane_bits=5)

    def test_num_planes(self):
        assert SlicingSpec(2).num_planes(8) == 4
        assert SlicingSpec(4).num_planes(8) == 2
        assert SlicingSpec(8).num_planes(8) == 1

    def test_with_slicing_is_frozen_replace(self):
        eng = engine_for(_ideal_dpu(), "ref")
        assert eng.with_slicing(None) is eng
        sliced = eng.with_slicing(2)
        assert sliced is not eng
        assert sliced.slicing == SlicingSpec(2)
        assert sliced.with_slicing(SlicingSpec(2)) is sliced
        assert eng.slicing is None  # original untouched (frozen)

    def test_engine_constructor_normalizes(self):
        eng = PhotonicEngine(dpu=_ideal_dpu(), slicing="2")
        assert eng.slicing == SlicingSpec(2)
        with pytest.raises(ValueError):
            PhotonicEngine(dpu=_ideal_dpu(), slicing="both")


class TestEngineInfo:
    def test_str_preserves_legacy_text_at_defaults(self):
        eng = engine_for(_ideal_dpu(n=21), "ref")
        info = eng.describe()
        assert str(info) == (
            "ref backend, SMWA (blocks S->M->W->A->Sigma, through 2) "
            "B=4 N=21 @ 5.0 GS/s, channel=ideal, "
            "sites include=['*'] exclude=['router']"
        )

    def test_str_extends_for_platform_and_slicing(self):
        eng = engine_for(_noisy_dpu(n=21), "ref", slicing=2)
        text = str(eng.describe())
        assert "channel=analog, platform=SIN, slicing=2b planes, sites" in text

    def test_to_dict_round_trip(self):
        info = engine_for(_noisy_dpu(), "ref", slicing=2).describe()
        d = info.to_dict()
        assert d["platform"] == "SIN"
        assert d["slicing"] == 2
        assert d["organization"] == "SMWA"
        assert d["channel"] == "analog"
        # Frozen + hashable (rides jit closures / dry-run manifests).
        assert hash(info) == hash(dataclasses.replace(info))


# ---------------------------------------------------------------------------
# Benchmark registry contract (benchmarks/run.py)
# ---------------------------------------------------------------------------
class TestRegisterBenchmark:
    def test_valid_registration(self, monkeypatch):
        monkeypatch.setattr(bench_run, "_REGISTRY", {})

        @bench_run.register_benchmark("t1")
        def bench(smoke: bool = False):
            return {"ok": True}

        assert bench_run.registered_benchmarks() == {"t1": bench}

    def test_duplicate_name_raises(self, monkeypatch):
        monkeypatch.setattr(bench_run, "_REGISTRY", {})

        @bench_run.register_benchmark("dup")
        def bench(smoke: bool = False):
            return {}

        with pytest.raises(ValueError, match="already registered"):

            @bench_run.register_benchmark("dup")
            def bench2(smoke: bool = False):
                return {}

    def test_bad_signature_raises(self, monkeypatch):
        monkeypatch.setattr(bench_run, "_REGISTRY", {})
        with pytest.raises(TypeError, match="smoke"):

            @bench_run.register_benchmark("nosmoke")
            def bench(n: int = 3):
                return {}

        with pytest.raises(TypeError, match="smoke"):

            @bench_run.register_benchmark("wrongdefault")
            def bench3(smoke: bool = True):
                return {}

    def test_bad_name_raises(self):
        with pytest.raises(TypeError, match="non-empty str"):
            bench_run.register_benchmark("")
        with pytest.raises(TypeError, match="non-empty str"):
            bench_run.register_benchmark(3)

    def test_all_shipped_benchmarks_register(self):
        # Importing a benchmark module registers its entry point exactly
        # once (idempotent across repeated imports).
        import benchmarks.org_accuracy  # noqa: F401
        import benchmarks.tp_scaling  # noqa: F401

        names = set(bench_run.registered_benchmarks())
        assert {"org_accuracy", "tp_scaling"} <= names
