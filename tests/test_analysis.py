"""repro.analysis — Level-1 rule fixtures, the clean-tree bar, and the
Level-2 contract passes (DESIGN.md §12).

Every RPR rule gets a violating fixture snippet proving it fires (ID +
location), plus a clean twin proving the blessed idiom passes. The
clean-tree test is the acceptance criterion itself: zero findings over
the repo with zero suppressions under ``src/``.
"""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.analysis import (
    ContractChecker,
    all_rules,
    check_source,
    count_primitives,
    count_weight_round_ops,
    run_all,
)
from repro.analysis.contracts import ContractViolation, iter_eqns
from repro.compat import Mesh, PartitionSpec as P
from repro.core.dpu import DPUConfig
from repro.noise import build_channel_model
from repro.photonic import engine_for
from repro.photonic import sharded as tp_sharded

ROOT = Path(__file__).resolve().parents[1]

RNG = np.random.default_rng(0)


def rule_ids(findings):
    return [f.rule for f in findings]


# ---------------------------------------------------------------------------
# Registry sanity
# ---------------------------------------------------------------------------
def test_rule_registry_complete():
    rules = all_rules()
    ids = [r.id for r in rules]
    assert ids == [
        "RPR001", "RPR002", "RPR003", "RPR004", "RPR005", "RPR006", "RPR007",
        "RPR008", "RPR009", "RPR010",
    ]
    for r in rules:
        assert r.summary and r.rationale, f"{r.id} lacks docs"


# ---------------------------------------------------------------------------
# RPR001 — compat isolation
# ---------------------------------------------------------------------------
class TestRPR001:
    def test_attribute_path_fires(self):
        src = 'import jax\n\nmesh = jax.make_mesh((1,), ("d",))\n'
        f = check_source(src, "src/repro/foo.py")
        assert rule_ids(f) == ["RPR001"]
        assert f[0].line == 3

    def test_from_import_fires(self):
        src = "from jax.experimental.shard_map import shard_map\n"
        f = check_source(src, "src/repro/foo.py")
        assert rule_ids(f) == ["RPR001"]
        assert f[0].line == 1

    def test_name_from_jax_module_fires(self):
        src = "from jax.sharding import AxisType\n"
        assert rule_ids(check_source(src, "src/repro/foo.py")) == ["RPR001"]

    @pytest.mark.parametrize(
        "src",
        [
            "ca = compiled.cost_analysis()\n",
            "from jax.experimental.pallas import tpu as pltpu\n\n"
            "p = pltpu.CompilerParams(dimension_semantics=('parallel',))\n",
            "import jax\n\nys = jax.tree.map(f, xs)\n",
        ],
        ids=["cost_analysis", "pallas_compiler_params", "jax_tree"],
    )
    def test_stable_jax_surface_is_clean(self, src):
        # Symbols that are stable on the installed JAX are spelled directly.
        assert check_source(src, "src/repro/foo.py") == []

    def test_compat_module_and_its_tests_exempt(self):
        src = "from jax.experimental.shard_map import shard_map\n"
        assert check_source(src, "src/repro/compat.py") == []
        assert check_source(src, "tests/test_compat.py") == []


# ---------------------------------------------------------------------------
# RPR002 — single-point org resolution
# ---------------------------------------------------------------------------
class TestRPR002:
    def test_upper_on_org_fires(self):
        src = "def f(org):\n    return org.strip().upper()\n"
        f = check_source(src, "src/repro/foo.py")
        assert rule_ids(f) == ["RPR002"]
        assert f[0].line == 2

    def test_lower_on_organization_attr_fires(self):
        src = "def f(cfg):\n    return cfg.organization.lower()\n"
        assert rule_ids(check_source(src, "src/repro/foo.py")) == ["RPR002"]

    def test_non_org_receiver_clean(self):
        src = "def f(s):\n    return s.upper()\n"
        assert check_source(src, "src/repro/foo.py") == []

    def test_orgs_module_exempt(self):
        src = "def f(order):\n    return order.strip().upper()\n"
        assert check_source(src, "src/repro/orgs.py") == []


# ---------------------------------------------------------------------------
# RPR003 — engine-only GEMM routing in models/runtime
# ---------------------------------------------------------------------------
class TestRPR003:
    def test_kernel_import_fires_in_models(self):
        src = "from repro.kernels.photonic_gemm.ref import photonic_gemm_ref\n"
        f = check_source(src, "src/repro/models/foo.py")
        assert rule_ids(f) == ["RPR003"]
        assert f[0].line == 1

    def test_backend_call_fires_in_runtime(self):
        src = "def step(a, b):\n    return exact_int_gemm(a, b)\n"
        f = check_source(src, "src/repro/runtime/foo.py")
        assert rule_ids(f) == ["RPR003"]
        assert f[0].line == 2

    def test_photonic_and_kernels_zones_exempt(self):
        src = "def step(a, b):\n    return exact_int_gemm(a, b)\n"
        assert check_source(src, "src/repro/photonic/foo.py") == []
        assert check_source(src, "src/repro/kernels/foo.py") == []

    def test_engine_route_clean(self):
        src = (
            "def step(eng, x, packed):\n"
            '    return eng.matmul(x, packed, site="ffn.wi")\n'
        )
        assert check_source(src, "src/repro/models/foo.py") == []


# ---------------------------------------------------------------------------
# RPR004 — engine-derived randomness in models/kernels
# ---------------------------------------------------------------------------
class TestRPR004:
    def test_sampler_fires_in_models(self):
        src = (
            "import jax\n\n"
            "def forward(key, x):\n"
            "    return x + jax.random.normal(key, x.shape)\n"
        )
        f = check_source(src, "src/repro/models/foo.py")
        assert rule_ids(f) == ["RPR004"]
        assert f[0].line == 4

    def test_init_functions_exempt(self):
        src = (
            "import jax\n\n"
            "def init_weights(key):\n"
            "    return jax.random.normal(key, (4, 4))\n"
        )
        assert check_source(src, "src/repro/models/foo.py") == []

    def test_key_plumbing_clean(self):
        src = (
            "import jax\n\n"
            "def forward(key, i):\n"
            "    return jax.random.fold_in(key, i)\n"
        )
        assert check_source(src, "src/repro/models/foo.py") == []

    def test_out_of_scope_paths_clean(self):
        src = (
            "import jax\n\n"
            "def sample(key, logits):\n"
            "    return jax.random.categorical(key, logits)\n"
        )
        assert check_source(src, "src/repro/runtime/foo.py") == []


# ---------------------------------------------------------------------------
# RPR005 — reciprocal-multiply quantization
# ---------------------------------------------------------------------------
class TestRPR005:
    def test_constant_divisor_fires_once(self):
        src = (
            "import jax.numpy as jnp\n\n"
            "def _quantize(x, amax):\n"
            "    scale = jnp.maximum(amax, 1e-12) / 127.0\n"
            "    return jnp.round(x / scale)\n"
        )
        f = check_source(src, "src/repro/foo.py")
        assert rule_ids(f) == ["RPR005"]
        assert f[0].line == 4  # x / scale (traced divisor) must NOT flag

    def test_const_expression_divisor_fires(self):
        src = (
            "def quantize(amax):\n"
            "    return amax / float(2 ** 7 - 1)\n"
        )
        assert rule_ids(check_source(src, "src/repro/foo.py")) == ["RPR005"]

    def test_reciprocal_multiply_clean(self):
        src = (
            "import jax.numpy as jnp\n\n"
            "def _quantize(x, amax):\n"
            "    scale = jnp.maximum(amax, 1e-12) * (1.0 / 127.0)\n"
            "    return jnp.round(x / scale)\n"
        )
        assert check_source(src, "src/repro/foo.py") == []

    def test_non_quant_function_out_of_scope(self):
        src = "def halve(x):\n    return x / 2.0\n"
        assert check_source(src, "src/repro/foo.py") == []


# ---------------------------------------------------------------------------
# RPR006 — no tensor_parallel under shard_map
# ---------------------------------------------------------------------------
class TestRPR006:
    def test_named_body_fires(self):
        src = (
            "from repro import compat\n"
            "from repro.photonic.sharded import tensor_parallel\n\n"
            "def body(x):\n"
            '    with tensor_parallel(mesh, "tp"):\n'
            "        return x\n\n"
            "def run(mesh, x, spec):\n"
            "    return compat.shard_map(\n"
            "        body, mesh=mesh, in_specs=(spec,), out_specs=spec\n"
            "    )(x)\n"
        )
        f = check_source(src, "src/repro/foo.py")
        assert rule_ids(f) == ["RPR006"]
        assert f[0].line == 5

    def test_lambda_body_fires(self):
        src = (
            "import repro.photonic.sharded as tp\n\n"
            "out = compat.shard_map(\n"
            '    lambda x: tp.tensor_parallel(mesh, "tp"), mesh=mesh,\n'
            "    in_specs=(spec,), out_specs=spec,\n"
            ")(x)\n"
        )
        assert rule_ids(check_source(src, "src/repro/foo.py")) == ["RPR006"]

    def test_manual_tp_inside_body_clean(self):
        src = (
            "from repro import compat\n"
            "from repro.photonic.sharded import manual_tp\n\n"
            "def body(x):\n"
            '    with manual_tp("tp"):\n'
            "        return x\n\n"
            "def run(mesh, x, spec):\n"
            "    return compat.shard_map(\n"
            "        body, mesh=mesh, in_specs=(spec,), out_specs=spec\n"
            "    )(x)\n"
        )
        assert check_source(src, "src/repro/foo.py") == []

    def test_tensor_parallel_outside_body_clean(self):
        src = (
            "from repro.photonic.sharded import tensor_parallel\n\n"
            "def run(mesh, x):\n"
            '    with tensor_parallel(mesh, "tp"):\n'
            "        return go(x)\n"
        )
        assert check_source(src, "src/repro/foo.py") == []


# ---------------------------------------------------------------------------
# RPR007 — paged KV memory only through the kv_cache API
# ---------------------------------------------------------------------------
class TestRPR007:
    def test_pool_subscript_fires_in_models(self):
        src = "def read(kv_pool, blocks):\n    return kv_pool[blocks]\n"
        f = check_source(src, "src/repro/models/foo.py")
        assert rule_ids(f) == ["RPR007"]
        assert f[0].line == 2

    def test_block_table_indexing_fires_in_runtime(self):
        src = "def dest(block_table, p, bs):\n    return block_table[p // bs]\n"
        f = check_source(src, "src/repro/runtime/foo.py")
        assert rule_ids(f) == ["RPR007"]

    def test_at_update_fires(self):
        src = (
            "def write(kv_pool, b, o, rows):\n"
            "    return kv_pool.at[b, o].set(rows)\n"
        )
        assert rule_ids(check_source(src, "src/repro/models/foo.py")) == ["RPR007"]

    def test_api_calls_and_axis_insertion_clean(self):
        src = (
            "from repro.serving import kv_cache as kvc\n\n"
            "def read(kv_pool, block_table, n):\n"
            "    return kvc.gather_kv(kv_pool, block_table[None], n)\n"
        )
        assert check_source(src, "src/repro/models/foo.py") == []

    def test_kv_cache_and_serving_zone_exempt(self):
        src = "def read(kv_pool, blocks):\n    return kv_pool[blocks]\n"
        assert check_source(src, "src/repro/serving/kv_cache.py") == []
        assert check_source(src, "src/repro/serving/foo.py") == []


# ---------------------------------------------------------------------------
# RPR008 — engine GEMM outputs take no post-GEMM scale/bias shoulders
# ---------------------------------------------------------------------------
class TestRPR008:
    def test_scale_on_tracked_output_fires(self):
        src = (
            "def f(eng, x, pk, sx, ws):\n"
            "    y = eng.matmul(x, pk, site='attn.wq')\n"
            "    return y * sx * ws\n"
        )
        f = check_source(src, "src/repro/models/foo.py")
        assert rule_ids(f) == ["RPR008"]
        assert f[0].line == 3

    def test_bias_add_on_call_result_fires(self):
        src = (
            "def f(eng, x, w, b):\n"
            "    return eng.matmul_float(x, w, site='ffn.wi') + b\n"
        )
        f = check_source(src, "src/repro/models/foo.py")
        assert rule_ids(f) == ["RPR008"]
        assert f[0].line == 2

    def test_augassign_fires(self):
        src = (
            "def f(eng, x, pk, b):\n"
            "    y = eng.matmul(x, pk, site='s')\n"
            "    y += b\n"
            "    return y\n"
        )
        f = check_source(src, "src/repro/models/foo.py")
        assert rule_ids(f) == ["RPR008"]
        assert f[0].line == 3

    def test_epilogue_kwargs_clean(self):
        src = (
            "def f(eng, x, pk, b):\n"
            "    ep = Epilogue(EpilogueSpec(bias=True, activation='gelu'), b)\n"
            "    return eng.matmul(x, pk, site='s', epilogue=ep)\n"
        )
        assert check_source(src, "src/repro/models/foo.py") == []

    def test_dense_output_gating_clean(self):
        # SwiGLU gating and residual adds act on dense() results, which
        # are epilogue-complete already — the rule must not track them.
        src = (
            "def f(params, x, cfg):\n"
            "    u = dense(params['wi'], x, cfg, site='ffn.wi')\n"
            "    g = dense(params['wg'], x, cfg, site='ffn.wg')\n"
            "    return x + u * jax.nn.silu(g)\n"
        )
        assert check_source(src, "src/repro/models/foo.py") == []

    def test_reassignment_untracks(self):
        src = (
            "def f(eng, x, pk, b):\n"
            "    y = eng.matmul(x, pk, site='s')\n"
            "    y = jnp.reshape(y, (-1,))\n"
            "    return y + b\n"
        )
        assert check_source(src, "src/repro/models/foo.py") == []

    def test_digital_matmul_receivers_clean(self):
        src = (
            "def f(x, w, b):\n"
            "    y = jnp.matmul(x, w)\n"
            "    return y + b\n"
        )
        assert check_source(src, "src/repro/models/foo.py") == []

    def test_out_of_models_zone_clean(self):
        src = (
            "def f(eng, x, pk, sx):\n"
            "    return eng.matmul(x, pk, site='s') * sx\n"
        )
        assert check_source(src, "src/repro/photonic/foo.py") == []
        assert check_source(src, "benchmarks/foo.py") == []


# ---------------------------------------------------------------------------
# RPR009 — single-point platform resolution (mirror of RPR002)
# ---------------------------------------------------------------------------
class TestRPR009:
    def test_upper_on_platform_fires(self):
        src = "def f(platform):\n    return platform.strip().upper()\n"
        f = check_source(src, "src/repro/foo.py")
        assert rule_ids(f) == ["RPR009"]
        assert f[0].line == 2

    def test_lower_on_material_attr_fires(self):
        src = "def f(cfg):\n    return cfg.material.lower()\n"
        assert rule_ids(check_source(src, "src/repro/foo.py")) == ["RPR009"]

    def test_resolve_route_clean(self):
        # The clean twin of the violating fixture: same normalization
        # need, routed through THE resolution point.
        src = (
            "from repro import platforms\n\n"
            "def f(platform):\n"
            "    return platforms.resolve(platform).name\n"
        )
        assert check_source(src, "src/repro/foo.py") == []

    def test_non_platform_receiver_clean(self):
        src = "def f(s):\n    return s.upper()\n"
        assert check_source(src, "src/repro/foo.py") == []

    def test_platforms_module_exempt(self):
        src = "def f(platform):\n    return platform.strip().upper()\n"
        # _normalize_platform itself lives here — the one blessed site.
        assert check_source(src, "src/repro/platforms.py") == []


# ---------------------------------------------------------------------------
# RPR010 — timing/FPS aggregation routes through the mapper timeline
# ---------------------------------------------------------------------------
class TestRPR010:
    def test_sum_over_time_s_fires(self):
        src = (
            "def makespan(layers):\n"
            "    return sum(l.time_s for l in layers)\n"
        )
        f = check_source(src, "src/repro/foo.py")
        assert rule_ids(f) == ["RPR010"]
        assert f[0].line == 2

    def test_binop_on_makespan_fires(self):
        src = "def fps(t):\n    return 1.0 / t.makespan_s\n"
        assert rule_ids(check_source(src, "src/repro/foo.py")) == ["RPR010"]

    def test_augassign_energy_fires(self):
        src = (
            "def total(nodes):\n"
            "    e = 0.0\n"
            "    for n in nodes:\n"
            "        e += n.energy_j\n"
            "    return e\n"
        )
        assert rule_ids(check_source(src, "src/repro/foo.py")) == ["RPR010"]

    def test_benchmark_scope_fires(self):
        src = "def ms(t):\n    return t.makespan_s * 1e3\n"
        assert rule_ids(check_source(src, "benchmarks/foo.py")) == ["RPR010"]

    def test_timeline_metrics_clean(self):
        # The clean twin: same numbers, read from the blessed surface.
        src = (
            "def report(timeline):\n"
            "    d = timeline.to_dict()\n"
            "    return timeline.fps_per_w, d['makespan_s'] * 1e3\n"
        )
        assert check_source(src, "src/repro/foo.py") == []

    def test_plain_read_and_store_clean(self):
        src = (
            "def record(ns):\n"
            "    return {'time_s': ns.time_s, 'energy_j': ns.energy_j}\n"
        )
        assert check_source(src, "src/repro/foo.py") == []

    def test_mapper_and_simulator_exempt(self):
        src = "def makespan(ls):\n    return sum(l.time_s for l in ls)\n"
        assert check_source(src, "src/repro/mapper/timeline.py") == []
        assert check_source(src, "src/repro/core/simulator.py") == []

    def test_tests_out_of_scope(self):
        src = "def f(a, b):\n    return a.time_s - b.time_s\n"
        assert check_source(src, "tests/test_foo.py") == []


# ---------------------------------------------------------------------------
# The noqa escape hatch
# ---------------------------------------------------------------------------
class TestNoqa:
    BAD = (
        "def _quantize(x, amax):\n"
        "    return x / 127.0{comment}\n"
    )

    def test_matching_id_suppresses(self):
        src = self.BAD.format(comment="  # repro: noqa[RPR005]")
        assert check_source(src, "src/repro/foo.py") == []

    def test_bare_noqa_suppresses(self):
        src = self.BAD.format(comment="  # repro: noqa")
        assert check_source(src, "src/repro/foo.py") == []

    def test_other_id_does_not_suppress(self):
        src = self.BAD.format(comment="  # repro: noqa[RPR001]")
        assert rule_ids(check_source(src, "src/repro/foo.py")) == ["RPR005"]


# ---------------------------------------------------------------------------
# The acceptance bar: clean tree, zero suppressions in src/
# ---------------------------------------------------------------------------
class TestCleanTree:
    def test_run_all_default_paths_is_empty(self):
        assert run_all(root=ROOT) == []

    def test_src_has_zero_noqa_suppressions(self):
        # The hatch is for tests/fixtures; src/ must hold the bar with no
        # suppressions. repro/analysis itself documents the syntax in
        # docstrings, hence the carve-out.
        noqa = re.compile(r"#\s*repro:\s*noqa")
        hits = []
        for f in (ROOT / "src").rglob("*.py"):
            if "analysis" in f.parts:
                continue
            for i, line in enumerate(f.read_text().splitlines(), 1):
                if noqa.search(line):
                    hits.append(f"{f}:{i}")
        assert hits == []


# ---------------------------------------------------------------------------
# Level 2: traversal + contract passes
# ---------------------------------------------------------------------------
class TestJaxprTraversal:
    def test_recurses_custom_jvp_under_pjit(self):
        @jax.custom_jvp
        def rnd(x):
            return jnp.round(x)

        @rnd.defjvp
        def rnd_jvp(primals, tangents):
            (x,), (t,) = primals, tangents
            return rnd(x), t

        closed = jax.make_jaxpr(jax.jit(lambda x: rnd(x) * 2.0))(jnp.ones((8, 8)))
        # the round sits inside custom_jvp_call inside pjit — two levels of
        # closed sub-jaxpr the old engine walker missed on the 0.4.30 floor
        assert count_weight_round_ops(closed, 64) == 1
        assert count_weight_round_ops(closed.jaxpr, 64) == 1  # raw Jaxpr too

    def test_recurses_cond_branches(self):
        def fn(x):
            return jax.lax.cond(
                x.sum() > 0, lambda y: jnp.round(y), lambda y: y * 2.0, x
            )

        closed = jax.make_jaxpr(fn)(jnp.ones((8, 8)))
        assert count_weight_round_ops(closed, 64) == 1

    def test_min_size_filters_activation_rounds(self):
        closed = jax.make_jaxpr(lambda x: jnp.round(x))(jnp.ones((4,)))
        assert count_weight_round_ops(closed, 64) == 0
        assert count_weight_round_ops(closed, 1) == 1

    def test_count_primitives_and_iter_eqns(self):
        closed = jax.make_jaxpr(jax.jit(lambda x: jnp.sin(x) + jnp.sin(x * 2)))(
            jnp.ones((4,))
        )
        assert count_primitives(closed, "sin") == 2
        assert any(e.primitive.name == "sin" for e in iter_eqns(closed))

    def test_back_compat_reexport(self):
        from repro.photonic.engine import count_weight_round_ops as legacy

        assert legacy is count_weight_round_ops


class TestContractChecker:
    def _engine(self):
        return engine_for(
            DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0), "ref"
        )

    def test_decode_zero_quant_on_packed_path(self):
        from repro.photonic.packing import pack_dense

        eng = self._engine()
        w = jnp.asarray(RNG.normal(size=(64, 48)), jnp.float32)
        x = jnp.asarray(RNG.normal(size=(4, 64)), jnp.float32)
        packed = pack_dense({"w": w}, eng)["w"]

        checker = ContractChecker.trace(
            lambda a, p: eng.matmul(a, p, site="ffn.wi"), x, packed
        )
        assert checker.weight_round_ops(64 * 48) == 0
        checker.assert_zero_weight_rounds(64 * 48)  # must not raise

    def test_per_call_path_violates_and_raises(self):
        eng = self._engine()
        w = jnp.asarray(RNG.normal(size=(64, 48)), jnp.float32)
        x = jnp.asarray(RNG.normal(size=(4, 64)), jnp.float32)
        checker = ContractChecker.trace(
            lambda a, b: eng.matmul_float(a, b, site="ffn.wi"), x, w
        )
        assert checker.weight_round_ops(64 * 48) > 0
        with pytest.raises(ContractViolation, match="weight-stationary"):
            checker.assert_zero_weight_rounds(64 * 48)

    def _psum_body_checker(self, n_gemms):
        eng = self._engine()
        mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
        xq = jnp.asarray(RNG.integers(-7, 8, (4, 16)), jnp.int32)
        wq = jnp.asarray(RNG.integers(-7, 8, (16, 16)), jnp.int32)

        def body(a, b):
            out = tp_sharded.psum_int_gemm(eng, a, b, axis="tp", site="ffn.wi")
            for _ in range(n_gemms - 1):
                out = tp_sharded.psum_int_gemm(
                    eng, out, b, axis="tp", site="ffn.wo"
                )
            return out

        fn = compat.shard_map(
            body, mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False
        )
        return ContractChecker.trace(fn, xq, wq, label=f"{n_gemms}-gemm")

    def test_single_psum_per_routed_gemm(self):
        # psum sits inside the shard_map sub-jaxpr: the count proves both
        # the contract and the traversal into shard_map bodies.
        self._psum_body_checker(1).assert_psum_per_gemm(1)

    def test_psum_count_tracks_gemms_and_mismatch_raises(self):
        checker = self._psum_body_checker(2)
        checker.assert_psum_per_gemm(2)
        with pytest.raises(ContractViolation, match="psum"):
            checker.assert_psum_per_gemm(1)

    def test_noisy_channel_untraceable_without_source(self):
        ch = build_channel_model("SMWA", n=21, bits=4, datarate_gs=5.0)
        eng = engine_for(DPUConfig(dpe_size=21, channel=ch), "ref")  # no seed
        x = jnp.zeros((2, 21), jnp.float32)
        w = jnp.zeros((21, 8), jnp.float32)
        ContractChecker.assert_untraceable_without_source(
            lambda a, b: eng.matmul_float(a, b, site="ffn.wi"), x, w
        )

    def test_hlo_bridge_reuses_hlo_analysis_on_the_same_call(self):
        from repro.launch import hlo_analysis

        eng = self._engine()
        w = jnp.asarray(RNG.normal(size=(64, 48)), jnp.float32)
        x = jnp.asarray(RNG.normal(size=(4, 64)), jnp.float32)
        checker = ContractChecker.trace(
            lambda a, b: eng.matmul_float(a, b, site="ffn.wi"), x, w
        )
        hlo = checker.hlo_text()
        assert "HloModule" in hlo
        summary = checker.collective_summary()
        assert summary == hlo_analysis.collective_summary(hlo)
        assert "total_wire_bytes" in summary

    def test_hlo_bridge_requires_trace_built_checker(self):
        closed = jax.make_jaxpr(lambda a: a + 1)(jnp.zeros((2,)))
        with pytest.raises(ValueError, match="ContractChecker.trace"):
            ContractChecker(closed).hlo_text()

    def test_seeded_noisy_channel_traces_and_hatch_detects_it(self):
        ch = build_channel_model("SMWA", n=21, bits=4, datarate_gs=5.0)
        eng = engine_for(DPUConfig(dpe_size=21, channel=ch, noise_seed=7), "ref")
        x = jnp.zeros((2, 21), jnp.float32)
        w = jnp.zeros((21, 8), jnp.float32)
        with pytest.raises(ContractViolation, match="traced without"):
            ContractChecker.assert_untraceable_without_source(
                lambda a, b: eng.matmul_float(a, b, site="ffn.wi"), x, w
            )


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
class TestCLI:
    def _run(self, *args, cwd=ROOT):
        env = dict(os.environ)
        env["PYTHONPATH"] = str(ROOT / "src")
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis", *args],
            cwd=cwd, env=env, capture_output=True, text=True,
        )

    def test_clean_tree_exits_zero(self):
        r = self._run()
        assert r.returncode == 0, r.stdout + r.stderr
        assert r.stdout == ""

    def test_list_rules(self):
        r = self._run("--list-rules")
        assert r.returncode == 0
        for rid in ("RPR001", "RPR006"):
            assert rid in r.stdout

    def test_violation_github_format_and_report(self, tmp_path):
        proj = tmp_path / "proj"
        (proj / "src" / "repro").mkdir(parents=True)
        (proj / "pyproject.toml").write_text("[project]\nname='x'\n")
        bad = proj / "src" / "repro" / "bad.py"
        bad.write_text("def f(org):\n    return org.upper()\n")
        report = tmp_path / "report.json"
        r = self._run(
            "--root", str(proj), "--format", "github",
            "--report", str(report), str(proj / "src"),
        )
        assert r.returncode == 1
        assert "::error file=src/repro/bad.py,line=2" in r.stdout
        assert "RPR002" in r.stdout
        data = json.loads(report.read_text())
        assert data["count"] == 1 and not data["ok"]
        assert data["findings"][0]["rule"] == "RPR002"

    def test_select_filters_rules(self, tmp_path):
        proj = tmp_path / "proj"
        (proj / "src").mkdir(parents=True)
        (proj / "pyproject.toml").write_text("[project]\nname='x'\n")
        (proj / "src" / "bad.py").write_text(
            "def f(org):\n    return org.upper()\n"
        )
        r = self._run("--root", str(proj), "--select", "RPR001", str(proj / "src"))
        assert r.returncode == 0
