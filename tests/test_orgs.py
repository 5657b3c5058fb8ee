"""Tests for `repro.orgs` — first-class organization specs.

The tentpole contract: the Table II/III/IV profiles are *derived* from the
block order, and for the three paper-studied orders the derivation equals
the legacy hand-copied tables exactly.  The legacy values are spelled out
here as literals (they no longer exist as hardcoded tables in the source)
so the assertion stays a real paper-anchored check.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro import orgs
from repro.core import organizations as org_tables
from repro.core import scalability as sc
from repro.core.dpu import DPUConfig
from repro.core.params import PhotonicParams
from repro.core.perfmodel import AcceleratorConfig
from repro.kernels.photonic_gemm.ref import exact_int_gemm
from repro.noise import build_channel_model, shard_local_channel
from repro.orgs import OrgSpec, resolve, valid_orderings
from repro.photonic import engine_for

# ---------------------------------------------------------------------------
# The paper's hand-tabulated values (Tables II, III, IV and §IV-B1), kept
# as literals: the derivation must reproduce them, not the other way round.
# ---------------------------------------------------------------------------
TABLE_II = {  # (inter_modulation, cross_weight, filter_truncation)
    "ASMW": (True, True, False),
    "MASW": (False, True, True),
    "SMWA": (False, False, True),
}
TABLE_III = {  # (through level, propagation level, through formula, wg factor)
    "ASMW": ("high", "moderate", "2(N-1)", 1.0),
    "MASW": ("moderate", "low", "N", 0.75),
    "SMWA": ("high", "high", "2", 1.5),
}
TABLE_IV_PENALTY = {"ASMW": 5.8, "MASW": 4.8, "SMWA": 1.8}
THROUGH_COUNT = {  # §IV-B1 at N
    "ASMW": lambda n: 2 * (n - 1),
    "MASW": lambda n: n,
    "SMWA": lambda n: 2,
}
RINGS_PER_DPU = {  # Fig. 2 at (N, M)
    "ASMW": lambda n, m: 2 * n * m,
    "MASW": lambda n, m: n + n * m,
    "SMWA": lambda n, m: 3 * n * m,
}
BLOCK_ORDERS = {
    "ASMW": ("A", "S", "M", "W", "Sigma"),
    "MASW": ("M", "A", "S", "W", "Sigma"),
    "SMWA": ("S", "M", "W", "A", "Sigma"),
}


class TestDerivedEqualsPaperTables:
    @pytest.mark.parametrize("org", orgs.ORGANIZATIONS)
    def test_block_orders(self, org):
        assert resolve(org).blocks == BLOCK_ORDERS[org]

    @pytest.mark.parametrize("org", orgs.ORGANIZATIONS)
    def test_table_ii_crosstalk_derived(self, org):
        s = resolve(org)
        assert (s.inter_modulation, s.cross_weight, s.filter_truncation) == (
            TABLE_II[org]
        )
        # ... and the legacy dict view agrees field-for-field.
        xt = org_tables.CROSSTALK[org]
        assert (xt.inter_modulation, xt.cross_weight, xt.filter_truncation) == (
            TABLE_II[org]
        )

    @pytest.mark.parametrize("org", orgs.ORGANIZATIONS)
    def test_table_iii_losses_derived(self, org):
        s = resolve(org)
        derived = (
            s.through_loss_level,
            s.propagation_loss_level,
            s.through_devices,
            s.waveguide_length_factor,
        )
        assert derived == TABLE_III[org]
        lp = org_tables.LOSSES[org]
        assert (
            lp.through_loss_level,
            lp.propagation_loss_level,
            lp.through_devices,
            lp.waveguide_length_factor,
        ) == TABLE_III[org]

    @pytest.mark.parametrize("org", orgs.ORGANIZATIONS)
    def test_table_iv_penalty_derived(self, org):
        s = resolve(org)
        assert s.derived_penalty_db == pytest.approx(TABLE_IV_PENALTY[org])
        # The PhotonicParams fields remain the calibrated anchors and win
        # for the paper orgs...
        assert PhotonicParams().penalty_db(org) == TABLE_IV_PENALTY[org]
        # ...including under ablation replaces.
        p = dataclasses.replace(PhotonicParams(), penalty_smwa_db=9.9)
        assert p.penalty_db("smwa") == 9.9

    @pytest.mark.parametrize("org", orgs.ORGANIZATIONS)
    @pytest.mark.parametrize("n", [2, 10, 17, 83])
    def test_through_device_count(self, org, n):
        assert resolve(org).through_device_count(n) == THROUGH_COUNT[org](n)
        assert org_tables.through_device_count(org, n) == THROUGH_COUNT[org](n)

    @pytest.mark.parametrize("org", orgs.ORGANIZATIONS)
    def test_rings_per_dpu_derived(self, org):
        s = resolve(org)
        for n, m in ((8, 8), (17, 17), (40, 24)):
            assert s.rings_per_dpu(n, m) == RINGS_PER_DPU[org](n, m)
        cfg = AcceleratorConfig(organization=org, n=40, m=40)
        assert cfg.rings_per_dpu == RINGS_PER_DPU[org](40, 40)


class TestChannelModelEquivalence:
    @pytest.mark.parametrize("org", orgs.ORGANIZATIONS)
    def test_spec_and_name_build_identical_models(self, org):
        by_name = build_channel_model(org, n=21, bits=4, datarate_gs=5.0)
        by_spec = build_channel_model(resolve(org), n=21, bits=4, datarate_gs=5.0)
        # Deliberately un-normalized input: the point is that resolve()
        # normalizes it.
        by_case = build_channel_model(
            org.lower(), n=21, bits=4, datarate_gs=5.0  # repro: noqa[RPR002]
        )
        # Frozen-dataclass equality covers every field INCLUDING the
        # builder provenance tuple.
        assert by_name == by_spec == by_case
        assert by_name.builder == by_spec.builder
        for f in dataclasses.fields(by_name):
            assert getattr(by_name, f.name) == getattr(by_spec, f.name), f.name

    @pytest.mark.parametrize("org", orgs.ORGANIZATIONS)
    def test_shard_local_round_trip(self, org):
        """Builder provenance survives spec-built models: the shard-local
        rebuild of a spec-built channel equals the name-built one."""
        by_name = build_channel_model(org, n=32, bits=4, datarate_gs=5.0)
        by_spec = build_channel_model(resolve(org), n=32, bits=4, datarate_gs=5.0)
        for n_local in (16, 8, 3):
            a = shard_local_channel(by_name, n_local)
            b = shard_local_channel(by_spec, n_local)
            assert a == b
            assert a == build_channel_model(org, n=n_local, bits=4, datarate_gs=5.0)

    def test_dpu_config_shard_local_accepts_spec(self):
        ch = build_channel_model(resolve("MASW"), n=32)
        cfg = DPUConfig(organization=resolve("MASW"), dpe_size=32, channel=ch)
        local = cfg.shard_local(8)
        assert local.organization == "MASW"
        assert local.channel == build_channel_model("MASW", n=8)

    def test_novel_ordering_channel_profile(self):
        """An ordering the paper never studied gets a structurally derived
        channel: MWAS is filter-only with ONE through device."""
        ch = build_channel_model("MWAS", n=16)
        assert ch.intermod_eps == 0.0
        assert ch.crossweight_eps == 0.0
        assert ch.filter_alpha > 0.0
        assert ch.through_loss_db == pytest.approx(1 * sc.CALIBRATED.p_mrm_obl_db)
        assert ch.penalty_db == pytest.approx(resolve("MWAS").derived_penalty_db)


class TestEagerValidation:
    @pytest.mark.parametrize(
        "ctor",
        [
            lambda org: DPUConfig(organization=org),
            lambda org: AcceleratorConfig(organization=org),
            lambda org: build_channel_model(org, n=8),
        ],
        ids=["DPUConfig", "AcceleratorConfig", "build_channel_model"],
    )
    def test_unknown_org_raises_valueerror_naming_choices(self, ctor):
        with pytest.raises(ValueError, match="ASMW"):
            ctor("not-an-org")
        with pytest.raises(ValueError, match="MASW"):
            ctor("WSMA")  # W before M: physically invalid order

    def test_case_normalization_unified(self):
        assert DPUConfig(organization="smwa") == DPUConfig(organization="SMWA")
        assert hash(DPUConfig(organization="smwa")) == hash(
            DPUConfig(organization="SMWA")
        )
        assert AcceleratorConfig(organization="masw").organization == "MASW"
        assert build_channel_model("aSmW", n=8).organization == "ASMW"

    def test_spec_input_normalizes_to_canonical_name(self):
        cfg = DPUConfig(organization=resolve("ASMW"))
        assert cfg.organization == "ASMW"
        assert cfg.org_spec is resolve("ASMW")

    def test_resolve_rejects_non_string(self):
        with pytest.raises(ValueError, match="str or OrgSpec"):
            resolve(3)


class TestDesignSpace:
    def test_twelve_valid_orderings(self):
        space = valid_orderings()
        names = [s.name for s in space]
        assert len(space) == 12
        assert len(set(names)) == 12
        assert names[:3] == list(orgs.ORGANIZATIONS)
        for s in space:
            assert s.blocks[-1] == "Sigma"
            assert s.blocks.index("M") < s.blocks.index("W")
            assert sorted(s.blocks[:-1]) == ["A", "M", "S", "W"]

    def test_specs_hashable_and_order_is_identity(self):
        assert len({s for s in valid_orderings()}) == 12
        assert OrgSpec.from_order("smwa") is resolve("SMWA")
        assert resolve(resolve("MASW")) is resolve("MASW")

    def test_invalid_orders_rejected(self):
        for bad in ("SSMW", "SAMWX", "SAM", "ABCD"):
            with pytest.raises(ValueError):
                OrgSpec.from_order(bad)
        with pytest.raises(ValueError, match="Modulation"):
            OrgSpec(blocks=("W", "M", "S", "A", "Sigma"))
        with pytest.raises(ValueError, match="terminal"):
            OrgSpec(blocks=("Sigma", "S", "M", "W", "A"))

    @given(idx=st.integers(min_value=0, max_value=11), n=st.integers(2, 200))
    @settings(max_examples=60, deadline=None)
    def test_through_count_matches_formula_property(self, idx, n):
        """Property: through_device_count agrees with the canonical formula
        string for every ordering in the space."""
        s = valid_orderings()[idx]
        expected = {
            "2(N-1)": 2 * (n - 1),
            "N-1": n - 1,
            "N": n,
            "N+1": n + 1,
            "2N": 2 * n,
            "2N-1": 2 * n - 1,
            "2N-2": 2 * n - 2,
            "0": 0,
            "1": 1,
            "2": 2,
        }[s.through_devices]
        assert s.through_device_count(n) == expected
        assert s.through_device_count(n) >= 0

    @given(idx=st.integers(min_value=0, max_value=11))
    @settings(max_examples=24, deadline=None)
    def test_crosstalk_rules_property(self, idx):
        """Property: the Table II mechanisms follow the structural rules
        for every ordering (not just the paper's three)."""
        s = valid_orderings()[idx]
        assert s.inter_modulation == s.before("A", "M")
        assert s.cross_weight == s.before("A", "W")
        assert s.filter_truncation == s.before("M", "A")
        # filter truncation and inter-modulation are mutually exclusive
        # (M<A vs A<M), a structural theorem of the rule set.
        assert not (s.inter_modulation and s.filter_truncation)

    def test_scalability_solver_covers_novel_orderings(self):
        """The Eq. 1-3 solver works on the whole space; filter-only
        orderings achieve the largest N (Fig. 5 logic, generalized)."""
        ns = {s.name: sc.calibrated_max_n(s, 4, 5) for s in valid_orderings()}
        best = max(ns.values())
        assert ns["SMWA"] == best
        assert ns["MWAS"] == best  # the unstudied challenger ties SMWA
        for s in valid_orderings():
            if s.cross_weight or s.inter_modulation:
                assert ns[s.name] < best, ns

    def test_novel_ordering_ideal_gemm_bitwise_exact(self):
        """A novel ordering runs the full DPU datapath; ideal channel is
        bit-identical to the exact integer GEMM (DESIGN.md §8 contract 1,
        extended to the whole design space)."""
        rng = np.random.default_rng(0)
        xq = jnp.asarray(rng.integers(-127, 128, (5, 40), dtype=np.int8))
        wq = jnp.asarray(rng.integers(-127, 128, (40, 7), dtype=np.int8))
        gold = exact_int_gemm(xq, wq)
        for order in ("MWAS", "SAMW", "MSAW"):
            cfg = DPUConfig(organization=order, bits=4, dpe_size=16)
            out = engine_for(cfg, "ref").int_gemm(xq, wq)
            assert jnp.array_equal(out, gold), order
