"""Operation and byte counts against a hand count for qwen2-0.5b."""

import json
from pathlib import Path

import pytest

from perfbench import counts, spec

ROOT = Path(__file__).resolve().parents[2]
SIZES = json.loads((ROOT / "perfbench/configs/qwen2_0_5b-ideal.json").read_text())["sizes"]
PEAKS = spec.peaks_for("TPU v5 lite")

# Per layer: Q 896x896, K 896x128, V 896x128, O 896x896, gate+up 896x9728,
# down 4864x896.
PER_LAYER = 802_816 + 114_688 + 114_688 + 802_816 + 8_716_288 + 4_358_144


def test_routed_params_by_hand():
    assert PER_LAYER == 14_909_440
    assert counts.routed_params(SIZES) == 24 * PER_LAYER == 357_826_560


def test_routed_params_plus_head_is_the_published_size():
    # Qwen2-0.5B has 494M parameters: routed GEMMs, the tied 151936 x 896
    # embedding, norms and biases.
    total = counts.routed_params(SIZES) + 151_936 * 896
    assert 493e6 < total < 495e6


def test_prefill_ops_by_hand():
    attn = 24 * 4 * 14 * 64 * (512 * 513 // 2)
    want = 2 * 357_826_560 * 512 + attn + 2 * 151_936 * 896
    assert counts.prefill_ops(SIZES, 0, 512, True) == pytest.approx(want, rel=1e-12)
    # A later chunk attends to the earlier ones, and only a final one pays
    # for the head.
    attn2 = 24 * 4 * 14 * 64 * sum(range(513, 1025))
    assert counts.prefill_ops(SIZES, 512, 512, False) == pytest.approx(
        2 * 357_826_560 * 512 + attn2, rel=1e-12
    )


def test_decode_ops_by_hand():
    ctx = [100, 1500]
    want = 2 * 2 * (357_826_560 + 151_936 * 896) + 24 * 4 * 14 * 64 * 1600
    assert counts.decode_ops(SIZES, ctx) == pytest.approx(want, rel=1e-12)


@pytest.mark.parametrize(
    "rows,k,c,bound",
    [
        # 8 rows through the gate/up bank: the int8 weights' read bounds it.
        (8, 896, 9728, (896 * 9728 + 2 * 8 * 896 + 2 * 8 * 9728) / 819e9),
        # 4096 rows: the int8 operations bound it.
        (4096, 896, 9728, 2 * 4096 * 896 * 9728 / 393e12),
    ],
)
def test_gemm_call_bound_by_hand(rows, k, c, bound):
    assert counts.gemm_call_bound_s(rows, k, c, PEAKS) == pytest.approx(bound, rel=1e-12)


def test_gemm_bound_sums_every_site_of_every_layer():
    one = sum(counts.gemm_call_bound_s(64, k, c, PEAKS) for _, k, c in counts.gemm_sites(SIZES))
    assert counts.gemm_bound_s(SIZES, 64, PEAKS) == pytest.approx(24 * one, rel=1e-12)
    assert counts.gemm_bound_s(SIZES, 0, PEAKS) == 0.0
