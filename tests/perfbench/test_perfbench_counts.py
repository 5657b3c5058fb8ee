"""Operation and byte counts against a hand count for qwen2-0.5b, whose
sites come from its architecture module, and the generic formulas at one
pass and one bank against the dense-only ones they replaced."""

import json
from pathlib import Path

import pytest

from perfbench import counts, spec

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "perfbench/configs/qwen2_0_5b-ideal.json").read_text())
SIZES = CONFIG["sizes"]
ARCH = spec.architecture(CONFIG)
PEAKS = spec.peaks_for("TPU v5 lite")

# Per layer: Q 896x896, K 896x128, V 896x128, O 896x896, gate+up 896x9728,
# down 4864x896.
PER_LAYER = 802_816 + 114_688 + 114_688 + 802_816 + 8_716_288 + 4_358_144


def test_routed_params_by_hand():
    assert PER_LAYER == 14_909_440
    assert counts.routed_params(ARCH, SIZES) == 24 * PER_LAYER == 357_826_560


def test_routed_params_plus_head_is_the_published_size():
    # Qwen2-0.5B has 494M parameters: routed GEMMs, the tied 151936 x 896
    # embedding, norms and biases.
    total = counts.routed_params(ARCH, SIZES) + 151_936 * 896
    assert 493e6 < total < 495e6


def test_prefill_ops_by_hand():
    attn = 24 * 4 * 14 * 64 * (512 * 513 // 2)
    want = 2 * 357_826_560 * 512 + attn + 2 * 151_936 * 896
    assert counts.prefill_ops(ARCH, SIZES, 0, 512, True) == pytest.approx(want, rel=1e-12)
    # A later chunk attends to the earlier ones, and only a final one pays
    # for the head.
    attn2 = 24 * 4 * 14 * 64 * sum(range(513, 1025))
    assert counts.prefill_ops(ARCH, SIZES, 512, 512, False) == pytest.approx(
        2 * 357_826_560 * 512 + attn2, rel=1e-12
    )


def test_decode_ops_by_hand():
    ctx = [100, 1500]
    want = 2 * 2 * (357_826_560 + 151_936 * 896) + 24 * 4 * 14 * 64 * 1600
    assert counts.decode_ops(ARCH, SIZES, ctx) == pytest.approx(want, rel=1e-12)


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
    sites = ARCH.gemm_sites(SIZES)
    assert [(p, b, n) for _, _, _, p, b, n in sites] == [(1, 1, 24)] * 6
    one = sum(counts.gemm_call_bound_s(64, k, c, PEAKS) for _, k, c, _, _, _ in sites)
    assert counts.gemm_bound_s(ARCH, SIZES, 64, PEAKS) == pytest.approx(24 * one, rel=1e-12)
    assert counts.gemm_bound_s(ARCH, SIZES, 0, PEAKS) == 0.0


# The dense-only formulas as they stood before the counts took passes,
# banks and per-site layer counts from an architecture module.
def _dense_call_bound_s(rows, k, c, peaks):
    ops = 2.0 * rows * k * c
    nbytes = k * c + 2.0 * rows * k + 2.0 * rows * c
    return max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"])


def _dense_sites(sizes):
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    hd = sizes["hidden_size"] // sizes["num_attention_heads"]
    hq, hkv = sizes["num_attention_heads"] * hd, sizes["num_key_value_heads"] * hd
    return [(d, hq), (d, hkv), (d, hkv), (hq, d), (d, 2 * f), (f, d)]


def _dense_routed(sizes):
    return sizes["num_hidden_layers"] * sum(k * c for k, c in _dense_sites(sizes))


def _dense_attention(sizes, contexts):
    h = sizes["num_attention_heads"]
    per_pos = 4.0 * h * (sizes["hidden_size"] // h)
    return sizes["num_hidden_layers"] * per_pos * float(sum(contexts))


def _dense_unembed(sizes, rows):
    return 2.0 * rows * sizes["vocab_size"] * sizes["hidden_size"]


@pytest.mark.parametrize("rows", [1, 5, 64, 512, 4096])
def test_one_pass_one_bank_equals_the_dense_formulas_exactly(rows):
    for k, c in _dense_sites(SIZES) + [(7, 3), (151_936, 896)]:
        want = _dense_call_bound_s(rows, k, c, PEAKS)
        assert counts.gemm_call_bound_s(rows, k, c, PEAKS) == want
        assert counts.gemm_call_bound_s(rows, k, c, PEAKS, 1, 1) == want
    per_layer = sum(_dense_call_bound_s(rows, k, c, PEAKS) for k, c in _dense_sites(SIZES))
    assert counts.gemm_bound_s(ARCH, SIZES, rows, PEAKS) == 24 * per_layer
    assert counts.routed_params(ARCH, SIZES) == _dense_routed(SIZES)
    contexts = list(range(1000, 1000 + rows))
    assert counts.attention_ops(ARCH, SIZES, contexts) == _dense_attention(SIZES, contexts)
    assert counts.unembed_ops(ARCH, SIZES, rows) == _dense_unembed(SIZES, rows)
    for t0, final in ((0, True), (512, False), (1536, True)):
        want = (
            2.0 * _dense_routed(SIZES) * rows
            + _dense_attention(SIZES, range(t0 + 1, t0 + rows + 1))
            + (_dense_unembed(SIZES, 1) if final else 0.0)
        )
        assert counts.prefill_ops(ARCH, SIZES, t0, rows, final) == want
    want = (
        2.0 * _dense_routed(SIZES) * rows
        + _dense_unembed(SIZES, rows)
        + _dense_attention(SIZES, contexts)
    )
    assert counts.decode_ops(ARCH, SIZES, contexts) == want
