"""Operations and bytes a step needs, from shapes alone.

These are the yardstick's own counts: what the algorithm needs, whatever
implements it.  No slice pass, DPE chunk slot, tile padding or idle decode
row is counted, so a later implementation that does the same work in
fewer passes reads as faster and not as less work.

``sizes`` is a configuration file's ``sizes`` group (published key names).
"""

from __future__ import annotations

from typing import Dict, Iterable, List, Tuple


def head_dim(sizes: dict) -> int:
    return sizes.get("head_dim") or sizes["hidden_size"] // sizes["num_attention_heads"]


def gemm_sites(sizes: dict) -> List[Tuple[str, int, int]]:
    """(site, K, C) of every GEMM of one layer that runs through the
    photonic kernel: Q, K, V, O, the fused gate/up and down projections."""
    d, f = sizes["hidden_size"], sizes["intermediate_size"]
    hq = sizes["num_attention_heads"] * head_dim(sizes)
    hkv = sizes["num_key_value_heads"] * head_dim(sizes)
    return [
        ("attn.wq", d, hq),
        ("attn.wk", d, hkv),
        ("attn.wv", d, hkv),
        ("attn.wo", hq, d),
        ("ffn.wi", d, 2 * f),
        ("ffn.wo", f, d),
    ]


def routed_params(sizes: dict) -> int:
    """Weights that pass through the photonic kernel, all layers."""
    per_layer = sum(k * c for _, k, c in gemm_sites(sizes))
    return sizes["num_hidden_layers"] * per_layer


def gemm_call_bound_s(rows: int, k: int, c: int, peaks: Dict[str, float]) -> float:
    """Least time one GEMM of ``rows`` x ``k`` x ``c`` can take on the chip:
    int8 operations at the int8 peak, or int8 weights plus bf16
    activations in and out at the HBM bandwidth, whichever is longer."""
    ops = 2.0 * rows * k * c
    nbytes = k * c + 2.0 * rows * k + 2.0 * rows * c
    return max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"])


def gemm_bound_s(sizes: dict, rows: int, peaks: Dict[str, float]) -> float:
    """Least time of every routed GEMM of one model call over ``rows``."""
    if rows <= 0:
        return 0.0
    per_layer = sum(gemm_call_bound_s(rows, k, c, peaks) for _, k, c in gemm_sites(sizes))
    return sizes["num_hidden_layers"] * per_layer


def attention_ops(sizes: dict, contexts: Iterable[int]) -> float:
    """Q.K and P.V operations of one query token per entry of ``contexts``
    (the number of positions it attends to), over all layers."""
    per_pos = 4.0 * sizes["num_attention_heads"] * head_dim(sizes)
    return sizes["num_hidden_layers"] * per_pos * float(sum(contexts))


def unembed_ops(sizes: dict, rows: int) -> float:
    return 2.0 * rows * sizes["vocab_size"] * sizes["hidden_size"]


def prefill_ops(sizes: dict, t0: int, tc: int, final: bool) -> float:
    """A chunk of ``tc`` prompt tokens from position ``t0``: routed GEMMs,
    causal attention at the real context, and the head for the last
    token of a final chunk."""
    attn = attention_ops(sizes, range(t0 + 1, t0 + tc + 1))
    return 2.0 * routed_params(sizes) * tc + attn + (unembed_ops(sizes, 1) if final else 0.0)


def decode_ops(sizes: dict, contexts: List[int]) -> float:
    """One decode step of ``len(contexts)`` live rows; ``contexts`` holds
    each row's positions attended (its cache length plus the new token)."""
    rows = len(contexts)
    return (
        2.0 * routed_params(sizes) * rows
        + unembed_ops(sizes, rows)
        + attention_ops(sizes, contexts)
    )
