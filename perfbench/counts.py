"""Operations and bytes a step needs, from shapes alone.

These are the yardstick's own counts: what the algorithm needs, whatever
implements it.  No slice pass, DPE chunk slot, tile padding or idle decode
row is counted, so a later implementation that does the same work in
fewer passes reads as faster and not as less work.

The shapes come from the configuration's architecture module (``arch``):

* ``gemm_sites(sizes)``: (site, K, C, passes, banks, layers) of every GEMM
  through the photonic kernel.  A token row passes ``passes`` times through
  a K x C weight, on average (a share of held experts), and a call reads
  ``banks`` such weights; the site runs in ``layers`` layers;
* ``attention_widths(sizes)``: (QK width, PV width, layers), the widths one
  query token contracts against each position it attends;
* ``unembed_shape(sizes)``: (K, C) of the unembedding.

``sizes`` is a configuration file's ``sizes`` group (published key names).
"""

from __future__ import annotations

from typing import Dict, Iterable, List


def routed_params(arch, sizes: dict):
    """Weights a token row passes through in the photonic kernel, all
    layers: each site's K x C times its passes and its layers."""
    sites = arch.gemm_sites(sizes)
    return sum(layers * passes * k * c for _, k, c, passes, _, layers in sites)


def gemm_call_bound_s(
    rows: int, k: int, c: int, peaks: Dict[str, float], passes=1, banks=1
) -> float:
    """Least time one GEMM call over ``rows`` token rows can take on the
    chip: its int8 operations (``passes`` per row through a ``k`` x ``c``
    weight) at the int8 peak, or its ``banks`` int8 weights plus bf16
    activations in and out at the HBM bandwidth, whichever is longer."""
    ops = 2.0 * rows * passes * k * c
    nbytes = banks * k * c + 2.0 * rows * passes * k + 2.0 * rows * passes * c
    return max(ops / peaks["int8_ops"], nbytes / peaks["hbm_bytes_per_s"])


def gemm_bound_s(arch, sizes: dict, rows: int, peaks: Dict[str, float]) -> float:
    """Least time of every routed GEMM of one model call over ``rows``:
    the sites that share a layer count are summed, then multiplied by it."""
    if rows <= 0:
        return 0.0
    by_layers: Dict[int, List[float]] = {}
    for _, k, c, passes, banks, layers in arch.gemm_sites(sizes):
        by_layers.setdefault(layers, []).append(
            gemm_call_bound_s(rows, k, c, peaks, passes, banks)
        )
    return sum(layers * sum(bounds) for layers, bounds in by_layers.items())


def attention_ops(arch, sizes: dict, contexts: Iterable[int]) -> float:
    """Q.K and P.V operations of one query token per entry of ``contexts``
    (the number of positions it attends to), over all layers."""
    widths = arch.attention_widths(sizes)
    per_pos = sum(layers * (2.0 * qk + 2.0 * pv) for qk, pv, layers in widths)
    return per_pos * float(sum(contexts))


def unembed_ops(arch, sizes: dict, rows: int) -> float:
    k, c = arch.unembed_shape(sizes)
    return 2.0 * rows * c * k


def prefill_ops(arch, sizes: dict, t0: int, tc: int, final: bool) -> float:
    """A chunk of ``tc`` prompt tokens from position ``t0``: routed GEMMs,
    causal attention at the real context, and the head for the last
    token of a final chunk."""
    attn = attention_ops(arch, sizes, range(t0 + 1, t0 + tc + 1))
    head = unembed_ops(arch, sizes, 1) if final else 0.0
    return 2.0 * routed_params(arch, sizes) * tc + attn + head


def decode_ops(arch, sizes: dict, contexts: List[int]) -> float:
    """One decode step of ``len(contexts)`` live rows; ``contexts`` holds
    each row's positions attended (its cache length plus the new token)."""
    rows = len(contexts)
    return (
        2.0 * routed_params(arch, sizes) * rows
        + unembed_ops(arch, sizes, rows)
        + attention_ops(arch, sizes, contexts)
    )
