"""Gradient compression for cross-pod data parallelism.

``compressed_psum(tree, axis_name)`` — int8-quantized all-reduce for use
inside ``shard_map``: each leaf is symmetric-quantized to int8 with an f32
per-leaf scale, summed in int32 across the axis (exact given int8 inputs),
and dequantized with the psum of scales' max.  Halves (vs bf16) / quarters
(vs f32) the wire bytes of the slow inter-pod gradient reduction at a
bounded quantization error (<= 1/254 of each leaf's max-abs per shard).

``with_error_feedback`` keeps the per-step quantization residual and adds it
to the next step's gradients (1-bit-Adam style error feedback), making the
compression unbiased over time.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

from repro import compat


def _quantize(g: jax.Array) -> Tuple[jax.Array, jax.Array]:
    if g.size == 0:  # zero-layer ladder variants produce (0, ...) leaves
        return g.astype(jnp.int8), jnp.ones((), jnp.float32)
    amax = jnp.max(jnp.abs(g)).astype(jnp.float32)
    scale = jnp.maximum(amax, 1e-12) * (1.0 / 127.0)
    q = jnp.clip(jnp.round(g.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def compressed_psum(tree: Any, axis_name: str) -> Any:
    """int8 all-reduce of a pytree over a shard_map axis."""

    def leaf(g):
        q, scale = _quantize(g)
        # max-scale across the axis so all shards dequantize consistently;
        # requantize local values at the shared scale, then int32-sum.
        scale_max = jax.lax.pmax(scale, axis_name)
        q2 = jnp.clip(
            jnp.round(g.astype(jnp.float32) / scale_max), -127, 127
        ).astype(jnp.int8)
        total = jax.lax.psum(q2.astype(jnp.int32), axis_name)
        return (total.astype(jnp.float32) * scale_max).astype(g.dtype)

    return jax.tree.map(leaf, tree)


def ring_int8_allreduce(tree: Any, axis_name) -> Any:
    """All-reduce with int8 WIRE bytes: a reduce-scatter ring of quantized
    chunks (ppermute int8 payloads, f32 local accumulation) followed by an
    int8 all-gather ring.  2(n-1) steps; wire = 2x int8 vs 2x bf16/f32 for a
    plain psum — the half-traffic variant XLA cannot express with psum
    (int8 summands overflow; accumulation must stay local).

    Requantization error per hop is bounded by the per-chunk scale; for
    gradient averaging this is the standard int8-ring trade (error feedback
    available via with_error_feedback)."""
    n = compat.axis_size(axis_name)
    if n == 1:
        return tree
    idx = jax.lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    def leaf(g):
        if g.size == 0:
            return g
        shape = g.shape
        flat = g.astype(jnp.float32).reshape(-1)
        pad = (-flat.size) % n
        flat = jnp.pad(flat, (0, pad))
        chunks = flat.reshape(n, -1)  # chunk c owned by device c

        # reduce-scatter ring: at step s, device d sends chunk (d - s) and
        # accumulates into chunk (d - s - 1).
        def rs_step(s, carry):
            acc = carry  # (n, chunk) f32 local view
            send_idx = (idx - s) % n
            q, scale = _quantize(acc[send_idx])
            q_recv = jax.lax.ppermute(q, axis_name, fwd)
            s_recv = jax.lax.ppermute(scale, axis_name, fwd)
            recv_idx = (idx - s - 1) % n
            acc = acc.at[recv_idx].add(q_recv.astype(jnp.float32) * s_recv)
            return acc

        acc = jax.lax.fori_loop(0, n - 1, rs_step, chunks)
        # device d now owns the fully reduced chunk (d + 1) % n

        # all-gather ring: at step t, device d sends chunk (d+1-t) (complete
        # by induction) and overwrites chunk (d-t) with its neighbour's.
        def ag_step(t, carry):
            acc = carry
            send_idx = (idx + 1 - t) % n
            q, scale = _quantize(acc[send_idx])
            q_recv = jax.lax.ppermute(q, axis_name, fwd)
            s_recv = jax.lax.ppermute(scale, axis_name, fwd)
            recv_idx = (idx - t) % n
            acc = acc.at[recv_idx].set(q_recv.astype(jnp.float32) * s_recv)
            return acc

        acc = jax.lax.fori_loop(0, n - 1, ag_step, acc)
        out = acc.reshape(-1)
        if pad:
            out = out[: g.size]
        return out.reshape(shape).astype(g.dtype)

    return jax.tree.map(leaf, tree)


def quantize_dequantize(tree: Any) -> Tuple[Any, Any]:
    """(compressed value, residual) per leaf — error-feedback building block."""

    def leaf(g):
        q, scale = _quantize(g)
        deq = (q.astype(jnp.float32) * scale).astype(g.dtype)
        return deq, (g - deq)

    pairs = jax.tree.map(leaf, tree)
    comp = jax.tree.map(
        lambda p: p[0], pairs, is_leaf=lambda x: isinstance(x, tuple)
    )
    resid = jax.tree.map(
        lambda p: p[1], pairs, is_leaf=lambda x: isinstance(x, tuple)
    )
    return comp, resid


def with_error_feedback(grads: Any, residual: Any) -> Tuple[Any, Any]:
    """Add carried residual, compress, return (compressed, new residual)."""
    fed = jax.tree.map(lambda g, r: g + r.astype(g.dtype), grads, residual)
    return quantize_dequantize(fed)
