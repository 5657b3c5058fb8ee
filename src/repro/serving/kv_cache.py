"""Paged KV cache: fixed-size blocks, a free-list allocator, block tables.

The serving pool stores every layer's KV rows in ``num_blocks`` fixed-size
blocks instead of a dense ``(batch, max_seq)`` reservation per decode slot.
A request owns an ordered list of block ids (its *block table* row); flat
token position ``p`` lives at ``(table[p // block_size], p % block_size)``.
Attention reads through the table with :func:`gather_kv` and writes through
:func:`scatter_kv` — models and runtime never index the pool directly
(rule RPR007 in ``repro.analysis``).

Pool leaf layout (dense-LM family): ``(layers, num_blocks, block_size,
...)`` — exactly ``arch.cache_def(cfg, batch=num_blocks,
max_seq=block_size, ...)`` with the ``(batch, kv_seq)`` axes reinterpreted
as ``(block, in-block offset)``, so int8 KV scale leaves page for free.

Reserved blocks (never allocated to a request):

* block ``NULL_BLOCK`` (0) — permanently zero; unallocated table entries
  point here so a full-width gather reads exact zeros, and it is never a
  scatter destination;
* blocks ``1 .. batch_size`` — one private *trash* block per decode slot;
  inactive slots in a batched decode step redirect their writes there
  (per-slot, so no two rows ever scatter to the same destination and the
  step stays bitwise deterministic).

Admission contract (DESIGN.md §13): a request is admitted only once
``blocks_needed(prompt + max_new_tokens)`` blocks can be reserved, and the
allocator zeroes every block at (re)allocation time — a recycled block can
never leak the previous occupant's KV into a new request (the stale-KV
regression in ``tests/test_serving.py`` plants sentinels to prove it).
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

NULL_BLOCK = 0


class BlockAllocator:
    """Host-side free-list allocator over the pool's block ids.

    Blocks ``[0, reserved)`` (the null block + per-slot trash blocks) are
    never handed out.  ``alloc`` is all-or-nothing: admission either gets
    the request's full worst-case reservation or stays queued, so a running
    request can never hit pool exhaustion mid-decode (no preemption path).
    """

    def __init__(self, num_blocks: int, block_size: int, *, reserved: int = 1):
        if reserved < 1:
            raise ValueError("need at least the null block reserved")
        if num_blocks <= reserved:
            raise ValueError(
                f"num_blocks={num_blocks} leaves no allocatable blocks "
                f"(reserved={reserved})"
            )
        self.num_blocks = num_blocks
        self.block_size = block_size
        self.reserved = reserved
        self._free: collections.deque = collections.deque(range(reserved, num_blocks))

    def blocks_needed(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Reserve ``n`` blocks, or None (and no change) if unavailable."""
        if n > len(self._free):
            return None
        return [self._free.popleft() for _ in range(n)]

    def free(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            if not (self.reserved <= b < self.num_blocks):
                raise ValueError(f"freed block {b} was never allocatable")
        self._free.extend(blocks)


# ---------------------------------------------------------------------------
# Pool construction
# ---------------------------------------------------------------------------
def _is_def_leaf(x) -> bool:
    return (
        isinstance(x, tuple)
        and len(x) == 3
        and isinstance(x[0], tuple)
        and isinstance(x[1], tuple)
    )


def init_pool(pool_def: Any) -> Any:
    """Zero-initialized pool from a ``cache_def``-style ``(shape, axes,
    dtype)`` leaf tree (evaluated with ``batch=num_blocks,
    max_seq=block_size``).  Zeros everywhere make the null block exact and
    every block "pre-zeroed" for its first allocation."""

    def leaf(d):
        shape, axes, dtype = d
        if "batch" not in axes or "kv_seq" not in axes:
            raise ValueError(f"cache leaf axes {axes} have no (batch, kv_seq) pair")
        if axes.index("kv_seq") != axes.index("batch") + 1:
            raise ValueError(
                f"paged pool needs kv_seq right after batch, got axes {axes}"
            )
        return jnp.zeros(shape, dtype)

    return jax.tree.map(leaf, pool_def, is_leaf=_is_def_leaf)


# ---------------------------------------------------------------------------
# Destination computation (the only code that touches block tables)
# ---------------------------------------------------------------------------
def token_dest(
    block_table: jax.Array,  # (B, W) int32
    pos: jax.Array,  # (B,) int32 — flat write position per row
    active: jax.Array,  # (B,) bool — rows with a live decode slot
    trash_blocks: jax.Array,  # (B,) int32 — per-slot trash block ids
    block_size: int,
) -> Tuple[jax.Array, jax.Array]:
    """(block ids, in-block offsets) for one decode token per batch row;
    inactive rows redirect to their private trash block."""
    owned = jnp.take_along_axis(block_table, (pos // block_size)[:, None], axis=1)
    blocks = jnp.where(active, owned[:, 0], trash_blocks)
    offsets = jnp.where(active, pos % block_size, 0)
    return blocks, offsets


def chunk_dest(
    block_table: jax.Array,  # (W,) int32 — one request's table row
    t0: jax.Array,  # scalar int32 — chunk start (flat position)
    tc: int,
    block_size: int,
) -> Tuple[jax.Array, jax.Array]:
    """(block ids, offsets), each (tc,), for a prefill chunk covering flat
    positions ``[t0, t0 + tc)`` of a single request."""
    flat = t0 + jnp.arange(tc, dtype=jnp.int32)
    return block_table[flat // block_size], flat % block_size


# ---------------------------------------------------------------------------
# Gather / scatter (the only code that indexes pool leaves)
# ---------------------------------------------------------------------------
def gather_kv(
    kv_pool: Dict[str, jax.Array],  # per-layer leaves (num_blocks, bs, ...)
    block_table: jax.Array,  # (B, W) int32
    length: int,
    layer: Optional[jax.Array] = None,
) -> Dict[str, jax.Array]:
    """Contiguous ``(B, length, ...)`` view of the first ``length`` cache
    rows of each request, read through the block table.  Unallocated table
    entries point at the null block, so rows past a request's allocation
    read as exact zeros.  With ``layer`` the leaves are the whole stacked
    pool ``(layers, num_blocks, bs, ...)`` and that layer is read."""

    def leaf(p):
        bs = p.shape[1] if layer is None else p.shape[2]
        n = -(-length // bs)
        t = block_table[:, :n]
        out = p[t] if layer is None else p[layer, t]  # (B, n, bs, ...)
        out = out.reshape((t.shape[0], n * bs) + out.shape[3:])
        return out[:, :length]

    return jax.tree.map(leaf, kv_pool)


def scatter_kv(
    kv_pool: Dict[str, jax.Array],
    blocks: jax.Array,  # (N,) int32
    offsets: jax.Array,  # (N,) int32
    rows: Dict[str, jax.Array],  # per-layer: leaves (N, ...)
    layer: Optional[jax.Array] = None,
) -> Dict[str, jax.Array]:
    """Write N rows at ``(blocks[i], offsets[i])`` in every leaf (of layer
    ``layer`` of the whole stacked pool, when given).  Callers guarantee
    destinations are distinct (distinct block tables per request;
    per-slot trash blocks), keeping the scatter order-free."""
    if layer is None:
        return jax.tree.map(
            lambda p, r: p.at[blocks, offsets].set(r.astype(p.dtype)), kv_pool, rows
        )
    return jax.tree.map(
        lambda p, r: p.at[layer, blocks, offsets].set(r.astype(p.dtype)), kv_pool, rows
    )


def zero_blocks(kv_pool: Any, blocks: Sequence[int]) -> Any:
    """Zero the given blocks across every leaf of the full stacked pool
    (leaves ``(layers, num_blocks, block_size, ...)``) — the allocation-time
    half of the stale-KV admission contract."""
    idx = jnp.asarray(list(blocks), jnp.int32)
    return jax.tree.map(lambda p: p.at[:, idx].set(0), kv_pool)
