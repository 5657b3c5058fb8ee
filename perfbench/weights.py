"""The benchmark's weights, made on the device from ``--seed``.

One jitted call makes every weight in bf16, the type it is served in: in
the published layout that the configuration's architecture module states
(its ``layout``), for the reference, or mapped by the module's
``to_program`` to the program's parameter tree, for the system under test.
Both come from the same seed through the same draws, so the reference
takes nothing that the program made.  This module imports nothing of the
program and names no architecture.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed (past 32 bits too)."""
    s = seed % (1 << 62)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0x7FFFFFFF), s >> 31)


def _make(arch, sizes_items: tuple, key: jax.Array) -> dict:
    """Draw ``arch.layout``'s weights in its order: the i-th from
    ``fold_in(key, i)``."""
    out = {}
    for i, (name, (shape, init)) in enumerate(arch.layout(dict(sizes_items)).items()):
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if init == "embed":
            x = 0.02 * z
        elif init == "bias":
            x = 0.1 * z
        elif init == "norm":
            x = 1.0 + 0.1 * z
        else:
            x = z / math.sqrt(int(init.split(":")[1]))
        out[name] = x.astype(jnp.bfloat16)
    return out


@functools.lru_cache(maxsize=None)
def _maker(arch, sizes_items: tuple, program_layout: bool):
    def make(key):
        w = _make(arch, sizes_items, key)
        return arch.to_program(w) if program_layout else w

    return jax.jit(make)


def _items(sizes: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in sizes.items() if not isinstance(v, (dict, list))))


def published_weights(arch, sizes: dict, seed: int) -> dict:
    """The weights of ``seed`` in ``arch``'s published layout, bf16, on device."""
    return _maker(arch, _items(sizes), False)(seed_key(seed))


def program_weights(arch, sizes: dict, seed: int) -> dict:
    """The same weights in the program's parameter tree, one jitted call."""
    return _maker(arch, _items(sizes), True)(seed_key(seed))
