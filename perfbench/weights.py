"""The benchmark's weights, made on the device from ``--seed``.

One jitted call makes every weight in bf16, the type it is served in:
in the published layout for the reference (``perfbench/reference_qwen2.py``)
or in the program's parameter tree for the system under test.  Both come
from the same seed through the same draws, so the reference takes nothing
that the program made.  This module imports nothing of the program.
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


def _shapes(sizes: dict):
    L, d = sizes["num_hidden_layers"], sizes["hidden_size"]
    hd = sizes.get("head_dim") or d // sizes["num_attention_heads"]
    hq = sizes["num_attention_heads"] * hd
    hkv = sizes["num_key_value_heads"] * hd
    f, v = sizes["intermediate_size"], sizes["vocab_size"]
    # name: (shape, init) with init "embed" (0.02), "fan:<n>" (1/sqrt n),
    # "bias" (0.1), "norm" (1 + 0.1 N)
    return {
        "embed": ((v, d), "embed"),
        "final_norm": ((d,), "norm"),
        "in_norm": ((L, d), "norm"),
        "post_norm": ((L, d), "norm"),
        "q_w": ((L, d, hq), f"fan:{d}"),
        "q_b": ((L, hq), "bias"),
        "k_w": ((L, d, hkv), f"fan:{d}"),
        "k_b": ((L, hkv), "bias"),
        "v_w": ((L, d, hkv), f"fan:{d}"),
        "v_b": ((L, hkv), "bias"),
        "o_w": ((L, hq, d), f"fan:{hq}"),
        "gate_w": ((L, d, f), f"fan:{d}"),
        "up_w": ((L, d, f), f"fan:{d}"),
        "down_w": ((L, f, d), f"fan:{f}"),
    }


def _make(sizes_items: tuple, key: jax.Array) -> dict:
    out = {}
    for i, (name, (shape, init)) in enumerate(_shapes(dict(sizes_items)).items()):
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
def _maker(sizes_items: tuple, program_layout: bool):
    def make(key):
        w = _make(sizes_items, key)
        return to_program(w) if program_layout else w

    return jax.jit(make)


def _items(sizes: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in sizes.items() if not isinstance(v, (dict, list))))


def published_weights(sizes: dict, seed: int) -> dict:
    """The weights of ``seed`` in the published layout, bf16, on device."""
    return _maker(_items(sizes), False)(seed_key(seed))


def program_weights(sizes: dict, seed: int) -> dict:
    """The same weights in the program's parameter tree, one jitted call."""
    return _maker(_items(sizes), True)(seed_key(seed))


def to_program(w: dict) -> dict:
    """Published layout -> the program's tree (``models/lm.py``): the
    SwiGLU input projection is one ``wi`` bank, up columns then gate."""
    return {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "ln1": {"scale": w["in_norm"]},
            "ln2": {"scale": w["post_norm"]},
            "attn": {
                "wq": {"w": w["q_w"], "b": w["q_b"]},
                "wk": {"w": w["k_w"], "b": w["k_b"]},
                "wv": {"w": w["v_w"], "b": w["v_b"]},
                "wo": {"w": w["o_w"]},
            },
            "ffn": {
                "wi": {"w": jnp.concatenate([w["up_w"], w["gate_w"]], axis=-1)},
                "wo": {"w": w["down_w"]},
            },
        },
    }


