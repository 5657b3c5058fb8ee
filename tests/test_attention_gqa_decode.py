"""Grouped-query decode attention (``attention._gqa_decode_core``).

The core contracts each group of ``R = H // KV`` query heads with its own KV
head instead of repeating K/V to ``H`` heads.  It must equal the
repeat-then-contract formulation written out below, for every group size,
for float and int8 KV, and for ragged per-row positions; and the traced
paged decode step must hold no KV slab widened to the query heads.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.contracts import iter_eqns
from repro.models import attention as attn
from repro.models import registry
from repro.models.common import init_tree
from repro.serving import Scheduler, ServingConfig

ARCH = registry.get("qwen2-0.5b")
HI = jax.lax.Precision.HIGHEST


def _repeat_then_contract(q, kf, vf, pos):
    """The decode core as it was: K/V repeated to H heads, one contraction
    per query head, per-row mask ``kv_pos <= pos[b]``."""
    b, _, h, hd = q.shape
    r = h // kf.shape[2]
    kr = jnp.repeat(kf, r, axis=2)
    vr = jnp.repeat(vf, r, axis=2)
    qf = q.astype(jnp.float32) * hd ** -0.5
    s = jnp.einsum("bqhd,bshd->bhqs", qf, kr, precision=HI)
    valid = jnp.arange(kf.shape[1])[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", p, vr, precision=HI)


@pytest.mark.parametrize("int8_kv", [False, True], ids=["float_kv", "int8_kv"])
@pytest.mark.parametrize("r", [1, 2, 7])
def test_grouped_core_matches_repeat_then_contract(r, int8_kv):
    b, s, kvh, hd = 4, 40, 2, 32
    h = kvh * r
    pos = jnp.asarray([0, s - 1, 7, 23], jnp.int32)  # ragged, both ends
    ks = jax.random.split(jax.random.PRNGKey(r), 5)
    q = jax.random.normal(ks[0], (b, 1, h, hd), jnp.bfloat16)
    cfg = dataclasses.replace(ARCH.smoke_config, kv_cache_int8=int8_kv)
    if int8_kv:
        shape = (b, s, kvh, hd)
        g = {
            "k": jax.random.randint(ks[1], shape, -127, 128, jnp.int32).astype(jnp.int8),
            "v": jax.random.randint(ks[2], shape, -127, 128, jnp.int32).astype(jnp.int8),
            "k_scale": jax.random.uniform(ks[3], shape[:3], minval=1e-3, maxval=2e-2),
            "v_scale": jax.random.uniform(ks[4], shape[:3], minval=1e-3, maxval=2e-2),
        }
        k_ref = g["k"].astype(jnp.float32) * g["k_scale"][..., None]
        v_ref = g["v"].astype(jnp.float32) * g["v_scale"][..., None]
    else:
        g = {
            "k": jax.random.normal(ks[1], (b, s, kvh, hd), jnp.bfloat16),
            "v": jax.random.normal(ks[2], (b, s, kvh, hd), jnp.bfloat16),
        }
        k_ref, v_ref = g["k"].astype(jnp.float32), g["v"].astype(jnp.float32)

    kf, vf = attn._kv_view(g, cfg)
    valid = jnp.arange(s)[None, :] <= pos[:, None]
    got = jax.jit(attn._gqa_decode_core)(q, kf, vf, valid)
    want = _repeat_then_contract(q, k_ref, v_ref, pos)
    assert got.shape == (b, 1, h, hd) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    # row 0 attends only its first KV row: the output is exactly that V row
    np.testing.assert_array_equal(
        np.asarray(got[0, 0]), np.asarray(jnp.repeat(v_ref[0, 0], r, axis=0))
    )


def test_paged_decode_step_never_widens_kv_to_query_heads():
    """Trace one ``lm_decode_paged`` step (H=4 query, KV=2 heads): no value
    in its jaxpr (scan body included) or lowered program has the shape of a
    KV slab repeated to the query heads, flat or grouped."""
    cfg = dataclasses.replace(
        ARCH.smoke_config, num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=64, remat=False,
    )
    params = init_tree(ARCH.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    sch = Scheduler(
        ARCH, cfg, params, ServingConfig(batch_size=3, max_seq=24, block_size=8)
    )
    b, g, h, kvh, hd = 3, 24, cfg.n_q_heads, cfg.num_kv_heads, cfg.hd
    widened = {(b, g, h, hd), (b, g, kvh, h // kvh, hd)}
    args = (
        sch.params, jnp.asarray(sch._tokens), sch.kv_pool, jnp.asarray(sch._table),
        jnp.asarray(sch._pos), jnp.ones((b,), bool),
    )

    traced = {
        tuple(getattr(v.aval, "shape", ()))
        for eqn in iter_eqns(sch.decode_checker().jaxpr)
        for v in eqn.outvars
    }
    assert (b, g, kvh, hd) in traced  # the gathered slab itself is there
    assert not traced & widened

    text = jax.jit(sch._decode_fn).lower(*args).as_text()
    lowered = {
        tuple(int(d) for d in m.split("x"))
        for m in re.findall(r"tensor<((?:\d+x)+)", text)
        for m in [m.rstrip("x")]
    }
    assert (b, g, kvh, hd) in lowered
    assert not lowered & widened
