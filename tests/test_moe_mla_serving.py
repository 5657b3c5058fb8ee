"""DeepSeek-V2's mechanisms on the paged serving path, at the SMOKE size.

* the MoE is dropless: every (token, pick) pair routed to a held expert is
  computed, whatever the skew, by every GEMM path (digital, ``ref``,
  ``pallas``), and equals a per-token loop over the experts;
* a layer that holds ``num_experts`` of the router's ``num_experts *
  ep_size`` experts computes its share: the shares' parts, with the shared
  experts counted once, add up to the uncut layer;
* the absorbed paged MLA decode equals the expanded form;
* YaRN's inverse frequencies and softmax factor equal hand values;
* the MoE programs count their expert rows on the ``serve.prefill`` /
  ``serve.decode`` spans, and a dense model's spans carry no such count;
* the tiny Qwen2 paged prefill and decode logits are bit for bit what
  they were before MoE and MLA came to the paged path.
"""

import dataclasses
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dpu import DPUConfig
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import ffn, registry
from repro.models.common import init_tree
from repro.serving import Request, Scheduler, ServingConfig, spans
from repro.serving import kv_cache as kvc

DS = registry.get("deepseek-v2-lite-16b")
IDEAL = DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0)


def _smoke(**kw):
    return dataclasses.replace(
        DS.smoke_config, remat=False, param_dtype=jnp.float32,
        compute_dtype=jnp.float32, **kw,
    )


def _moe_params(cfg, seed=0):
    return init_tree(ffn.moe_def(cfg), jax.random.PRNGKey(seed), jnp.float32)


def _loop_moe(params, x, cfg):
    """The held experts' part token by token: sum over its top-k picks of
    weight x SwiGLU expert, skipping experts not held."""
    _, topv, topi = ffn.route(params, x, cfg)
    wi, wo = params["experts"]["wi"]["w"], params["experts"]["wo"]["w"]
    out = np.zeros(x.shape, np.float64)
    xs = np.asarray(x, np.float64)
    for t in range(x.shape[0]):
        for j in range(cfg.num_experts_per_tok):
            e = int(topi[t, j])
            if e >= cfg.num_experts:
                continue
            h = xs[t] @ np.asarray(wi[e], np.float64)
            u, g = np.split(h, 2)
            y = (u * g / (1 + np.exp(-g))) @ np.asarray(wo[e], np.float64)
            out[t] += float(topv[t, j]) * y
    return out


# Relative (Frobenius) error of an int8 photonic MoE layer against float64.
INT8_REL = 0.05


def _rel(got, want):
    got = np.asarray(got, np.float64)
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-12)


def _skew(params, cfg, kind):
    """Router weights that send every token's first pick to one expert."""
    if kind == "router":
        return params
    w = np.array(params["router"]["w"])
    target = 0 if kind == "one_held" else cfg.num_experts * cfg.ep_size - 1
    w[:, target] += 100.0
    return {**params, "router": {"w": jnp.asarray(w)}}


@pytest.mark.parametrize("path", ["digital", "ref", "pallas"])
@pytest.mark.parametrize("skew", ["router", "one_held", "one_absent"])
def test_moe_is_dropless_and_equals_the_per_token_loop(skew, path):
    """No pair is dropped, however skewed the routing: with every token's
    first pick on one held expert (the case a capacity would cut), on one
    expert held elsewhere, or as the router draws.  The digital path equals
    the loop to float32 rounding.  The photonic ideal datapath quantizes
    activations per tensor and weights per column to int8, twice (expert
    input and hidden): its relative error reads 0.025-0.031 over three
    seeds at this size, so the bound is 0.05; 6-bit operands read
    0.10-0.12 and 4-bit 0.40-0.50, both outside it (4-bit below)."""
    kw = {} if path == "digital" else dict(photonic=IDEAL, photonic_backend=path)
    cfg = _smoke(ep_size=2, num_experts=4, **kw)
    params = _skew(_moe_params(cfg), cfg, skew)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(37, cfg.d_model)), jnp.float32)
    out, _, topi, counts = ffn.held_experts(params, x, cfg)
    want = _loop_moe(params, x, cfg)
    held_pairs = int(np.sum(np.asarray(topi) < cfg.num_experts))
    assert int(counts[0]) == held_pairs
    tile = ffn.row_tile(37, cfg)
    assert int(counts[1]) % tile == 0 and int(counts[1]) >= held_pairs
    err = _rel(out, want)
    assert err < (1e-5 if path == "digital" else INT8_REL), err
    if skew == "one_held":
        assert held_pairs >= 37  # every token reached the skewed expert


def test_int8_tolerance_fails_a_four_bit_datapath():
    """The bound above is int8's: the same layer with 4-bit operands reads
    far wider, so the tolerance separates the two."""
    cfg = _smoke(ep_size=2, num_experts=4, photonic=IDEAL, photonic_backend="ref")
    cfg4 = dataclasses.replace(
        cfg, photonic=dataclasses.replace(IDEAL, operand_bits=4)
    )
    params = _moe_params(cfg)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(37, cfg.d_model)), jnp.float32)
    want = _loop_moe(params, x, cfg)
    out4, *_ = ffn.held_experts(params, x, cfg4)
    assert _rel(out4, want) > INT8_REL


def test_ep_shares_add_up_to_the_uncut_layer():
    """Each of ``ep_size`` chips holds its own ``num_experts`` (share r is
    the uncut router with experts ``r*held ..`` moved to the front); the
    shares' routed parts plus the shared experts once equal the uncut MoE
    layer (float32, digital)."""
    full = _smoke()
    params = _moe_params(full, seed=3)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 9, full.d_model)), jnp.float32)
    want, _ = ffn.moe_layer(params, x, full)
    ep = 4
    held = full.num_experts // ep
    share_cfg = dataclasses.replace(full, num_experts=held, ep_size=ep)
    total = np.zeros(want.shape)
    for r in range(ep):
        order = np.roll(np.arange(full.num_experts), -r * held)
        p = {
            "router": {"w": params["router"]["w"][:, order]},
            "experts": jax.tree.map(lambda a: a[order[:held]], params["experts"]),
        }
        part, *_ = ffn.held_experts(p, x.reshape(-1, full.d_model), share_cfg)
        total += np.asarray(part).reshape(want.shape)
    total += np.asarray(ffn.mlp(params["shared"], x, full, site="ffn.shared"))
    np.testing.assert_allclose(total, np.asarray(want), atol=1e-5, rtol=1e-5)


def test_absorbed_paged_decode_equals_the_expanded_form():
    """One paged MLA decode step (absorbed: W_uk into the query, W_uv on
    the output) against keys and values expanded from the same gathered
    latent rows, at float32 rounding."""
    cfg = _smoke()
    p = init_tree(attn.mla_def(cfg), jax.random.PRNGKey(4), jnp.float32)
    b, bs, width = 3, 4, 6
    nb = 1 + b + b * width
    rng = np.random.default_rng(5)
    pool = kvc.init_pool({
        k: ((1,) + s, (None,) + a, jnp.float32)
        for k, (s, a, _) in attn.mla_cache_def(cfg, nb, bs, jnp.float32).items()
    })
    pool = jax.tree.map(lambda z: jnp.asarray(rng.normal(size=z.shape), jnp.float32), pool)
    table = jnp.asarray(1 + b + np.arange(b * width).reshape(b, width), jnp.int32)
    pos = jnp.asarray([0, 7, 22], jnp.int32)
    blocks, offsets = kvc.token_dest(table, pos, jnp.ones(b, bool), jnp.arange(1, b + 1), bs)
    x = jnp.asarray(rng.normal(size=(b, 1, cfg.d_model)), jnp.float32)
    li = jnp.int32(0)
    got, new_pool = attn.mla_decode_paged(
        p, x, pool, li, table, pos, blocks, offsets, cfg, gather_len=bs * width
    )
    g = kvc.gather_kv(new_pool, table, bs * width, layer=li)
    q_nope, q_rope, _, _ = attn._mla_qkv(p, x, cfg, pos[:, None])
    k, v = attn._mla_expand_kv(p, g["c_kv"], g["k_rope"][:, :, None, :], cfg)
    q = jnp.concatenate([q_nope, q_rope], -1)
    hi = jax.lax.Precision.HIGHEST
    s = jnp.einsum("bqhd,bshd->bhqs", q, k, precision=hi) * attn._mla_scale(cfg)
    valid = jnp.arange(bs * width)[None, :] <= pos[:, None]
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    o = jnp.einsum("bhqs,bshd->bqhd", jax.nn.softmax(s, -1), v, precision=hi)
    want = cm.dense(p["wo"], o.reshape(b, 1, -1), cfg, site="attn.wo")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_yarn_frequencies_and_softmax_scale_equal_hand_values():
    """DeepSeek-V2-Lite's YaRN (factor 40 over 4096, beta 32 / 1, rope dim
    64, theta 1e4): correction dims floor(10.47) = 10 and ceil(22.51) = 23;
    below 10 the original frequency, from 23 on it over 40, between them a
    linear ramp; softmax scale 192**-0.5 * (0.1 * 0.707 * ln 40 + 1)**2."""
    full = DS.config
    inv = np.asarray(cm.rope_inv_freq(64, full.rope_theta, full.rope_scaling))
    base = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:10], base[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    ramp = (16 - 10) / 13
    np.testing.assert_allclose(inv[16], 0.01 * (ramp / 40 + 1 - ramp), rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert abs(m * m - 1.5896) < 1e-4
    assert abs(attn._mla_scale(full) - 192**-0.5 * m * m) < 1e-9


def _ds_requests(cfg, lengths=(13, 5, 21), max_new=5):
    rng = np.random.default_rng(6)
    return [
        Request(uid=i, prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                max_new_tokens=max_new)
        for i, n in enumerate(lengths)
    ]


def test_moe_spans_count_expert_rows():
    """Every ``serve.prefill`` / ``serve.decode`` span of a MoE model holds
    ``expert_rows`` and ``expert_rows_computed``, read with the step's own
    transfer.  Holding all experts (``ep_size`` 1), every pair is a held
    expert's: rows x top-k x MoE layers exactly; the GEMM computes whole row
    tiles, never fewer rows.  The totals add up in ``Scheduler.stats``."""
    cfg = _smoke(photonic=IDEAL, photonic_backend="ref")
    params = init_tree(DS.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    scfg = ServingConfig(batch_size=2, max_seq=40, block_size=4, chunk_tokens=8)
    sched = Scheduler(DS, cfg, params, scfg)
    steps = 0
    for r in _ds_requests(cfg):
        sched.submit(r)
    while sched.pending:
        sched.step()
        steps += 1
    moe_layers = cfg.num_layers - cfg.first_dense_layers
    k = cfg.num_experts_per_tok
    rows = computed = 0
    for step in list(spans.LOG)[-steps:]:
        for sp in step:
            if sp.name == "serve.prefill":
                assert sp.counts["expert_rows"] == sp.counts["tc"] * k * moe_layers
            elif sp.name == "serve.decode":
                b = scfg.batch_size
                assert sp.counts["expert_rows"] == b * k * moe_layers
            else:
                continue
            assert sp.counts["expert_rows_computed"] >= sp.counts["expert_rows"]
            rows += sp.counts["expert_rows"]
            computed += sp.counts["expert_rows_computed"]
    assert rows > 0 and not sched._unread
    assert (rows, computed) == (
        sched.stats["expert_rows"], sched.stats["expert_rows_computed"]
    )


def test_dense_spans_carry_no_expert_count():
    arch = registry.get("qwen2-0.5b")
    cfg = dataclasses.replace(
        arch.smoke_config, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=128, vocab_size=64, remat=False,
    )
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    sched = Scheduler(arch, cfg, params, ServingConfig(batch_size=2, max_seq=32, block_size=4))
    steps = 0
    for r in _ds_requests(cfg, (6, 9), 3):
        sched.submit(r)
    while sched.pending:
        sched.step()
        steps += 1
    for step in list(spans.LOG)[-steps:]:
        for sp in step:
            assert "expert_rows" not in sp.counts
    assert sched.stats["expert_rows"] == 0


# The logits rows every request of a tiny Qwen2 run through the paged
# scheduler emitted (chunked prefill, interleaved decode), float and ideal
# photonic Pallas, hashed; the digests and tokens are those of the tree as
# it stood before MoE and MLA came to the paged path.
QWEN2_DIGESTS = {
    "float": "610043eeff51011d8640daeab98306947cc9d2ec8bddf331e12d22e677e5fc59",
    "ideal": "57a7d3e6115c3b1f98aba91390608127ba712f60a0b4a1068eef463af43842c8",
}
QWEN2_TOKENS = [[52, 8, 11, 53, 52], [60, 60, 12, 12, 12], [34, 31, 10, 31, 15]]


@pytest.mark.parametrize("path", ["float", "ideal"])
def test_qwen2_paged_logits_are_bitwise_unchanged(path):
    arch = registry.get("qwen2-0.5b")
    kw = {} if path == "float" else dict(photonic=IDEAL, photonic_backend="pallas")
    cfg = dataclasses.replace(
        arch.smoke_config, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
        d_ff=128, vocab_size=64, remat=False, **kw,
    )
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(7), cfg.param_dtype)
    sched = Scheduler(arch, cfg, params, ServingConfig(
        batch_size=2, max_seq=32, block_size=4, chunk_tokens=6, record_logits=True,
    ))
    rng = np.random.default_rng(11)
    reqs = [
        Request(uid=i, prompt=rng.integers(0, 64, n).astype(np.int32), max_new_tokens=5)
        for i, n in enumerate((9, 4, 13))
    ]
    sched.run(reqs)
    h = hashlib.sha256()
    for r in reqs:
        for row in r.logits:
            h.update(np.asarray(row, np.float32).tobytes())
    assert [r.output for r in reqs] == QWEN2_TOKENS
    assert h.hexdigest() == QWEN2_DIGESTS[path]


def test_paged_moe_mla_decode_has_zero_weight_rounds():
    """The weight-stationary contract holds on the new path: a decode step
    over the prepacked params rounds no weight (experts read their packed
    banks; ``wuk``/``wuv`` enter the absorbed einsums as floats)."""
    cfg = _smoke(photonic=IDEAL, photonic_backend="ref")
    params = init_tree(DS.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    sched = Scheduler(DS, cfg, params, ServingConfig(batch_size=2, max_seq=32, block_size=8))
    # One expert's input bank; the grouped activation buffers of a 2-row
    # decode (64 rows of at most d_model) stay below it.
    min_w = cfg.d_model * 2 * cfg.moe_hidden
    sched.decode_checker().assert_zero_weight_rounds(min_w)
    # positive control: the same step over per-call (unpacked) params
    sched.params = params
    assert sched.decode_checker().weight_round_ops(min_w) > 0
