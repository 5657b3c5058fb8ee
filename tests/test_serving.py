"""Paged-KV continuous-batching serving — DESIGN.md §13.

Contracts under test:

* the block allocator is all-or-nothing and never double-books;
* gather/scatter round-trip through block tables exactly;
* chunked prefill interleaved with decode preserves per-request outputs
  bitwise vs one-shot prefill (float path, any chunking), and vs the
  legacy fixed-slot engine under an ideal photonic channel on both
  backends (lockstep waves), with and without a TP mesh;
* decode over prepacked params traces with zero weight-sized round ops
  (the PR-3 weight-stationary contract, via ``ContractChecker``);
* a recycled slot cannot replay a previous occupant's sampling stream
  (keys fold in the request uid, not the slot);
* a recycled KV block cannot leak stale rows into a new request
  (allocation-time zeroing; NaN sentinels would propagate loudly).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dpu import DPUConfig
from repro.launch import mesh as mesh_mod
from repro.models import registry
from repro.models.common import init_tree
from repro.runtime import serve
from repro.serving import NULL_BLOCK, BlockAllocator, Request, Scheduler, ServingConfig
from repro.serving import kv_cache as kvc

TP = mesh_mod.max_tp_degree()

ARCH = registry.get("qwen2-0.5b")


def _small_cfg(**kw):
    return dataclasses.replace(
        ARCH.smoke_config,
        num_layers=2,
        d_model=64,
        num_heads=4,
        num_kv_heads=2,
        d_ff=128,
        vocab_size=64,
        remat=False,
        **kw,
    )


def _ideal_dpu(n=16):
    return DPUConfig(organization="SMWA", bits=4, dpe_size=n)


def _params(cfg, seed=0):
    return init_tree(ARCH.param_defs(cfg), jax.random.PRNGKey(seed), cfg.param_dtype)


def _reqs(lengths, cfg, max_new=4, uid0=0):
    rng = np.random.default_rng(42)
    return [
        Request(
            uid=uid0 + i,
            prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
            max_new_tokens=max_new,
        )
        for i, n in enumerate(lengths)
    ]


# ---------------------------------------------------------------------------
# Block allocator
# ---------------------------------------------------------------------------
class TestBlockAllocator:
    def test_all_or_nothing_and_recycle(self):
        a = BlockAllocator(8, 4, reserved=2)
        assert a.free_blocks == 6
        got = a.alloc(4)
        assert sorted(got) == [2, 3, 4, 5]
        assert a.alloc(3) is None  # only 2 left: no partial grant
        assert a.free_blocks == 2
        a.free(got)
        assert a.free_blocks == 6

    def test_blocks_needed_ceil(self):
        a = BlockAllocator(8, 4)
        assert [a.blocks_needed(n) for n in (1, 4, 5, 8, 9)] == [1, 1, 2, 2, 3]

    def test_reserved_blocks_never_granted_and_guarded(self):
        a = BlockAllocator(6, 4, reserved=3)
        assert sorted(a.alloc(3)) == [3, 4, 5]
        with pytest.raises(ValueError):
            a.free([NULL_BLOCK])
        with pytest.raises(ValueError):
            BlockAllocator(3, 4, reserved=3)  # nothing allocatable


# ---------------------------------------------------------------------------
# Pool primitives
# ---------------------------------------------------------------------------
class TestPoolOps:
    def test_scatter_gather_roundtrip(self):
        bs = 4
        pool = {"k": jnp.zeros((6, bs, 2, 3)), "v": jnp.zeros((6, bs, 2, 3))}
        table = jnp.asarray([[2, 5, NULL_BLOCK]], jnp.int32)  # one request
        rng = np.random.default_rng(0)
        rows = {
            "k": jnp.asarray(rng.normal(size=(6, 2, 3)), jnp.float32),
            "v": jnp.asarray(rng.normal(size=(6, 2, 3)), jnp.float32),
        }
        blocks, offsets = kvc.chunk_dest(table[0], jnp.int32(0), 6, bs)
        np.testing.assert_array_equal(blocks, [2, 2, 2, 2, 5, 5])
        np.testing.assert_array_equal(offsets, [0, 1, 2, 3, 0, 1])
        pool = kvc.scatter_kv(pool, blocks, offsets, rows)
        out = kvc.gather_kv(pool, table, 6)
        np.testing.assert_array_equal(np.asarray(out["k"][0]), np.asarray(rows["k"]))
        # beyond the written prefix: null block -> exact zeros
        full = kvc.gather_kv(pool, table, 12)
        assert np.all(np.asarray(full["v"][0, 8:]) == 0)

    def test_token_dest_redirects_inactive_rows_to_trash(self):
        table = jnp.asarray([[3, 4], [5, NULL_BLOCK]], jnp.int32)
        pos = jnp.asarray([6, 1], jnp.int32)
        active = jnp.asarray([True, False])
        trash = jnp.asarray([1, 2], jnp.int32)
        blocks, offsets = kvc.token_dest(table, pos, active, trash, 4)
        np.testing.assert_array_equal(blocks, [4, 2])
        np.testing.assert_array_equal(offsets, [2, 0])

    def test_zero_blocks_targets_only_given_blocks(self):
        pool = {"k": jnp.ones((2, 5, 3, 2))}  # stacked: (layers, blocks, ...)
        pool = kvc.zero_blocks(pool, [1, 3])
        k = np.asarray(pool["k"])
        assert np.all(k[:, [1, 3]] == 0)
        assert np.all(k[:, [0, 2, 4]] == 1)

    def test_init_pool_validates_paged_axes(self):
        good = {"k": ((4, 2, 3), ("batch", "kv_seq", None), jnp.float32)}
        assert kvc.init_pool(good)["k"].shape == (4, 2, 3)
        bad = {"k": ((4, 3, 2), ("batch", None, "kv_seq"), jnp.float32)}
        with pytest.raises(ValueError):
            kvc.init_pool(bad)


# ---------------------------------------------------------------------------
# Chunked prefill: bitwise vs one-shot (float path)
# ---------------------------------------------------------------------------
class TestChunkedPrefillBitwise:
    def test_chunked_interleaved_matches_one_shot_bitwise(self):
        """chunk_tokens=3 forces multi-chunk prefills interleaved with live
        decodes; every request's logits must match the one-shot run
        bit-for-bit (same KV block partition fed to attention)."""
        cfg = _small_cfg()
        params = _params(cfg)
        base = dict(batch_size=2, max_seq=32, block_size=4, record_logits=True)

        def run(chunk_tokens, lengths=(5, 11, 7)):
            sch = Scheduler(
                ARCH, cfg, params,
                ServingConfig(chunk_tokens=chunk_tokens, **base),
            )
            reqs = _reqs(lengths, cfg)
            sch.run(reqs)
            assert all(r.done for r in reqs)
            return reqs, sch

        chunked, sch = run(3)
        oneshot, _ = run(64)
        assert sch.stats["prefill_chunks"] > len(chunked)  # actually chunked
        for a, b in zip(chunked, oneshot):
            assert a.output == b.output
            for ra, rb in zip(a.logits, b.logits):
                np.testing.assert_array_equal(ra, rb)

    def test_chunked_matches_standalone_decode_loop(self):
        cfg = _small_cfg()
        params = _params(cfg)
        sch = Scheduler(
            ARCH, cfg, params,
            ServingConfig(batch_size=2, max_seq=32, block_size=4, chunk_tokens=4),
        )
        reqs = _reqs((9, 6), cfg, max_new=5)
        sch.run(reqs)
        for r in reqs:
            b = {"tokens": jnp.asarray(r.prompt)[None, :]}
            logits, cache = ARCH.prefill(params, b, cfg, 32)
            toks = [int(jnp.argmax(logits[0, -1, : cfg.vocab_size]))]
            for _ in range(4):
                logits, cache = ARCH.decode(
                    params, jnp.asarray([[toks[-1]]], jnp.int32), cache, cfg
                )
                toks.append(int(jnp.argmax(logits[0, -1, : cfg.vocab_size])))
            assert toks == r.output


# ---------------------------------------------------------------------------
# Photonic parity vs the legacy engine (ideal channel, both backends)
# ---------------------------------------------------------------------------
class TestPhotonicLegacyParity:
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_lockstep_wave_matches_legacy_bitwise(self, backend):
        """Ideal channel, same-length lockstep wave (the regime where the
        legacy engine is exact): the paged scheduler must emit identical
        tokens — per-tensor activation scales see the same tensors."""
        cfg = _small_cfg(photonic=_ideal_dpu(), photonic_backend=backend)
        params = _params(cfg)
        legacy = serve.LegacyEngine(
            ARCH, cfg, params, serve.ServeConfig(batch_size=2, max_seq=32)
        )
        ref_reqs = _reqs((6, 6), cfg)
        legacy.run(ref_reqs)

        sch = Scheduler(
            ARCH, cfg, params,
            ServingConfig(batch_size=2, max_seq=32, block_size=8, chunk_tokens=64),
        )
        paged_reqs = _reqs((6, 6), cfg)
        sch.run(paged_reqs)
        assert [r.output for r in paged_reqs] == [r.output for r in ref_reqs]

    @pytest.mark.skipif(TP < 2, reason="needs a multi-device TP mesh")
    def test_lockstep_wave_matches_legacy_under_tp_mesh(self):
        mesh = mesh_mod.make_tp_smoke_mesh()
        cfg = _small_cfg(photonic=_ideal_dpu(), photonic_backend="ref")
        params = _params(cfg)
        legacy = serve.LegacyEngine(
            ARCH, cfg, params, serve.ServeConfig(batch_size=2, max_seq=32),
            mesh=mesh, tp_axis="model",
        )
        ref_reqs = _reqs((6, 6), cfg)
        legacy.run(ref_reqs)

        sch = Scheduler(
            ARCH, cfg, params,
            ServingConfig(batch_size=2, max_seq=32, block_size=8, chunk_tokens=64),
            mesh=mesh, tp_axis="model",
        )
        paged_reqs = _reqs((6, 6), cfg)
        sch.run(paged_reqs)
        assert [r.output for r in paged_reqs] == [r.output for r in ref_reqs]


# ---------------------------------------------------------------------------
# Weight-stationary decode (PR-3 contract over the stepped jaxpr)
# ---------------------------------------------------------------------------
class TestWeightStationaryDecode:
    def test_paged_decode_has_zero_weight_rounds(self):
        cfg = _small_cfg(photonic=_ideal_dpu(), photonic_backend="ref")
        params = _params(cfg)
        sch = Scheduler(
            ARCH, cfg, params,
            ServingConfig(batch_size=2, max_seq=32, block_size=8),
        )
        min_w = cfg.d_model * cfg.d_ff // 2
        sch.decode_checker().assert_zero_weight_rounds(min_w)
        # positive control: the same step over per-call (unpacked) params
        # quantizes weights every call
        sch.params = params
        assert sch.decode_checker().weight_round_ops(min_w) > 0


# ---------------------------------------------------------------------------
# Sampling streams: uid-keyed, slot-recycling safe
# ---------------------------------------------------------------------------
class TestSamplingStreams:
    CFG = dict(batch_size=1, max_seq=32, block_size=8, greedy=False, seed=0)

    def test_recycled_slot_does_not_replay_previous_stream(self):
        """batch_size=1 forces the second request through the recycled
        slot; its sample stream must depend only on (seed, uid, step) —
        identical to running it alone in a fresh engine."""
        cfg = _small_cfg()
        params = _params(cfg)
        sch = Scheduler(ARCH, cfg, params, ServingConfig(**self.CFG))
        first, second = _reqs((6, 6), cfg, max_new=8, uid0=11)
        second.prompt = first.prompt.copy()  # same prompt, different uid
        sch.run([first, second])

        fresh = Scheduler(ARCH, cfg, params, ServingConfig(**self.CFG))
        alone = Request(uid=second.uid, prompt=first.prompt, max_new_tokens=8)
        fresh.run([alone])
        assert second.output == alone.output
        # distinct uids on the same prompt sample distinct streams
        assert first.output != second.output

    def test_same_uid_same_prompt_reproduces(self):
        cfg = _small_cfg()
        params = _params(cfg)
        outs = []
        for _ in range(2):
            sch = Scheduler(ARCH, cfg, params, ServingConfig(**self.CFG))
            (r,) = _reqs((7,), cfg, max_new=6, uid0=5)
            sch.run([r])
            outs.append(r.output)
        assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Stale-KV admission contract
# ---------------------------------------------------------------------------
class TestStaleKV:
    def test_recycled_blocks_cannot_leak_into_new_request(self):
        """Fill the pool with one request, then plant NaN sentinels in every
        allocatable block: if admission failed to zero the new request's
        blocks, NaN would reach the logits through 0 * v in attention.
        Logits must be bit-identical to a fresh engine."""
        cfg = _small_cfg()
        params = _params(cfg)
        scfg = ServingConfig(
            batch_size=1, max_seq=32, block_size=4, record_logits=True
        )
        sch = Scheduler(ARCH, cfg, params, scfg)
        (warm,) = _reqs((12,), cfg, max_new=4)
        sch.run([warm])
        res = sch.allocator.reserved

        def poison(p):
            if jnp.issubdtype(p.dtype, jnp.floating):
                return p.at[:, res:].set(jnp.nan)
            return p.at[:, res:].set(99)

        sch.kv_pool = jax.tree.map(poison, sch.kv_pool)
        victim = Request(
            uid=1,
            prompt=np.arange(5, dtype=np.int32) % cfg.vocab_size,
            max_new_tokens=4,
        )
        sch.run([victim])

        fresh = Scheduler(ARCH, cfg, params, scfg)
        clean = Request(uid=1, prompt=victim.prompt, max_new_tokens=4)
        fresh.run([clean])
        assert victim.output == clean.output
        for ra, rb in zip(victim.logits, clean.logits):
            assert np.all(np.isfinite(ra))
            np.testing.assert_array_equal(ra, rb)


# ---------------------------------------------------------------------------
# Admission control / backpressure
# ---------------------------------------------------------------------------
class TestAdmission:
    def test_block_backpressure_serializes_and_completes(self):
        cfg = _small_cfg()
        params = _params(cfg)
        # reserved = 1 + 2 (null + trash); 2 allocatable blocks = exactly one
        # request's worst case, so admissions serialize on memory.
        sch = Scheduler(
            ARCH, cfg, params,
            ServingConfig(batch_size=2, max_seq=32, block_size=8, num_blocks=5),
        )
        reqs = _reqs((6, 6, 6), cfg)
        sch.run(reqs)
        assert all(r.done for r in reqs)
        assert sch.stats["completed"] == 3
        assert sch.allocator.free_blocks == 2
        for r in reqs:
            assert r.t_submit <= r.t_first <= r.t_done

    def test_oversized_requests_rejected_at_submit(self):
        cfg = _small_cfg()
        params = _params(cfg)
        sch = Scheduler(
            ARCH, cfg, params,
            ServingConfig(batch_size=1, max_seq=16, block_size=8, num_blocks=3),
        )
        with pytest.raises(ValueError, match="max_seq"):
            sch.submit(Request(uid=0, prompt=np.zeros(15, np.int32)))
        with pytest.raises(ValueError, match="allocatable"):
            sch.submit(Request(uid=0, prompt=np.zeros(9, np.int32), max_new_tokens=2))

    def test_scheduler_rejects_unsupported_families(self):
        """Vision cross-attention has no paged cache: the pool refuses it
        (MLA, which it used to refuse too, now has its latent pool)."""
        vlm_arch = registry.get("llama-3.2-vision-90b")
        vlm_cfg = dataclasses.replace(vlm_arch.smoke_config, remat=False)
        with pytest.raises(ValueError, match="LegacyEngine"):
            Scheduler(
                vlm_arch, vlm_cfg, {}, ServingConfig(batch_size=1, max_seq=16)
            )


# ---------------------------------------------------------------------------
# serve.Engine compatibility wrapper routing
# ---------------------------------------------------------------------------
class TestEngineRouting:
    def test_dense_family_routes_to_paged_scheduler(self):
        cfg = _small_cfg()
        eng = serve.Engine(
            ARCH, cfg, _params(cfg), serve.ServeConfig(batch_size=2, max_seq=32)
        )
        assert isinstance(eng.impl, Scheduler)

    def test_mla_family_falls_back_to_legacy(self):
        """The MLA + MoE family no longer falls back: it routes to the paged
        scheduler over its latent pool (only cross-attention stays legacy)."""
        arch = registry.get("deepseek-v2-lite-16b")
        cfg = dataclasses.replace(arch.smoke_config, remat=False)
        params = init_tree(
            arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype
        )
        eng = serve.Engine(
            arch, cfg, params, serve.ServeConfig(batch_size=1, max_seq=16)
        )
        assert isinstance(eng.impl, Scheduler)
