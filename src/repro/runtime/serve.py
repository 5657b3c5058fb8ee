"""Serving entry point (compatibility wrapper over ``repro.serving``).

``Engine`` keeps the original constructor/run surface but routes the dense
and MoE LM families (GQA, or MLA over a latent pool) onto the paged-KV
continuous-batching scheduler (``repro.serving.Scheduler``, DESIGN.md
§13): block-granular KV memory, chunked prefill interleaved with batched
decode, and per-request sampling streams.  Families the paged path does
not cover (vision cross-attention, SSM/hybrid/audio) fall back to
``LegacyEngine`` —
the original fixed-slot loop, kept verbatim as the baseline the serving
tests and the ``serve_latency`` benchmark compare against.

Both paths share the weight-stationary prepack
(``repro.serving.prepack_serving_params``): with a photonic engine
configured, every policy-routed weight packs ONCE at construction, so
steady-state decode performs zero weight-quantization work — the software
analogue of programming the DPU weight MRR banks once per tile.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from repro.serving.scheduler import (
    Request,
    Scheduler,
    ServingConfig,
    prepack_serving_params,
)

__all__ = ["Request", "ServeConfig", "Engine", "LegacyEngine"]


@dataclasses.dataclass
class ServeConfig:
    batch_size: int = 4
    max_seq: int = 256
    greedy: bool = True
    temperature: float = 1.0
    seed: int = 0


def _paged_block_size(max_seq: int) -> int:
    for b in (16, 8, 4, 2, 1):
        if max_seq % b == 0:
            return b
    raise AssertionError  # unreachable: 1 always divides


class Engine:
    def __init__(
        self,
        arch,
        model_cfg,
        params,
        cfg: ServeConfig,
        *,
        mesh=None,
        tp_axis: str = "model",
    ):
        self.arch = arch
        self.model_cfg = model_cfg
        self.cfg = cfg
        paged = (
            getattr(arch, "family", None) in ("dense", "moe")
            and not model_cfg.cross_attn_every
        )
        if paged:
            # Legacy-compatible scheduler setup: a chunk budget of a full
            # wave (batch_size * max_seq tokens) admits and fully prefills
            # every free slot before the step's decode, preserving the old
            # engine's admission order.  Callers that want chunked-prefill
            # interleaving construct repro.serving.Scheduler directly.
            scfg = ServingConfig(
                batch_size=cfg.batch_size,
                max_seq=cfg.max_seq,
                block_size=_paged_block_size(cfg.max_seq),
                chunk_tokens=cfg.batch_size * cfg.max_seq,
                greedy=cfg.greedy,
                temperature=cfg.temperature,
                seed=cfg.seed,
            )
            self.impl = Scheduler(
                arch, model_cfg, params, scfg, mesh=mesh, tp_axis=tp_axis
            )
        else:
            self.impl = LegacyEngine(
                arch, model_cfg, params, cfg, mesh=mesh, tp_axis=tp_axis
            )

    @property
    def photonic(self):
        return self.impl.photonic

    @property
    def params(self):
        return self.impl.params

    @params.setter
    def params(self, value):
        self.impl.params = value

    @property
    def stats(self):
        return self.impl.stats

    def _tp_scope(self):
        return self.impl._tp_scope()

    def run(self, requests: List[Request], max_steps: int = 10_000) -> List[Request]:
        return self.impl.run(requests, max_steps)


class LegacyEngine:
    """The original fixed-slot continuous-batching loop.

    Keeps ``batch_size`` decode slots backed by one dense ``(batch,
    max_seq)`` KV cache.  Free slots fill by prefilling the prompt (one
    prefill per admission, scattered into the batch cache at the slot
    index), then all active slots advance together through ``decode`` steps.
    Known limitations the paged scheduler exists to fix: worst-case cache
    memory per slot, head-of-line blocking on long prompts, batchless cache
    leaves (e.g. the scalar ``pos``) staying live across admissions, and a
    shared sampling stream across slots.
    """

    def __init__(
        self,
        arch,
        model_cfg,
        params,
        cfg: ServeConfig,
        *,
        mesh=None,
        tp_axis: str = "model",
        clock: Callable[[], float] = time.monotonic,
    ):
        self.arch = arch
        self.model_cfg = model_cfg
        # Tensor-parallel photonic serving: with a mesh whose `tp_axis` is
        # sized > 1, the int8 banks prepack in the K-sharded layout
        # (shard-local tile padding, fan-in rows on the TP axis, scales
        # replicated) and every prefill/decode step runs its routed GEMMs
        # inside shard_map with shard-local channel models (DESIGN.md §10).
        self.mesh = mesh
        self.tp_axis = tp_axis
        self._tp_size = (
            int(mesh.shape[tp_axis])
            if mesh is not None and tp_axis in mesh.shape
            else 1
        )
        self._clock = clock
        self.photonic, self.params = prepack_serving_params(
            arch, model_cfg, params, mesh=mesh, tp_axis=tp_axis
        )
        self.cfg = cfg
        self._decode = jax.jit(lambda p, t, c: arch.decode(p, t, c, model_cfg))
        self.slots: List[Optional[Request]] = [None] * cfg.batch_size
        self.cache = None
        self.tokens = jnp.zeros((cfg.batch_size, 1), jnp.int32)
        self._rng = jax.random.PRNGKey(cfg.seed)
        self.stats = {"prefills": 0, "decode_steps": 0, "completed": 0}
        # Batch-axis index per cache leaf, from the cache_def's logical axes
        # (guessing by size collides with e.g. n_layers == batch_size).
        cache_def = arch.cache_def(
            model_cfg, cfg.batch_size, cfg.max_seq,
            {"enc_seq": cfg.max_seq}, model_cfg.compute_dtype,
        )

        def _axis(leaf):
            _, axes, _ = leaf
            return axes.index("batch") if "batch" in axes else None

        self._batch_axis = jax.tree.map(
            _axis, cache_def,
            is_leaf=lambda x: isinstance(x, tuple) and len(x) == 3
            and isinstance(x[0], tuple) and isinstance(x[1], tuple),
        )

    def _tp_scope(self):
        """The tensor-parallel scope every model call runs under (a no-op
        without a TP mesh); consulted at trace time by ``dense``."""
        if self.photonic is not None and self._tp_size > 1:
            from repro.photonic import sharded

            return sharded.tensor_parallel(self.mesh, self.tp_axis)
        return contextlib.nullcontext()

    # -- admission -----------------------------------------------------------
    def _admit(self, req: Request, slot: int):
        """Prefill the prompt for one slot and merge into the batch cache."""
        b = self.cfg.batch_size
        prompt = jnp.asarray(req.prompt)[None, :]  # (1, T)
        batch = {"tokens": jnp.tile(prompt, (b, 1))}
        with self._tp_scope():
            logits, cache = self.arch.prefill(
                self.params, batch, self.model_cfg, self.cfg.max_seq
            )
        self.stats["prefills"] += 1
        if self.cache is None:
            self.cache = cache
        else:
            # scatter this request's row into the live cache at `slot`,
            # along the true batch axis of each leaf
            def merge(live, new, ax):
                if ax is None or live.ndim == 0:
                    return live  # batchless leaves (pos scalar) stay live
                idx = [slice(None)] * live.ndim
                idx[ax] = slice(slot, slot + 1)
                return live.at[tuple(idx)].set(new[tuple(idx)])

            self.cache = jax.tree.map(merge, self.cache, cache, self._batch_axis)
        tok = jnp.argmax(logits[:, -1, : self.model_cfg.vocab_size], axis=-1)
        self.tokens = self.tokens.at[slot, 0].set(tok[slot].astype(jnp.int32))
        req.output.append(int(tok[slot]))
        if req.t_first is None:
            req.t_first = self._clock()
        self.slots[slot] = req

    # -- one engine iteration ------------------------------------------------
    def step(self, queue: List[Request]):
        # fill free slots
        for slot in range(self.cfg.batch_size):
            if self.slots[slot] is None and queue:
                self._admit(queue.pop(0), slot)
        if all(s is None for s in self.slots):
            return
        with self._tp_scope():
            logits, self.cache = self._decode(self.params, self.tokens, self.cache)
        self.stats["decode_steps"] += 1
        logits = logits[:, -1, : self.model_cfg.vocab_size]
        if self.cfg.greedy:
            nxt = jnp.argmax(logits, axis=-1)
        else:
            self._rng, k = jax.random.split(self._rng)
            nxt = jax.random.categorical(k, logits / self.cfg.temperature, axis=-1)
        self.tokens = nxt[:, None].astype(jnp.int32)
        for slot, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(nxt[slot])
            req.output.append(tok)
            if (
                len(req.output) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)
            ):
                req.done = True
                req.t_done = self._clock()
                self.stats["completed"] += 1
                self.slots[slot] = None

    def run(self, requests: List[Request], max_steps: int = 10_000) -> List[Request]:
        queue = list(requests)
        for req in queue:
            if req.t_submit is None:
                req.t_submit = self._clock()
        steps = 0
        while (queue or any(s is not None for s in self.slots)) and steps < max_steps:
            self.step(queue)
            steps += 1
        return requests
