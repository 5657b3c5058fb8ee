"""Token-budgeted continuous-batching scheduler over the paged KV pool.

Supersedes the fixed-slot loop in ``repro.runtime.serve``: requests admit
into ``batch_size`` decode slots backed by the block pool
(``repro.serving.kv_cache``), prompts prefill in chunks of at most
``chunk_tokens`` tokens per engine step, and every step interleaves that
prefill budget with one batched decode over all live slots — a long prompt
can no longer head-of-line-block the tokens streaming out of the decode
batch, and admission reserves request-sized block counts instead of a
worst-case ``max_seq`` row per slot.

Correctness contracts (tested in ``tests/test_serving.py``):

* **bitwise vs one-shot** — on the float path, chunked prefill +
  interleaved paged decode reproduce the legacy one-shot engine's logits
  bit-for-bit per request (the chunk attention feeds exactly the one-shot
  KV block partition); under a photonic engine the same holds whenever a
  wave admits in lockstep with single-chunk prefills (per-tensor activation
  scales are the one chunk-extensive quantity);
* **weight-stationary** — decode steps over the prepacked params trace
  with zero weight-sized round ops (``ContractChecker``, PR-3 invariant);
* **per-request sampling streams** — the sampling key folds in the request
  ``uid`` and its token index, never the slot id, so a recycled slot cannot
  replay (or be influenced by) a previous occupant's sample stream;
* **no stale KV** — blocks zero at (re)allocation; see ``kv_cache``.

Every step records host spans with their counts (``repro.serving.spans``):
on the profiler's clock when one runs, and in ``spans.LOG`` always; the
counts also add up in ``Scheduler.stats``.  A MoE program's counts
(``expert_rows``: token-expert pairs routed to held experts, summed over
the MoE layers; ``expert_rows_computed``: rows its grouped GEMMs computed,
padding included) come back from the device beside its logits and join
their ``serve.prefill`` / ``serve.decode`` span at the step's next
transfer.  Each ``serve.wait`` counts the ``logits_rows`` it brings to the
host for ``ServingConfig.record_logits`` (none when that is off); they come
in the pick's own transfer, so the emit loop indexes no device array.

Tensor parallel: pass the PR-4 ``mesh``/``tp_axis`` and every model call
runs under ``repro.photonic.sharded.tensor_parallel`` with shard-local
prepacked banks, exactly like the legacy engine.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving import kv_cache as kvc
from repro.serving import spans


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (T,) int32
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    # filled by the engine
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # latency bookkeeping, in units of the scheduler's clock
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None  # queue wait: t_admit - t_submit
    t_first: Optional[float] = None
    t_done: Optional[float] = None
    # per-emitted-token logits rows, only with ServingConfig.record_logits
    logits: List[np.ndarray] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class ServingConfig:
    batch_size: int = 4
    max_seq: int = 256
    block_size: int = 16
    num_blocks: Optional[int] = None  # None: worst case (null + trash + B*max_seq)
    chunk_tokens: int = 64  # prefill token budget per engine step
    greedy: bool = True
    temperature: float = 1.0
    seed: int = 0
    record_logits: bool = False


def prepack_serving_params(arch, model_cfg, params, *, mesh=None, tp_axis="model"):
    """Weight-stationary prepack (DESIGN.md §9) shared by the paged
    scheduler and the legacy engine: with a photonic engine configured,
    quantize + pack every routed weight ONCE, so serving steps stream
    activations against packed int8 banks and never re-quantize.  Returns
    ``(engine_or_None, params)``."""
    from repro.models.common import engine_from_model_config
    from repro.photonic.packing import prepack_params

    engine = engine_from_model_config(model_cfg)
    if engine is None:
        return None, params
    pack_engine = engine
    if model_cfg.mla:
        # Absorbed MLA decode (the paged path's, and the legacy one under
        # ``mla_absorb``) consumes wuk/wuv as raw floats in its einsums;
        # keep them float.  Prefill quantizes them per call instead.
        pol = dataclasses.replace(
            pack_engine.policy, exclude=pack_engine.policy.exclude + ("wuk", "wuv")
        )
        pack_engine = dataclasses.replace(pack_engine, policy=pol)
    tp_size = (
        int(mesh.shape[tp_axis]) if mesh is not None and tp_axis in mesh.shape else 1
    )
    params = prepack_params(
        params,
        arch.param_defs(model_cfg),
        pack_engine,
        mesh=mesh if tp_size > 1 else None,
        axis=tp_axis,
    )
    return engine, params


class _Slot:
    """Host-side per-slot state; the device sees only (table, pos, active)."""

    def __init__(self, req: Request, blocks: List[int]):
        self.req = req
        self.blocks = blocks
        self.prefill_done = 0
        self.decoding = False


class Scheduler:
    def __init__(
        self,
        arch,
        model_cfg,
        params,
        cfg: ServingConfig,
        *,
        mesh=None,
        tp_axis: str = "model",
        clock: Callable[[], float] = time.monotonic,
    ):
        from repro.models import lm

        if cfg.max_seq % cfg.block_size:
            raise ValueError(
                f"block_size={cfg.block_size} must divide max_seq={cfg.max_seq}"
            )
        b = cfg.batch_size
        self.arch = arch
        self.model_cfg = model_cfg
        self.cfg = cfg
        self.mesh = mesh
        self.tp_axis = tp_axis
        self._tp_size = (
            int(mesh.shape[tp_axis])
            if mesh is not None and tp_axis in mesh.shape
            else 1
        )
        self._clock = clock
        self._spans = spans.Recorder(clock)

        self.photonic, self.params = prepack_serving_params(
            arch, model_cfg, params, mesh=mesh, tp_axis=tp_axis
        )

        table_width = cfg.max_seq // cfg.block_size
        reserved = 1 + b  # null block + one trash block per slot
        num_blocks = cfg.num_blocks
        if num_blocks is None:
            num_blocks = reserved + b * table_width
        self.num_blocks = num_blocks
        self.allocator = kvc.BlockAllocator(
            num_blocks, cfg.block_size, reserved=reserved
        )
        self.kv_pool = kvc.init_pool(
            lm.paged_pool_def(
                model_cfg, num_blocks, cfg.block_size, model_cfg.compute_dtype
            )
        )
        self._trash = jnp.arange(1, b + 1, dtype=jnp.int32)

        self._table = np.full((b, table_width), kvc.NULL_BLOCK, np.int32)
        self._pos = np.zeros((b,), np.int32)
        self._tokens = np.zeros((b, 1), np.int32)
        self.slots: List[Optional[_Slot]] = [None] * b
        self._prefill_fifo: List[int] = []  # slot ids, admission order
        self.queue: collections.deque = collections.deque()
        # Cumulative counts of the spans (``prefills``: requests admitted).
        self.stats = {
            "prefills": 0, "blocks_zeroed": 0, "prefill_chunks": 0,
            "prefill_tokens": 0, "decode_steps": 0, "decode_rows": 0,
            "kv_rows_attended": 0, "kv_rows_gathered": 0, "completed": 0,
            "expert_rows": 0, "expert_rows_computed": 0, "logits_rows_fetched": 0,
        }
        # (span, device counts) of MoE programs not yet read on the host.
        self._unread: List[tuple] = []

        self._base_key = jax.random.PRNGKey(cfg.seed)
        self._gather_len = cfg.max_seq  # KV rows each decode row gathers

        def decode_fn(p, tok, pool, table, pos, active):
            return lm.lm_decode_paged(
                p, tok, pool, table, pos, active, self._trash, model_cfg,
                gather_len=self._gather_len, block_size=cfg.block_size,
            )

        def prefill_fn(p, toks, pool, table_row, t0, t_full, with_logits):
            return lm.lm_prefill_chunk(
                p, toks, pool, table_row, t0, model_cfg,
                t_full=t_full, block_size=cfg.block_size, with_logits=with_logits,
            )

        self._decode_fn = decode_fn
        # The latent pool rides the MLA programs' scan carry and is written
        # in place: donated, the pool is never held twice.
        donate = (2,) if model_cfg.mla else ()
        self._decode_program = jax.jit(decode_fn, donate_argnums=donate)
        self._prefill_program = jax.jit(
            prefill_fn, static_argnums=(5, 6), donate_argnums=donate
        )

        # Each program returns (logits, pool, counts); callers get (logits,
        # pool), and a MoE program's counts wait, still on the device, for
        # the next transfer the step makes (``_fetch``).
        def prefill(*args):
            logits, pool, counts = self._prefill_program(*args)
            self._hold(counts)
            return logits, pool

        def decode(*args):
            logits, pool, counts = self._decode_program(*args)
            self._hold(counts)
            return logits, pool

        self._prefill, self._decode = prefill, decode
        self._argmax = jax.jit(lambda rows: jnp.argmax(rows, axis=-1))

        def sample_fn(rows, uids, ns):
            def key(u, n):
                return jax.random.fold_in(jax.random.fold_in(self._base_key, u), n)

            keys = jax.vmap(key)(uids, ns)
            draw = lambda k, row: jax.random.categorical(k, row / cfg.temperature)
            return jax.vmap(draw)(keys, rows)

        self._sample = jax.jit(sample_fn)

    # -- helpers -------------------------------------------------------------
    def _tp_scope(self):
        """The tensor-parallel scope every model call runs under (a no-op
        without a TP mesh); consulted at trace time by ``dense``."""
        if self.photonic is not None and self._tp_size > 1:
            from repro.photonic import sharded

            return sharded.tensor_parallel(self.mesh, self.tp_axis)
        return contextlib.nullcontext()

    def _pick(self, rows: jax.Array, uids, ns) -> jax.Array:
        """Next-token choice per row.  The sampling key is derived from
        (seed, request uid, token index) — never the slot — so a request's
        stream is reproducible and slot recycling cannot replay streams."""
        if self.cfg.greedy:
            return self._argmax(rows)
        return self._sample(
            rows,
            jnp.asarray(np.asarray(uids, np.int32)),
            jnp.asarray(np.asarray(ns, np.int32)),
        )

    def _hold(self, counts) -> None:
        """Keep a MoE program's (expert rows, expert rows computed) with
        the span it ran in (none in a warm-up outside any span)."""
        span = self._spans.current()
        if counts is not None and span is not None:
            self._unread.append((span, counts))

    def _fetch(self, picks, rows=None):
        """``(picks, rows)`` on the host, with the held counts in the same
        transfer: the step waits for its last program here anyway, so the
        counts and the logits ``rows`` to record (``None``: none) cost no
        sync of their own."""
        picks, rows, counts = jax.device_get(
            (picks, rows, [c for _, c in self._unread])
        )
        for (span, _), (n, computed) in zip(self._unread, counts):
            span.counts.update(expert_rows=int(n), expert_rows_computed=int(computed))
            self.stats["expert_rows"] += int(n)
            self.stats["expert_rows_computed"] += int(computed)
        self._unread.clear()
        return np.asarray(picks), rows

    def _emit(self, slot: int, tok: int, logits_row=None) -> None:
        """Append ``tok`` to the slot's request and retire it when done.
        ``logits_row`` is the host row recorded with ``record_logits``."""
        s = self.slots[slot]
        req = s.req
        req.output.append(tok)
        if req.t_first is None:
            req.t_first = self._clock()
        if self.cfg.record_logits and logits_row is not None:
            # A copy: the row is a view of the whole fetched batch.
            req.logits.append(np.array(logits_row))
        done = len(req.output) >= req.max_new_tokens or (
            req.eos_id is not None and tok == req.eos_id
        )
        if done:
            self.allocator.free(s.blocks)
            self._table[slot, :] = kvc.NULL_BLOCK
            # An idle row still rides the batched decode (and its
            # activations feed the per-tensor quantization scale), so it
            # must not carry the previous occupant's token or position.
            self._tokens[slot, 0] = 0
            self._pos[slot] = 0
            self.slots[slot] = None
            if slot in self._prefill_fifo:
                self._prefill_fifo.remove(slot)
            req.done = True
            req.t_done = self._clock()
            self.stats["completed"] += 1

    # -- request lifecycle ---------------------------------------------------
    def submit(self, req: Request, *, t_submit: Optional[float] = None) -> None:
        total = len(req.prompt) + req.max_new_tokens
        if len(req.prompt) < 1:
            raise ValueError("empty prompt")
        if total > self.cfg.max_seq:
            raise ValueError(
                f"prompt + max_new_tokens = {total} exceeds max_seq="
                f"{self.cfg.max_seq}"
            )
        cap = self.allocator.num_blocks - self.allocator.reserved
        if self.allocator.blocks_needed(total) > cap:
            raise ValueError(
                f"request needs {self.allocator.blocks_needed(total)} blocks "
                f"but the pool only has {cap} allocatable"
            )
        req.t_submit = self._clock() if t_submit is None else t_submit
        self.queue.append(req)

    def _admit(self) -> None:
        """Fill free slots FCFS.  All-or-nothing block reservation for
        prompt + max_new_tokens: if the pool cannot cover the queue head's
        worst case, admission waits (no preemption path exists)."""
        admitted = zeroed = 0
        with self._spans.span("serve.admit") as add:
            for slot in range(self.cfg.batch_size):
                if not self.queue:
                    break
                if self.slots[slot] is not None:
                    continue
                req = self.queue[0]
                total = len(req.prompt) + req.max_new_tokens
                blocks = self.allocator.alloc(self.allocator.blocks_needed(total))
                if blocks is None:
                    break
                self.queue.popleft()
                req.t_admit = self._clock()
                # Stale-KV admission contract: recycled blocks zero here,
                # before any table entry can reach them.
                self.kv_pool = kvc.zero_blocks(self.kv_pool, blocks)
                self._table[slot, :] = kvc.NULL_BLOCK
                self._table[slot, : len(blocks)] = blocks
                self._pos[slot] = 0
                self.slots[slot] = _Slot(req, blocks)
                self._prefill_fifo.append(slot)
                admitted += 1
                zeroed += len(blocks)
            add(admitted=admitted, blocks_zeroed=zeroed)
        self.stats["prefills"] += admitted
        self.stats["blocks_zeroed"] += zeroed

    def _prefill_phase(self) -> None:
        budget = self.cfg.chunk_tokens
        while budget > 0 and self._prefill_fifo:
            slot = self._prefill_fifo[0]
            s = self.slots[slot]
            prompt = np.asarray(s.req.prompt, np.int32)
            t_full = len(prompt)
            tc = min(budget, t_full - s.prefill_done)
            toks = jnp.asarray(prompt[s.prefill_done : s.prefill_done + tc][None, :])
            final = s.prefill_done + tc == t_full
            uid = s.req.uid
            with self._spans.span(
                "serve.prefill", uid=uid, t0=s.prefill_done, tc=tc, t_full=t_full,
                final=int(final),
            ), self._tp_scope():
                logits, self.kv_pool = self._prefill(
                    self.params, toks, self.kv_pool,
                    jnp.asarray(self._table[slot]),
                    jnp.int32(s.prefill_done), t_full, final,
                )
            budget -= tc
            s.prefill_done += tc
            self.stats["prefill_chunks"] += 1
            self.stats["prefill_tokens"] += tc
            if final:
                self._prefill_fifo.pop(0)
                row = logits[:, -1, : self.model_cfg.vocab_size]
                record = self.cfg.record_logits
                with self._spans.span("serve.wait", uid=uid, logits_rows=int(record)):
                    picks, host_rows = self._fetch(
                        self._pick(row, [uid], [len(s.req.output)]),
                        row if record else None,
                    )
                tok = int(picks[0])
                self.stats["logits_rows_fetched"] += int(record)
                with self._spans.span("serve.emit", uid=uid, tokens=1):
                    self._pos[slot] = t_full
                    self._tokens[slot, 0] = tok
                    s.decoding = True
                    self._emit(slot, tok, logits_row=host_rows[0] if record else None)

    def _decode_phase(self) -> None:
        decoding = [
            i for i, s in enumerate(self.slots) if s is not None and s.decoding
        ]
        if not decoding:
            return
        b = self.cfg.batch_size
        active = np.zeros((b,), bool)
        active[decoding] = True
        # A live row attends its pos + 1 cached rows (``kv_pos <= pos``) of
        # the ``gather_len`` rows the program gathers for every slot.
        counts = dict(
            rows=len(decoding),
            kv_rows_attended=int(self._pos[decoding].sum()) + len(decoding),
            kv_rows_gathered=b * self._gather_len,
        )
        with self._spans.span("serve.decode", **counts), self._tp_scope():
            logits, self.kv_pool = self._decode(
                self.params, jnp.asarray(self._tokens), self.kv_pool,
                jnp.asarray(self._table), jnp.asarray(self._pos),
                jnp.asarray(active),
            )
        self.stats["decode_steps"] += 1
        self.stats["decode_rows"] += counts["rows"]
        self.stats["kv_rows_attended"] += counts["kv_rows_attended"]
        self.stats["kv_rows_gathered"] += counts["kv_rows_gathered"]
        rows = logits[:, -1, : self.model_cfg.vocab_size]
        uids = [self.slots[i].req.uid if active[i] else 0 for i in range(b)]
        ns = [len(self.slots[i].req.output) if active[i] else 0 for i in range(b)]
        record = self.cfg.record_logits
        kept = len(decoding) if record else 0
        with self._spans.span("serve.wait", logits_rows=kept):
            toks, host_rows = self._fetch(
                self._pick(rows, uids, ns), rows if record else None
            )
        self.stats["logits_rows_fetched"] += kept
        with self._spans.span("serve.emit", tokens=len(decoding)):
            for i in decoding:
                tok = int(toks[i])
                self._pos[i] += 1
                self._tokens[i, 0] = tok
                self._emit(i, tok, logits_row=host_rows[i] if record else None)

    # -- one engine iteration ------------------------------------------------
    def step(self) -> None:
        with self._spans.span("serve.step"):
            self._admit()
            self._prefill_phase()
            self._decode_phase()

    @property
    def pending(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    def run(
        self, requests: Optional[List[Request]] = None, max_steps: int = 100_000
    ) -> Optional[List[Request]]:
        if requests:
            for r in requests:
                self.submit(r)
        steps = 0
        while self.pending and steps < max_steps:
            self.step()
            steps += 1
        return requests

    # -- contract access (tests / analysis) ----------------------------------
    def decode_checker(self, label: str = "paged_decode"):
        """ContractChecker over one traced decode step with the live state —
        the PR-3 weight-stationary assertion runs against the exact stepped
        program (``assert_zero_weight_rounds``)."""
        from repro.analysis.contracts import ContractChecker

        b = self.cfg.batch_size
        return ContractChecker.trace(
            self._decode_fn,
            self.params,
            jnp.asarray(self._tokens),
            self.kv_pool,
            jnp.asarray(self._table),
            jnp.asarray(self._pos),
            jnp.ones((b,), bool),
            label=label,
        )
