"""One run of one cell: set-up, a measured window, the check, one result line.

``python perfbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` from the root of a checkout.  The run

1. finds the cell's configuration and mix by name (``perfbench/spec.py``)
   and stops, printing no result, unless JAX sees a TPU with as many chips
   as the cell asks for;
2. makes the weights on the device from the seed, builds the program's
   ``Scheduler`` (which prepacks every routed weight) and warms every
   program the mix can reach: each prefill (chunk, prompt, final) triple,
   the decode step, the KV-block zeroing of each block count, and the
   small host-side ops around them.  All of that is ``setup_s``;
3. drives ``Scheduler.submit`` / ``step`` for ``--seconds``: a closed loop
   keeps the queue full and reports ``tokens_per_s``; an open loop submits
   Poisson arrivals at the mix's fixed rate, drains the window's requests
   afterwards, and reports ``ttft_p95_ms`` and ``tpot_p95_ms``.  With
   ``--trace 1`` a stretch in the middle of the window is traced and the
   cell's per-layer metrics are read from it instead;
4. frees the program's state and runs the float32 reference of the
   configuration's architecture module (the file its ``"reference"`` key
   names) over a seeded sample of the finished requests, the longest among
   them: ``correct`` holds when no served token lies further below the
   reference's best logit than the configuration's limit.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from types import ModuleType
from typing import Dict, List, Optional

import numpy as np

from perfbench import spec, traffic


class CompileLog:
    """Counts backend compiles, their seconds and persistent-cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits


@dataclasses.dataclass
class Step:
    t0: float
    t1: float = 0.0
    prefill: List[tuple] = dataclasses.field(default_factory=list)  # (t0, tc, t_full, final)
    decode: Optional[List[int]] = None  # contexts of the live rows


class Instrument:
    """Host spans and counts around the program's calls.

    Wraps the scheduler's jitted prefill and decode, its next-token pick
    and its per-token bookkeeping (instance attributes, so the program's
    code is unchanged).  Spans go to the profiler's trace when one is
    running and to ``steps``, which the metrics read."""

    def __init__(self, sched):
        self.sched = sched
        self.steps: List[Step] = []
        self.prefill_tokens = 0
        self.emitted = 0
        self.tracing = False
        self.cur: Optional[Step] = None
        prefill, decode, pick, emit = sched._prefill, sched._decode, sched._pick, sched._emit

        def prefill_call(p, toks, pool, row, t0, t_full, final):
            s = sched.slots[sched._prefill_fifo[0]]
            tc = int(toks.shape[1])
            if self.cur is not None:
                self.cur.prefill.append((s.prefill_done, tc, t_full, final))
            self.prefill_tokens += tc
            with self.span("pb.prefill"):
                return prefill(p, toks, pool, row, t0, t_full, final)

        def decode_call(p, tok, pool, table, pos, active):
            if self.cur is not None:
                self.cur.decode = [
                    int(sched._pos[i]) + 1
                    for i, s in enumerate(sched.slots)
                    if s is not None and s.decoding
                ]
            with self.span("pb.decode"):
                return decode(p, tok, pool, table, pos, active)

        def pick_call(rows, uids, ns):
            with self.span("pb.pick"):
                return pick(rows, uids, ns)

        def emit_call(slot, tok, logits_row=None):
            self.emitted += 1
            with self.span("pb.emit"):
                return emit(slot, tok, logits_row=logits_row)

        sched._prefill, sched._decode = prefill_call, decode_call
        sched._pick, sched._emit = pick_call, emit_call

    def span(self, name: str):
        import contextlib

        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def step(self) -> None:
        self.cur = Step(time.perf_counter())
        with self.span("pb.step"):
            self.sched.step()
        self.cur.t1 = time.perf_counter()
        self.steps.append(self.cur)
        self.cur = None


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def warm_up(sched, mix: dict, pool_sizes) -> None:
    """Run every program the mix can reach once, on scratch blocks.

    Prefill compiles one program per (chunk, prompt length, final); decode
    one; ``zero_blocks`` one per block count; the eager slices the
    scheduler takes of logits one per shape and index.  Each is called
    here exactly as the scheduler calls it."""
    import jax
    import jax.numpy as jnp

    from repro.serving import kv_cache as kvc

    cfg, scfg = sched.model_cfg, sched.cfg
    vocab, bs = cfg.vocab_size, scfg.block_size
    width = scfg.max_seq // bs
    shapes = traffic.prefill_shapes(mix["prompt"]["buckets"], scfg.chunk_tokens)
    for tc, t_full, final in sorted(shapes):
        blocks = sched.allocator.alloc(-(-t_full // bs))
        row = np.full((width,), kvc.NULL_BLOCK, np.int32)
        row[: len(blocks)] = blocks
        t0 = t_full - tc if final else 0
        with sched._tp_scope():
            logits, sched.kv_pool = sched._prefill(
                sched.params, jnp.zeros((1, tc), jnp.int32), sched.kv_pool,
                jnp.asarray(row), jnp.int32(t0), t_full, final,
            )
        if final:
            r = logits[:, -1, :vocab]
            np.asarray(sched._pick(r, [0], [0]))
            r[0]
        sched.allocator.free(blocks)

    b = scfg.batch_size
    with sched._tp_scope():
        logits, sched.kv_pool = sched._decode(
            sched.params, jnp.asarray(sched._tokens), sched.kv_pool,
            jnp.asarray(sched._table), jnp.asarray(sched._pos),
            jnp.asarray(np.zeros((b,), bool)),
        )
    rows = logits[:, -1, :vocab]
    np.asarray(sched._pick(rows, [0] * b, [0] * b))
    for i in range(b):
        rows[i]

    for n in sorted(traffic.block_counts(mix, pool_sizes)):
        blocks = sched.allocator.alloc(n)
        sched.kv_pool = kvc.zero_blocks(sched.kv_pool, blocks)
        sched.allocator.free(blocks)
    jax.block_until_ready(sched.kv_pool)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------
class Tracer:
    """Profiles ``[start, start + length]`` of the window, at step bounds."""

    def __init__(self, inst: Instrument, t0: float, start: float, length: float):
        self.inst, self.begin, self.end = inst, t0 + start, t0 + start + length
        self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")
        self.state = "before"
        self.annotation = None
        self.lo = self.hi = 0.0

    def poll(self, now: float) -> None:
        import jax

        if self.state == "before" and now >= self.begin:
            jax.block_until_ready(self.inst.sched.kv_pool)
            jax.profiler.start_trace(self.dir)
            self.inst.tracing = True
            self.annotation = jax.profiler.TraceAnnotation("pb.traced")
            self.annotation.__enter__()
            self.lo = time.perf_counter()
            self.state = "on"
        elif self.state == "on" and now >= self.end:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state != "on":
            return
        jax.block_until_ready(self.inst.sched.kv_pool)
        self.hi = time.perf_counter()
        self.annotation.__exit__(None, None, None)
        self.inst.tracing = False
        jax.profiler.stop_trace()
        self.state = "done"


def _request(spec_: traffic.RequestSpec):
    from repro.serving import Request

    return Request(uid=spec_.index, prompt=spec_.prompt, max_new_tokens=spec_.max_new_tokens)


def closed_window(sched, inst, mix, seed, seconds, tracer, served_target=0) -> dict:
    import jax

    gen = traffic.closed_loop(mix, seed, sched.model_cfg.vocab_size)
    requests = []
    t0 = time.perf_counter()
    t_end = t0 + seconds
    inst.prefill_tokens = inst.emitted = 0
    while True:
        with inst.span("pb.submit"):
            while len(sched.queue) < mix["queue_min"]:
                r = _request(next(gen))
                sched.submit(r)
                requests.append(r)
        inst.step()
        now = time.perf_counter()
        if tracer is not None:
            tracer.poll(now)
        if now >= t_end:
            break
    if tracer is not None:
        tracer.stop()
    jax.block_until_ready(sched.kv_pool)
    window_s = time.perf_counter() - t0
    tokens = inst.prefill_tokens + inst.emitted
    in_window = sum(r.done for r in requests)
    # Unmeasured: serve on, with no new requests, until the finished ones
    # hold ``served_target`` served tokens for the check.
    cutoff = time.perf_counter() + mix["drain_limit_s"]
    while (
        sched.pending
        and sum(len(r.output) for r in requests if r.done) < served_target
        and time.perf_counter() < cutoff
    ):
        inst.step()
    done = [r for r in requests if r.done]
    return {
        "window_s": window_s,
        "requests": done,
        "attempted": in_window,
        "failed": 0,
        "metrics": {"tokens_per_s": tokens / window_s},
        "notes": {
            "prefill_tokens": inst.prefill_tokens,
            "output_tokens": tokens - inst.prefill_tokens,
            "completed": in_window,
            "completed_after_drain": len(done),
            "steps": len(inst.steps),
        },
    }


def _p95(values: List[float]) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]


def open_window(sched, inst, mix, seed, seconds, tracer, served_target=0) -> dict:
    """``served_target`` is not needed: every request of the window is
    served before the window's results are read, up to the drain limit."""
    import jax

    specs = traffic.open_loop(mix, seed, sched.model_cfg.vocab_size, seconds, mix["rate_per_s"])
    requests, late = [], []
    t0 = time.perf_counter()
    cutoff = t0 + seconds + mix["drain_limit_s"]
    i = 0
    while True:
        now = time.perf_counter()
        with inst.span("pb.submit"):
            while i < len(specs) and t0 + specs[i].arrival_s <= now:
                s = specs[i]
                r = _request(s)
                sched.submit(r, t_submit=t0 + s.arrival_s)
                late.append(now - (t0 + s.arrival_s))
                requests.append(r)
                i += 1
        if tracer is not None:
            tracer.poll(now)
        if sched.pending:
            inst.step()
        elif i < len(specs):
            with inst.span("pb.wait"):
                time.sleep(max(0.0, t0 + specs[i].arrival_s - time.perf_counter()))
        else:
            break
        if time.perf_counter() >= cutoff:
            break
    if tracer is not None:
        tracer.stop()
    jax.block_until_ready(sched.kv_pool)
    t_stop = time.perf_counter()
    # A request still unserved at the cutoff counts with the wait it had.
    ttft = [(r.t_first if r.t_first is not None else t_stop) - r.t_submit for r in requests]
    done = [r for r in requests if r.done]
    tpot = [
        (r.t_done - r.t_first) / (len(r.output) - 1) for r in done if len(r.output) > 1
    ]
    unserved = len(specs) - len(done)
    return {
        "window_s": seconds,
        "requests": done,
        "attempted": len(specs),
        "failed": unserved,
        "metrics": {
            "ttft_p95_ms": 1e3 * _p95(ttft),
            "tpot_p95_ms": 1e3 * _p95(tpot) if tpot else None,
        },
        "notes": {
            "requests": len(specs),
            "completed": len(done),
            "generator_late_p95_ms": 1e3 * _p95(late),
            "generator_late_max_ms": 1e3 * max(late),
            "drain_s": t_stop - (t0 + seconds),
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            # A backlog that grows over the window shows as a later half
            # that waits longer than the earlier one.
            "ttft_p50_first_half_ms": 1e3 * float(np.median(ttft[: len(ttft) // 2 or 1])),
            "ttft_p50_second_half_ms": 1e3 * float(np.median(ttft[len(ttft) // 2 :])),
            "tpot_p50_ms": 1e3 * float(np.median(tpot)) if tpot else None,
            "steps": len(inst.steps),
        },
    }


# ---------------------------------------------------------------------------
# per-layer metrics from the trace
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Context:
    """What a metric reader (``perfbench/metrics/<name>.py``) reads."""

    events: list  # perfbench.trace events of the traced window
    lo: int  # traced window, trace clock (ns)
    hi: int
    steps: List[Step]  # steps wholly inside the traced window
    sizes: dict
    arch: ModuleType  # the configuration's architecture module: its work counts
    peaks: Dict[str, float]
    kernel: List[str]  # names under which the device shows the GEMM kernel
    prefill_program: List[str]
    decode_program: List[str]


def traced_context(cell, inst, tracer, peaks) -> Context:
    from perfbench import trace

    events = trace.load(tracer.dir)
    lo, hi = trace.window(events)
    steps = [s for s in inst.steps if s.t0 >= tracer.lo and s.t1 <= tracer.hi]
    names = cell.config["device_names"]
    return Context(
        events=events, lo=lo, hi=hi, steps=steps, sizes=cell.config["sizes"], arch=cell.arch,
        peaks=peaks, kernel=names["kernel"], prefill_program=names["prefill_program"],
        decode_program=names["decode_program"],
    )


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def check_sample(requests, seed: int, served_target: int, max_requests: int):
    """A seeded sample of finished requests with the longest in it, up to
    ``served_target`` served tokens."""
    if not requests:
        return []
    order = sorted(requests, key=lambda r: (-(len(r.prompt) + len(r.output)), r.uid))
    sample, rest = [order[0]], order[1:]
    picks = traffic.rng(seed, 7).permutation(len(rest))
    served = len(order[0].output)
    for j in picks:
        if served >= served_target or len(sample) >= max_requests:
            break
        sample.append(rest[j])
        served += len(rest[j].output)
    return [(np.asarray(r.prompt, np.int32), list(r.output), r.max_new_tokens) for r in sample]


def reference_check(cell, seed: int, sample) -> Dict[str, float]:
    """Widest gap by which a served token's reference logit lies below
    the reference's best, over the sample; and the served-token count."""
    import jax

    from perfbench import weights

    sizes = cell.config["sizes"]
    w = weights.published_weights(cell.arch, sizes, seed)
    gaps, short = [], 0
    for prompt, served, want in sample:
        short += len(served) != want
        gaps.append(cell.arch.served_gaps(w, prompt, served, sizes))
    del w
    jax.clear_caches()
    if not gaps:
        return {"widest_gap": None, "served": 0, "short": short}
    g = np.concatenate(gaps)
    return {"widest_gap": float(g.max()), "served": int(g.size), "short": short}


# ---------------------------------------------------------------------------
def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None, *, t_start: float, require_chip: bool = True, fault=None, root=spec.ROOT) -> int:
    """Run one cell and print its result line; returns the exit code.

    ``require_chip=False``, ``fault`` and ``root`` are for the harness's
    own tests: they skip the look for a chip, break the timed path
    underneath, and read the cells of another tree."""
    args = parse(argv)
    try:
        cell = spec.find_cell(args.workload, root)
    except (KeyError, FileNotFoundError) as e:
        return fail(str(e))

    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            return fail(f"needs a TPU; JAX's backend is {devices[0].platform!r}")
        if len(devices) < cell.chips:
            return fail(f"{cell.name} needs {cell.chips} chips; JAX sees {len(devices)}")
    try:
        peaks = spec.peaks_for(devices[0].device_kind)
    except KeyError as e:
        if require_chip:
            return fail(str(e))
        peaks = None

    out = run_cell(cell, args, devices, peaks, t_start=t_start, fault=fault)
    print(json.dumps(out), flush=True)
    return 0


def pool_sizes(mix: dict, seconds: float):
    """The set of request sizes a run of ``mix`` draws from."""
    if mix["loop"] == "closed":
        return traffic.sizes(mix, mix["pool_requests"])
    return traffic.sizes(mix, max(1, int(round(mix["rate_per_s"] * seconds))))


def build_scheduler(cell, seed: int, sizes_pool):
    """Weights from the seed, the program's ``Scheduler`` over them, and
    every program the mix can reach warmed up."""
    from perfbench import model, weights
    from repro.serving import Scheduler, ServingConfig

    program_arch, cfg = model.build(cell.config, seed, cell.arch)
    params = weights.program_weights(cell.arch, cell.config["sizes"], seed)
    model.check_layout(program_arch, cfg, params)
    scfg = ServingConfig(**cell.mix["serving"], greedy=True, record_logits=False)
    sched = Scheduler(program_arch, cfg, params, scfg, clock=time.perf_counter)
    del params
    warm_up(sched, cell.mix, sizes_pool)
    return sched


def run_cell(cell, args, devices, peaks, *, t_start: float, fault=None) -> dict:
    from repro.launch.profile import enable_compilation_cache

    enable_compilation_cache()
    log = CompileLog()
    mix, seed = cell.mix, args.seed
    sched = build_scheduler(cell, seed, pool_sizes(mix, args.seconds))
    if fault is not None:
        fault(sched)
    inst = Instrument(sched)
    c0, s0, h0 = log.snapshot()
    setup_s = time.perf_counter() - t_start

    tracer = None
    if args.trace:
        length = min(mix["trace_seconds"], 0.5 * args.seconds)
        tracer = Tracer(inst, time.perf_counter(), 0.3 * args.seconds, length)
    window = closed_window if mix["loop"] == "closed" else open_window
    check = cell.config["check"]
    res = window(sched, inst, mix, seed, args.seconds, tracer, check["served_tokens"])
    c1, s1, h1 = log.snapshot()
    stats = devices[0].memory_stats() or {}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0)),
    }
    notes = dict(res["notes"])
    notes.update(
        setup_compiles=c0, setup_compile_s=s0, setup_cache_hits=h0,
        window_compiles=c1 - c0, window_compile_s=s1 - s0,
        window_s=res["window_s"],
    )

    metrics = {}
    breakdown = None
    if args.trace:
        from perfbench import trace

        ctx = traced_context(cell, inst, tracer, peaks)
        for m in cell.per_layer:
            v = spec.metric_reader(m.name)(ctx)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
        device["busy_s"] = trace.busy_ns(ctx.events, ctx.lo, ctx.hi) / 1e9
        device["window_s"] = (ctx.hi - ctx.lo) / 1e9
        breakdown = {
            "device_ops": trace.top_ops(ctx.events, ctx.lo, ctx.hi),
            "idle_gaps": trace.idle_gaps(ctx.events, ctx.lo, ctx.hi),
        }
        notes["traced_steps"] = len(ctx.steps)
        shutil.rmtree(tracer.dir, ignore_errors=True)
    else:
        for m in cell.end_to_end:
            if m.name == "setup_s":
                metrics[m.name] = {"value": setup_s, "unit": m.unit}
            elif res["metrics"].get(m.name) is not None:
                metrics[m.name] = {"value": res["metrics"][m.name], "unit": m.unit}
    print("window: " + json.dumps(notes), file=sys.stderr, flush=True)

    sample = check_sample(res["requests"], seed, check["served_tokens"], check["max_requests"])
    attempted, failed = res["attempted"], res["failed"]
    del sched, inst, res
    gc.collect()
    got = reference_check(cell, seed, sample)
    limit = check["widest_gap_limit"]
    correct = bool(
        got["served"] >= 1 and got["short"] == 0 and got["widest_gap"] <= limit
        and notes["window_compiles"] == 0
    )  # nothing served to compare is not correct
    checks = {
        "widest_gap": {"value": got["widest_gap"], "limit": limit},
        "short_requests": {"value": got["short"], "limit": 0},
        "window_compiles": {"value": notes["window_compiles"], "limit": 0},
    }
    print(f"checked {len(sample)} requests, {got['served']} served tokens", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} limit {v['limit']!r}", file=sys.stderr, flush=True)
    out = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out

