"""The arithmetic behind the per-layer metrics (``perfbench/metrics/``).

Each reader takes the traced window's :class:`perfbench.harness.Context`
and returns a number, or ``None`` where the window holds nothing to read
(no prefill in a decode-only stretch, no kernel event): the harness then
leaves the metric out of the line.
"""

from __future__ import annotations

from perfbench import counts, trace


def _matches(names):
    return lambda event: any(n in event for n in names)


def _op_named(names):
    """An ``XLA Ops`` event of the named operation; not an op that merely
    takes its result (the trace names ops by HLO instruction)."""
    return lambda event: any(event.lstrip("%").startswith(n) for n in names)


def idle_share(ctx):
    """Per cent of the traced window in which no operation ran on the device."""
    window = ctx.hi - ctx.lo
    if window <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_ns(ctx.events, ctx.lo, ctx.hi) / window)


def gemm_roofline(ctx):
    """Least time of the window's routed GEMMs (``counts.gemm_bound_s``
    over the real rows of each call, from the architecture module's sites)
    over the kernel's device time, per cent."""
    kernel_ns = trace.time_ns(ctx.events, ctx.lo, ctx.hi, trace.OPS_LINE, _op_named(ctx.kernel))
    if kernel_ns <= 0 or ctx.peaks is None:
        return None
    bound = 0.0
    for s in ctx.steps:
        for _, tc, _, _ in s.prefill:
            bound += counts.gemm_bound_s(ctx.arch, ctx.sizes, tc, ctx.peaks)
        if s.decode:
            bound += counts.gemm_bound_s(ctx.arch, ctx.sizes, len(s.decode), ctx.peaks)
    return 100.0 * bound / (kernel_ns / 1e9)


def prefill_ms_per_ktok(ctx):
    """Device time of the prefill programs per 1000 prompt tokens, ms."""
    tokens = sum(tc for s in ctx.steps for _, tc, _, _ in s.prefill)
    ns = trace.time_ns(
        ctx.events, ctx.lo, ctx.hi, trace.MODULES_LINE, _matches(ctx.prefill_program)
    )
    if tokens == 0 or ns <= 0:
        return None
    return ns / 1e6 / (tokens / 1000.0)


def decode_step_ms(ctx):
    """Device time of the decode program per decode step, ms."""
    steps = sum(1 for s in ctx.steps if s.decode)
    ns = trace.time_ns(
        ctx.events, ctx.lo, ctx.hi, trace.MODULES_LINE, _matches(ctx.decode_program)
    )
    if steps == 0 or ns <= 0:
        return None
    return ns / 1e6 / steps


def mfu(ctx):
    """Model operations of the window's steps over their wall time at the
    int8 peak, per cent: a lower bound on time for every operation, so the
    share cannot pass 100."""
    ops, wall = 0.0, 0.0
    for s in ctx.steps:
        for t0, tc, _, final in s.prefill:
            ops += counts.prefill_ops(ctx.arch, ctx.sizes, t0, tc, final)
        if s.decode:
            ops += counts.decode_ops(ctx.arch, ctx.sizes, s.decode)
        wall += s.t1 - s.t0
    if wall <= 0 or ops <= 0 or ctx.peaks is None:
        return None
    return 100.0 * ops / (wall * ctx.peaks["int8_ops"])
