"""Per-layer metrics read from the program's own span log.

The scheduler records every step's spans (``serve.step`` and its
``serve.admit``, ``serve.prefill``, ``serve.decode``, ``serve.wait``,
``serve.emit`` children, with their counts) in ``repro.serving.spans.LOG``,
timed with its clock: ``time.perf_counter`` under the harness, the clock of
the harness's own steps.  A reader takes the log's steps that fall within
the traced window's steps (``ctx.steps``) and returns a number, or ``None``
where there is nothing to read: a program without the log, or a window
without the spans a metric needs.
"""

from __future__ import annotations

import bisect
from typing import List, Optional, Sequence

DISPATCH = ("serve.prefill", "serve.decode")


def program_log() -> Sequence[list]:
    """The process's span log, or no steps where the program has none."""
    try:
        from repro.serving.spans import LOG
    except ImportError:
        return []
    return LOG


def traced_steps(ctx, log: Sequence[list]) -> List[list]:
    """The log's steps wholly inside the harness's traced steps."""
    if not ctx.steps:
        return []
    lo = min(s.t0 for s in ctx.steps)
    hi = max(s.t1 for s in ctx.steps)
    return [st for st in log if st and st[0].start >= lo and st[0].end <= hi]


def host_idle_ms(ctx, log: Optional[Sequence[list]] = None):
    """Host time per step in which the device has nothing queued, ms: from
    the end of each ``serve.wait`` (the host holds the device's last result)
    to the start of the next ``serve.prefill`` or ``serve.decode``, counted
    inside ``serve.step`` spans only.  Time between steps is the caller's
    (submitting requests, starting or stopping a profiler), not the
    scheduler's."""
    log = program_log() if log is None else log
    steps = traced_steps(ctx, log)
    if not steps:
        return None
    starts = sorted(sp.start for st in log for sp in st if sp.name in DISPATCH)
    bounds = sorted((st[0].start, st[0].end) for st in log if st)
    step_starts = [a for a, _ in bounds]
    idle = 0.0
    for st in steps:
        for sp in st:
            if sp.name != "serve.wait":
                continue
            i = bisect.bisect_left(starts, sp.end)
            if i == len(starts):
                continue
            j = bisect.bisect_right(step_starts, sp.end) - 1
            while j < len(bounds) and bounds[j][0] < starts[i]:
                a, b = bounds[j]
                idle += max(0.0, min(b, starts[i]) - max(a, sp.end))
                j += 1
    return 1e3 * idle / len(steps)


def decode_kv_useful(ctx, log: Optional[Sequence[list]] = None):
    """Per cent of the KV rows the decode program gathers that a live row
    attends: Σ ``kv_rows_attended`` over Σ ``kv_rows_gathered``."""
    log = program_log() if log is None else log
    attended = gathered = 0
    for st in traced_steps(ctx, log):
        for sp in st:
            if sp.name == "serve.decode":
                attended += sp.counts["kv_rows_attended"]
                gathered += sp.counts["kv_rows_gathered"]
    if gathered == 0:
        return None
    return 100.0 * attended / gathered
