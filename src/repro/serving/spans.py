"""Host spans and counts of the scheduler's steps.

Every :class:`~repro.serving.Scheduler` step is one ``serve.step`` span
with its children (``serve.admit``, ``serve.prefill``, ``serve.decode``,
``serve.wait``, ``serve.emit``).  Each span does two things under one name
and one set of counts:

* it enters ``jax.profiler.TraceAnnotation(name, **counts)``, so that when
  a profiler runs the span and its counts (as event stats) sit on the same
  clock as the device's ``XLA Ops`` / ``XLA Modules`` events;
* it is recorded in :data:`LOG`, timed with the scheduler's own clock, and
  readable with no profiler running.

``LOG`` is per process: the last :data:`STEPS_KEPT` steps of every
scheduler in it, oldest first.  A step is the list of its spans, the
``serve.step`` span first; each span names its parent by index in that
list.  Spans add no device op and no device sync: their counts come from
host-side state only.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
from typing import Callable, Deque, Dict, Iterator, List

import jax

# Enough for a 51 s benchmark window and the longest drain a mix allows
# (120 s) at any step above 42 ms; the 64-slot decode program alone takes
# 154 ms a step on a v5e at 2,304 cached rows per slot.
STEPS_KEPT = 4096

Step = List["Span"]


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float = math.nan
    parent: int = -1  # index of the enclosing span in its step; -1: none
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)


LOG: Deque[Step] = collections.deque(maxlen=STEPS_KEPT)


class Recorder:
    """Opens nested spans on one clock and hands each finished outermost
    span, with everything inside it, to ``log`` as one step."""

    def __init__(self, clock: Callable[[], float], log: Deque[Step] = LOG):
        self._clock = clock
        self._log = log
        self._step: Step = []
        self._open: List[int] = []  # indices in ``_step`` of the open spans

    def current(self):
        """The innermost open span, or ``None`` outside every span.  Counts
        the host learns only after a span has closed (read in a later
        transfer) go into its ``counts`` here; they reach ``LOG`` and
        ``Scheduler.stats``, not the profiler's already-written event."""
        return self._step[self._open[-1]] if self._open else None

    @contextlib.contextmanager
    def span(self, name: str, **counts: int) -> Iterator[Callable[..., None]]:
        """Record ``name`` around the ``with`` body.  The body may add
        counts known only at its end through the callable it is given."""
        if not self._open:
            self._step = []
        step = self._step
        sp = Span(name, self._clock(), parent=self._open[-1] if self._open else -1,
                  counts=counts)
        step.append(sp)
        self._open.append(len(step) - 1)
        with jax.profiler.TraceAnnotation(name, **counts) as trace_me:

            def add(**more: int) -> None:
                sp.counts.update(more)
                trace_me.set_metadata(**more)

            try:
                yield add
            finally:
                self._open.pop()
                sp.end = self._clock()
        if not self._open:
            self._log.append(step)
