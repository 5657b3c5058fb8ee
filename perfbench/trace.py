"""From a profiler trace to numbers.

``load`` turns the JAX profiler's ``.xplane.pb`` into a flat list of
events ``[plane, line, name, start_ns, dur_ns]``: every event of the
device planes, and the benchmark's own host spans (names starting
``pb.``).  Everything else here works on that list, so a small recorded
trace (``perfbench/fixtures/``) checks the reduction without a chip.

On a TPU v5e the device plane is ``/device:TPU:0``.  Its ``XLA Modules``
line holds one event per program run (``jit_prefill_fn(<hash>)``,
``jit_decode_fn(<hash>)``); its ``XLA Ops`` line one per operation, named
by the HLO instruction (``%photonic_gemm_fused_pallas.58 = f32[512,9728]
custom-call(...)``), with a loop's ``%while`` event around the operations
of its body.  ``load`` keeps only the instruction name (before `` = ``).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "pb."

Event = Tuple[str, str, str, int, int]  # plane, line, name, start_ns, dur_ns


def load(trace_dir: str) -> List[Event]:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events: List[Event] = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            device = plane.name.startswith(DEVICE_PLANE)
            for line in plane.lines:
                for ev in line.events:
                    if device or ev.name.startswith(HOST_PREFIX):
                        name = ev.name.split(" = ", 1)[0]
                        events.append(
                            (plane.name, line.name, name, int(ev.start_ns), int(ev.duration_ns))
                        )
    return events


def device_planes(events: Sequence[Event]) -> List[str]:
    return sorted({e[0] for e in events if e[0].startswith(DEVICE_PLANE)})


def intervals(events, plane: str, line: str, lo: int, hi: int, match=None):
    """(start, end) of the events of ``plane``/``line`` clipped to
    ``[lo, hi]``; ``match`` filters by name."""
    out = []
    for p, ln, name, s, d in events:
        if p != plane or ln != line or (match is not None and not match(name)):
            continue
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(iv: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(iv):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(events, lo: int, hi: int) -> float:
    """Nanoseconds in ``[lo, hi]`` in which some operation ran, averaged
    over the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = 0
    for plane in planes:
        total += sum(b - a for a, b in union(intervals(events, plane, OPS_LINE, lo, hi)))
    return total / len(planes)


def time_ns(events, lo: int, hi: int, line: str, match) -> float:
    """Summed duration of the matching events of ``line``, averaged over
    the device planes (a kernel's or a program's device time)."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    total = 0
    for plane in planes:
        total += sum(b - a for a, b in intervals(events, plane, line, lo, hi, match))
    return total / len(planes)


def self_times(events, plane: str, line: str, lo: int, hi: int) -> Dict[str, float]:
    """Nanoseconds per event name of ``plane``/``line`` within ``[lo, hi]``,
    each event less the events nested inside it (a loop less its body)."""
    evs = [
        (s, s + d, name) for p, ln, name, s, d in events
        if p == plane and ln == line and s >= lo and s + d <= hi
    ]
    acc: Dict[str, float] = {}
    stack: List[List] = []  # [end, name, self ns]
    for s, e, name in sorted(evs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            acc[n] = acc.get(n, 0.0) + own
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, name, e - s])
    for end, n, own in stack:
        acc[n] = acc.get(n, 0.0) + own
    return acc


def top_ops(events, lo: int, hi: int, n: int = 10) -> List[List]:
    """The ``n`` device operations (by name, self time) that took most
    time, in s, averaged over the device planes."""
    planes = device_planes(events)
    acc: Dict[str, float] = {}
    for plane in planes:
        for name, ns in self_times(events, plane, OPS_LINE, lo, hi).items():
            acc[name] = acc.get(name, 0.0) + ns
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / len(planes) / 1e9] for name, ns in ranked]


def host_spans(events) -> List[Tuple[str, int, int]]:
    return [(name, s, s + d) for p, ln, name, s, d in events if name.startswith(HOST_PREFIX)]


def label(spans, a: int, b: int) -> str:
    """The innermost host span around the middle of ``[a, b]`` (of spans
    as long as each other, the one opened last)."""
    mid = (a + b) // 2
    inner: Optional[Tuple[str, int, int]] = None
    for name, s, e in sorted(spans, key=lambda sp: sp[1]):
        if s <= mid <= e and (inner is None or e - s <= inner[2] - inner[1]):
            inner = (name, s, e)
    return inner[0] if inner else "outside any span"


def idle_gaps(events, lo: int, hi: int, n: int = 10) -> List[List]:
    """The ``n`` longest stretches of ``[lo, hi]`` with no operation on the
    first device, each labelled by what the host was doing, in s."""
    planes = device_planes(events)
    if not planes:
        return []
    busy = union(intervals(events, planes[0], OPS_LINE, lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    spans = host_spans(events)
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[label(spans, a, b), (b - a) / 1e9] for a, b in gaps[:n]]


def window(events, name: str = "pb.traced") -> Tuple[int, int]:
    """The host span that marks the traced window."""
    for span, s, e in host_spans(events):
        if span == name:
            return s, e
    raise ValueError(f"no {name!r} span in the trace")
