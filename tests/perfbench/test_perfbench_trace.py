"""The reduction from a profiler trace to per-layer metrics: on events
built by hand, where every answer is known, and on a small trace recorded
on one TPU v5e (``perfbench/fixtures/``)."""

import json
from pathlib import Path

import pytest

from perfbench import harness, readers, spec, trace

ROOT = Path(__file__).resolve().parents[2]
CONFIG = json.loads((ROOT / "perfbench/configs/qwen2_0_5b-ideal.json").read_text())
SIZES = CONFIG["sizes"]
ARCH = spec.architecture(CONFIG)
PEAKS = spec.peaks_for("TPU v5 lite")
DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1_000_000


def ev(line, name, start_ms, dur_ms, plane=DEV):
    return (plane, line, name, int(start_ms * MS), int(dur_ms * MS))


# A 100 ms window: a prefill program 10-40 ms with a kernel and an
# attention op after it, a decode program 60-80 ms with two overlapping
# kernel calls; the host spans say what the host did in the gaps.
EVENTS = [
    ev("XLA Modules", "jit_prefill_fn(12)", 10, 30),
    ev("XLA Ops", "photonic_gemm_fused_pallas.3", 10, 20),
    ev("XLA Ops", "attention_fusion", 30, 10),
    ev("XLA Modules", "jit_decode_fn(7)", 60, 20),
    ev("XLA Ops", "photonic_gemm_fused_pallas.3", 60, 8),
    ev("XLA Ops", "photonic_gemm_fused_pallas.9", 64, 10),
    ev("Steps", "ignored", 0, 100),
    ev("t1", "pb.traced", 0, 100, HOST),
    ev("t1", "pb.step", 0, 90, HOST),
    ev("t1", "pb.pick", 41, 10, HOST),
    ev("t1", "pb.emit", 82, 6, HOST),
]


def ctx(events, steps):
    lo, hi = trace.window(events)
    return harness.Context(
        events=events, lo=lo, hi=hi, steps=steps, sizes=SIZES, arch=ARCH, peaks=PEAKS,
        kernel=["photonic_gemm"], prefill_program=["prefill_fn"],
        decode_program=["decode_fn"],
    )


STEPS = [
    harness.Step(t0=0.0, t1=0.05, prefill=[(0, 512, 1024, False)]),
    harness.Step(t0=0.05, t1=0.09, decode=[100] * 64),
]


def test_busy_is_the_union_of_ops():
    # 10-40 and 60-74 ms.
    assert trace.busy_ns(EVENTS, 0, 100 * MS) == 44 * MS
    assert readers.idle_share(ctx(EVENTS, STEPS)) == pytest.approx(56.0)


def test_kernel_and_program_time():
    k = trace.time_ns(EVENTS, 0, 100 * MS, trace.OPS_LINE, lambda n: "photonic_gemm" in n)
    assert k == 38 * MS
    c = ctx(EVENTS, STEPS)
    assert readers.prefill_ms_per_ktok(c) == pytest.approx(30.0 / 0.512)
    assert readers.decode_step_ms(c) == pytest.approx(20.0)


def test_roofline_and_mfu_from_counts():
    c = ctx(EVENTS, STEPS)
    bound = readers.counts.gemm_bound_s(ARCH, SIZES, 512, PEAKS) + readers.counts.gemm_bound_s(
        ARCH, SIZES, 64, PEAKS
    )
    assert readers.gemm_roofline(c) == pytest.approx(100 * bound / 0.038)
    ops = readers.counts.prefill_ops(ARCH, SIZES, 0, 512, False) + readers.counts.decode_ops(
        ARCH, SIZES, [100] * 64
    )
    assert readers.mfu(c) == pytest.approx(100 * ops / (0.09 * 393e12))


def test_idle_gaps_are_labelled_by_the_host():
    # 74-100 ms (host in emit), 40-60 ms (in pick), 0-10 ms (in the step).
    gaps = trace.idle_gaps(EVENTS, 0, 100 * MS)
    assert gaps == [
        ["pb.emit", pytest.approx(0.026)],
        ["pb.pick", pytest.approx(0.020)],
        ["pb.step", pytest.approx(0.010)],
    ]


def test_top_ops():
    top = trace.top_ops(EVENTS, 0, 100 * MS)
    assert top[0][0].startswith("photonic_gemm") and len(top) == 3


def test_nothing_to_read_reads_nothing():
    c = ctx([e for e in EVENTS if "pallas" not in e[2] and "fn" not in e[2]], [])
    assert readers.gemm_roofline(c) is None
    assert readers.prefill_ms_per_ktok(c) is None
    assert readers.decode_step_ms(c) is None
    assert readers.mfu(c) is None


def recorded():
    import gzip

    with gzip.open(ROOT / "perfbench/fixtures/ideal_batch_step.json.gz", "rt") as f:
        data = json.load(f)
    events = [tuple(e) for e in data["events"]]
    s = data["step"]
    step = harness.Step(
        t0=s["t0"], t1=s["t1"], prefill=[tuple(p) for p in s["prefill"]], decode=s["decode"]
    )
    return events, step


def test_recorded_step_on_the_chip():
    """One step of ``qwen2_0_5b-ideal.batch``: a 512-token prefill chunk and
    a decode of 5 live rows (of 64)."""
    events, step = recorded()
    c = ctx(events, [step])
    assert (c.hi - c.lo) / 1e6 == pytest.approx(209.942195)
    modules = [(n, d) for p, ln, n, s, d in events if ln == trace.MODULES_LINE]
    decode = [d for n, d in modules if "decode_fn" in n]
    prefill = [d for n, d in modules if "prefill_fn" in n]
    assert len(decode) == 1 and len(prefill) == 1
    assert readers.decode_step_ms(c) == pytest.approx(decode[0] / 1e6)
    assert readers.prefill_ms_per_ktok(c) == pytest.approx(prefill[0] / 1e6 / 0.512)
    # Busy is a union: less than the summed op time (loops nest their body).
    busy = trace.busy_ns(events, c.lo, c.hi)
    summed = sum(d for p, ln, n, s, d in events if ln == trace.OPS_LINE)
    assert busy < summed and busy < c.hi - c.lo
    assert readers.idle_share(c) == pytest.approx(100 * (1 - busy / (c.hi - c.lo)))
    assert 0 < readers.idle_share(c) < 10
    # The kernel is matched by its own op name only, not by the ops that
    # read its output.
    kernel = [n for p, ln, n, s, d in events if ln == trace.OPS_LINE and "photonic_gemm" in n]
    assert kernel and all(n.startswith("%photonic_gemm_fused_pallas") for n in kernel)
    roof = readers.gemm_roofline(c)
    assert 0 < roof < 100
    assert 0 < readers.mfu(c) < 100


def test_recorded_top_ops_use_self_time():
    events, step = recorded()
    lo, hi = trace.window(events)
    top = trace.top_ops(events, lo, hi)
    assert len(top) == 10
    assert not any(name.startswith("%while") for name, _ in top)
    total = sum(s for _, s in top)
    assert total < (hi - lo) / 1e9
    gaps = trace.idle_gaps(events, lo, hi)
    assert gaps and all(name.startswith("pb.") for name, _ in gaps)


# What every reader of ``perfbench/metrics/`` gave on the recorded step
# before the work counts moved into the architecture module; the span-log
# readers find no log here.
RECORDED = {
    "decode_kv_useful.tps": None,
    "decode_step_ms.tpot": 154.058818,
    "decode_step_ms.tps": 154.058818,
    "gemm_roofline.tpot": 8.754380853192185,
    "gemm_roofline.tps": 8.754380853192185,
    "host_idle_ms.tps": None,
    "idle_share.tpot": 4.8137302746596555,
    "idle_share.tps": 4.8137302746596555,
    "mfu.tpot": 0.4644477814837012,
    "mfu.tps": 0.4644477814837012,
    "prefill_ms_per_ktok.tps": 88.744103515625,
    "prefill_ms_per_ktok.ttft": 88.744103515625,
}


def test_every_reader_reads_the_recorded_step_exactly_as_before(monkeypatch):
    from perfbench import spanlog

    monkeypatch.setattr(spanlog, "program_log", lambda: [])
    events, step = recorded()
    c = ctx(events, [step])
    names = sorted(p.name[: -len(".py")] for p in (ROOT / "perfbench/metrics").glob("*.py"))
    assert names == sorted(RECORDED)
    assert {n: spec.metric_reader(n)(c) for n in names} == RECORDED
