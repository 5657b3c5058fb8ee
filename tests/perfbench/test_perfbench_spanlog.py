"""The per-layer metrics read from the program's span log
(``perfbench/spanlog.py``): known answers on a log built by hand, nothing
where the program keeps no log, and a log long enough for every mix."""

import json
import sys
from pathlib import Path

import pytest

from perfbench import harness, spanlog, spec
from repro.serving.spans import STEPS_KEPT, Span

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
MS = 1e-3


def span(name, a_ms, b_ms, **counts):
    return Span(name, a_ms * MS, b_ms * MS, parent=-1 if name == "serve.step" else 0,
                counts=counts)


def decode(a_ms, attended):
    return span("serve.decode", a_ms, a_ms + 1, rows=4, kv_rows_attended=attended,
                kv_rows_gathered=1000)


# Three steps of 100 ms.  The harness traced the first two; the third
# holds the dispatch that ends the second step's last idle stretch.
LOG = [
    [span("serve.step", 0, 99), span("serve.admit", 0, 1), span("serve.prefill", 1, 2),
     decode(2, 100), span("serve.wait", 3, 80), span("serve.emit", 80, 90)],
    [span("serve.step", 100, 199), span("serve.admit", 100, 105),
     span("serve.prefill", 105, 106), span("serve.wait", 106, 150),
     span("serve.emit", 150, 152), decode(152, 300), span("serve.wait", 153, 190),
     span("serve.emit", 190, 195)],
    [span("serve.step", 200, 299), span("serve.admit", 200, 204), decode(204, 999),
     span("serve.wait", 205, 280), span("serve.emit", 280, 290)],
]
TRACED = [harness.Step(t0=-0.0005, t1=0.0995), harness.Step(t0=0.0995, t1=0.1995)]


def ctx(steps):
    return harness.Context(
        events=[], lo=0, hi=1, steps=steps, sizes={}, arch=None, peaks=None,
        kernel=["photonic_gemm"], prefill_program=["prefill_fn"],
        decode_program=["decode_fn"],
    )


def test_traced_steps_are_those_inside_the_harness_steps():
    assert spanlog.traced_steps(ctx(TRACED), LOG) == LOG[:2]
    assert spanlog.traced_steps(ctx([]), LOG) == []


def test_host_idle_runs_from_each_wait_to_the_next_dispatch_inside_steps():
    # 80 -> 99 and 100 -> 105, 150 -> 152, 190 -> 199 and 200 -> 204 (the
    # next step's decode): 39 ms over two steps.
    assert spanlog.host_idle_ms(ctx(TRACED), LOG) == pytest.approx(19.5)
    # A pause between steps (a profiler stopping) is not the scheduler's.
    later = [[Span(sp.name, sp.start + 5.0, sp.end + 5.0, sp.parent, sp.counts)
              for sp in LOG[2]]]
    assert spanlog.host_idle_ms(ctx(TRACED), LOG[:2] + later) == pytest.approx(19.5)


def test_decode_kv_useful_is_attended_over_gathered():
    assert spanlog.decode_kv_useful(ctx(TRACED), LOG) == pytest.approx(20.0)
    no_decode = [[sp for sp in st if sp.name != "serve.decode"] for st in LOG]
    assert spanlog.decode_kv_useful(ctx(TRACED), no_decode) is None


@pytest.mark.parametrize("name", ["host_idle_ms.tps", "decode_kv_useful.tps"])
def test_a_program_without_the_log_gives_no_reading(monkeypatch, name):
    read = spec.metric_reader(name)
    assert read(ctx([harness.Step(t0=-2.0, t1=-1.0)])) is None  # no such steps
    monkeypatch.setitem(sys.modules, "repro.serving.spans", None)  # import fails
    assert spanlog.program_log() == []
    assert read(ctx(TRACED)) is None


@pytest.mark.parametrize("mix", ["batch", "chat", "gen"])
def test_the_log_holds_a_window_and_the_longest_drain(mix):
    """The readers run after the window and its drain, so the log has to
    hold both.  A step runs the 64-slot decode program, whose device time
    grows with the KV rows it gathers: 154 ms at 64 x 2304 on a v5e
    (decode_step_ms.tps in PERF_LEDGER.jsonl)."""
    m = json.loads((ROOT / f"perfbench/mixes/{mix}.json").read_text())
    serving = m["serving"]
    step_s = 0.154 * serving["batch_size"] * serving["max_seq"] / (64 * 2304)
    assert STEPS_KEPT * step_s >= BENCH["run_seconds"] + m["drain_limit_s"]
