"""A whole run at a tiny size on the CPU, with the look for a chip skipped:
a sound run is correct, and a run whose timed path is broken underneath is
not.  The control (the reference at the next lower precision) reads a
wider gap than the program on the same served tokens."""

import json
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness, spec

ROOT = Path(__file__).resolve().parents[2]
SIZES = dict(
    hidden_size=64, intermediate_size=128, num_hidden_layers=2,
    num_attention_heads=4, num_key_value_heads=2, vocab_size=512,
)
SERVING = {"batch_size": 4, "block_size": 16, "chunk_tokens": 32, "max_seq": 96}
# Sound runs at this size read widest gaps of about 0.02; a wrong token or
# a lost KV write reads 0.2 and more.
LIMIT = 0.08


def tiny_tree(tmp: Path) -> Path:
    (tmp / "perfbench/configs").mkdir(parents=True)
    (tmp / "perfbench/mixes").mkdir(parents=True)
    config = json.loads((ROOT / "perfbench/configs/qwen2_0_5b-ideal.json").read_text())
    config["sizes"].update(SIZES)
    config["check"].update(served_tokens=48, widest_gap_limit=LIMIT)
    (tmp / "perfbench/configs/tiny.json").write_text(json.dumps(config))
    for name, loop in (("tinybatch", "batch"), ("tinychat", "chat")):
        mix = json.loads((ROOT / f"perfbench/mixes/{loop}.json").read_text())
        mix.update(serving=SERVING)
        mix["prompt"].update(buckets=[16, 32, 64], lognormal_median=32, lognormal_sigma=0.6)
        mix["output"].update(lognormal_median=8, lognormal_sigma=0.5, min=2, max=16)
        mix.update(pool_requests=16, queue_min=8, rate_per_s=3.0, drain_limit_s=30)
        (tmp / f"perfbench/mixes/{name}.json").write_text(json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": "tiny", "source": "x", "file": "perfbench/configs/tiny.json",
         "reduced": [], "why": "x"}
    ]
    cells = ["tiny.tinybatch", "tiny.tinychat"]
    bench["workloads"] = [
        {"name": c, "config": "tiny", "traffic": c.split(".")[1], "chips": 1, "why": "x"}
        for c in cells
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            chat = any(k in m["name"] for k in ("ttft", "tpot"))
            m["workloads"] = [c for c in cells if ("chat" in c) == chat]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run(tree, capsys, workload="tiny.tinybatch", fault=None, seed=2**31 + 9):
    argv = ["--workload", workload, "--seed", str(seed), "--seconds", "3", "--trace", "0"]
    rc = harness.main(argv, t_start=time.perf_counter(), require_chip=False,
                      fault=fault, root=tree)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def alter_tokens(sched):
    pick, vocab = sched._pick, sched.model_cfg.vocab_size
    sched._pick = lambda rows, uids, ns: (pick(rows, uids, ns) + 1) % vocab


def drop_kv_writes(sched):
    prefill, decode = sched._prefill, sched._decode

    def prefill_same_state(p, toks, pool, *rest):
        return prefill(p, toks, pool, *rest)[0], pool

    def decode_same_state(p, tok, pool, *rest):
        return decode(p, tok, pool, *rest)[0], pool

    sched._prefill, sched._decode = prefill_same_state, decode_same_state


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny_tree(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("workload", ["tiny.tinybatch", "tiny.tinychat"])
def test_sound_run_is_correct(tree, capsys, workload):
    out = run(tree, capsys, workload)
    assert out["correct"] is True
    assert list(out)[-1] == "checks"
    assert out["checks"]["widest_gap"]["value"] <= LIMIT
    assert out["checks"]["window_compiles"]["value"] == 0
    names = {m.name for m in spec.find_cell(workload, tree).end_to_end}
    assert set(out["metrics"]) == names
    assert all(np.isfinite(v["value"]) and v["value"] > 0 for v in out["metrics"].values())
    assert out["device"]["count"] == 1 and "kind" in out["device"]


@pytest.mark.parametrize("fault", [alter_tokens, drop_kv_writes], ids=lambda f: f.__name__)
def test_broken_timed_path_is_not_correct(tree, capsys, fault):
    out = run(tree, capsys, fault=fault)
    assert out["correct"] is False
    assert out["checks"]["widest_gap"]["value"] > LIMIT


def test_control_reads_wider_than_the_program(tree):
    from perfbench import reference_qwen2, weights

    cell = spec.find_cell("tiny.tinybatch", tree)
    sched = harness.build_scheduler(cell, 5, harness.pool_sizes(cell.mix, 3.0))
    inst = harness.Instrument(sched)
    res = harness.closed_window(sched, inst, cell.mix, 5, 3.0, None, 48)
    sample = harness.check_sample(res["requests"], 5, 48, 16)
    w = weights.published_weights(cell.config["sizes"], 5)
    bits = cell.config["check"]["control_bits"]
    prog, ctrl = (
        max(
            reference_qwen2.served_gaps(w, p, s, cell.config["sizes"], control_bits=b).max()
            for p, s, _ in sample
        )
        for b in (None, bits)
    )
    assert ctrl > 3 * prog
