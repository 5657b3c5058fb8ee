"""A whole run at a tiny size on the CPU, with the look for a chip skipped:
a sound run is correct, and a run whose timed path is broken underneath is
not.  The control (the reference at the next lower precision) reads a
wider gap than the program on the same served tokens.  A second
architecture, brought by files of a tree of its own, runs as well, and the
per-layer metrics count its work from its own module."""

import json
import shutil
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


# The second architecture's module, which exists only in the test's tree.
# The program serves one dense decoder on its paged path, so the module
# takes the reference, weight layout and size map of the Qwen2 module
# beside it; its work counts are those of a model whose first layer is
# dense and whose later layers each hold 4 of 16 routed experts, 2 routed
# per token, with QK and PV head widths that differ.
MOE_MODULE = """
import importlib.util
from pathlib import Path

_path = Path(__file__).with_name("reference_qwen2.py")
_spec = importlib.util.spec_from_file_location("tiny_moe_base", _path)
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

served_gaps, layout, to_program = _base.served_gaps, _base.layout, _base.to_program
SIZE_FIELDS = _base.SIZE_FIELDS
UNSHAPED = _base.UNSHAPED + (
    "first_k_dense_replace", "n_routed_experts", "n_held_experts",
    "num_experts_per_tok", "qk_head_dim", "v_head_dim",
)


def gemm_sites(sizes):
    d, f, n = sizes["hidden_size"], sizes["intermediate_size"], sizes["num_hidden_layers"]
    dense, held = sizes["first_k_dense_replace"], sizes["n_held_experts"]
    passes = sizes["num_experts_per_tok"] * held / sizes["n_routed_experts"]
    return [
        ("attn.wq", d, d, 1, 1, n),
        ("ffn.dense", d, 2 * f, 1, 1, dense),
        ("ffn.experts", d, f, passes, held, n - dense),
    ]


def attention_widths(sizes):
    h = sizes["num_attention_heads"]
    return [(h * sizes["qk_head_dim"], h * sizes["v_head_dim"], sizes["num_hidden_layers"])]


def unembed_shape(sizes):
    return sizes["hidden_size"], sizes["vocab_size"]
"""
MOE_SIZES = dict(
    first_k_dense_replace=1, n_routed_experts=16, n_held_experts=4,
    num_experts_per_tok=2, qk_head_dim=24, v_head_dim=16,
)


def tiny_tree(tmp: Path) -> Path:
    (tmp / "perfbench/configs").mkdir(parents=True)
    (tmp / "perfbench/mixes").mkdir(parents=True)
    shutil.copy(ROOT / "perfbench/reference_qwen2.py", tmp / "perfbench/reference_qwen2.py")
    (tmp / "perfbench/tiny_moe_counts.py").write_text(MOE_MODULE)
    config = json.loads((ROOT / "perfbench/configs/qwen2_0_5b-ideal.json").read_text())
    config["sizes"].update(SIZES)
    config["check"].update(served_tokens=48, widest_gap_limit=LIMIT)
    (tmp / "perfbench/configs/tiny.json").write_text(json.dumps(config))
    config["reference"] = "perfbench/tiny_moe_counts.py"
    config["sizes"].update(MOE_SIZES)
    (tmp / "perfbench/configs/tinymoe.json").write_text(json.dumps(config))
    for name, loop in (("tinybatch", "batch"), ("tinychat", "chat")):
        mix = json.loads((ROOT / f"perfbench/mixes/{loop}.json").read_text())
        mix.update(serving=SERVING)
        mix["prompt"].update(buckets=[16, 32, 64], lognormal_median=32, lognormal_sigma=0.6)
        mix["output"].update(lognormal_median=8, lognormal_sigma=0.5, min=2, max=16)
        mix.update(pool_requests=16, queue_min=8, rate_per_s=3.0, drain_limit_s=30)
        (tmp / f"perfbench/mixes/{name}.json").write_text(json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [
        {"name": name, "source": "x", "file": f"perfbench/configs/{name}.json",
         "reduced": [], "why": "x"}
        for name in ("tiny", "tinymoe")
    ]
    cells = ["tiny.tinybatch", "tiny.tinychat", "tinymoe.tinybatch"]
    bench["workloads"] = [
        {"name": c, "config": c.split(".")[0], "traffic": c.split(".")[1], "chips": 1,
         "why": "x"}
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
    from perfbench import weights

    cell = spec.find_cell("tiny.tinybatch", tree)
    sched = harness.build_scheduler(cell, 5, harness.pool_sizes(cell.mix, 3.0))
    inst = harness.Instrument(sched)
    res = harness.closed_window(sched, inst, cell.mix, 5, 3.0, None, 48)
    sample = harness.check_sample(res["requests"], 5, 48, 16)
    w = weights.published_weights(cell.arch, cell.config["sizes"], 5)
    bits = cell.config["check"]["control_bits"]
    prog, ctrl = (
        max(
            cell.arch.served_gaps(w, p, s, cell.config["sizes"], control_bits=b).max()
            for p, s, _ in sample
        )
        for b in (None, bits)
    )
    assert ctrl > 3 * prog


def test_second_architecture_runs_from_its_own_files(tree, capsys):
    """The cell of an architecture that only the test's tree holds runs
    correct, and ``gemm_roofline`` and ``mfu`` count its work from its
    module: sites with passes and banks other than 1, one site on fewer
    layers than the rest, and QK and PV widths that differ."""
    from perfbench import readers

    before = sorted(p.relative_to(ROOT) for p in (ROOT / "perfbench").rglob("*.py"))
    out = run(tree, capsys, "tinymoe.tinybatch")
    assert out["correct"] is True
    assert out["checks"]["widest_gap"]["value"] <= LIMIT

    cell = spec.find_cell("tinymoe.tinybatch", tree)
    assert Path(cell.arch.__file__) == tree / "perfbench/tiny_moe_counts.py"
    # One 8-token prefill chunk (positions 0-7, final) and one decode of
    # two rows attending 10 and 20 positions; 1 ms of kernel time.
    ms = 1_000_000
    events = [
        ("/device:TPU:0", "XLA Ops", "photonic_gemm_fused_pallas.1", 0, ms),
        ("/host:CPU", "t1", "pb.traced", 0, 10 * ms),
    ]
    steps = [
        harness.Step(t0=0.0, t1=0.004, prefill=[(0, 8, 8, True)]),
        harness.Step(t0=0.004, t1=0.010, decode=[10, 20]),
    ]
    c = harness.Context(
        events=events, lo=0, hi=10 * ms, steps=steps, sizes=cell.config["sizes"],
        arch=cell.arch, peaks=spec.peaks_for("TPU v5 lite"), kernel=["photonic_gemm"],
        prefill_program=["prefill_fn"], decode_program=["decode_fn"],
    )
    # By hand, d 64, f 128, 2 layers: attn.wq 64 x 64 in both layers;
    # ffn.dense 64 x 256 in layer 0; ffn.experts 64 x 128 in layer 1,
    # 4 banks read, half a pass per row (2 of 16 experts, 4 held).
    # Every call here is bound by its bytes at 819 GB/s.
    prefill_bytes = (
        2 * (64 * 64 + 2 * 8 * 64 + 2 * 8 * 64)
        + (64 * 256 + 2 * 8 * 64 + 2 * 8 * 256)
        + (4 * 64 * 128 + 8 * 64 + 8 * 128)
    )
    decode_bytes = (
        2 * (64 * 64 + 2 * 2 * 64 + 2 * 2 * 64)
        + (64 * 256 + 2 * 2 * 64 + 2 * 2 * 256)
        + (4 * 64 * 128 + 2 * 64 + 2 * 128)
    )
    assert prefill_bytes == 68_096 and decode_bytes == 60_032
    bound_s = (prefill_bytes + decode_bytes) / 819e9
    assert readers.gemm_roofline(c) == pytest.approx(100 * bound_s / 1e-3, rel=1e-12)
    # Per token row 2 x (2 x 64 x 64 + 64 x 256 + 0.5 x 64 x 128) = 57,344
    # routed operations; attention 2 layers x (2 x 4 x 24 + 2 x 4 x 16) =
    # 640 per position attended; the head 2 x 512 x 64 = 65,536 per row
    # that makes logits.
    prefill_ops = 8 * 57_344 + 640 * sum(range(1, 9)) + 65_536
    decode_ops = 2 * 57_344 + 640 * (10 + 20) + 2 * 65_536
    assert readers.mfu(c) == pytest.approx(100 * (prefill_ops + decode_ops) / (0.010 * 393e12))
    assert sorted(p.relative_to(ROOT) for p in (ROOT / "perfbench").rglob("*.py")) == before
