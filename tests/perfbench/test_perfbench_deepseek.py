"""The DeepSeek-V2-Lite configuration and its architecture module.

The configuration file holds the catalog's published ``config`` key by
key, with one chip's expert share the only change; its module's reference
agrees with the program served through the paged ``Scheduler``; the rope
permutation of ``to_program`` matches the published interleaved layout; a
whole run at a tiny size is correct and its control is not; and the
module's counts hold the held-expert sites."""

import dataclasses
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import control, harness, model, spec, weights

ROOT = Path(__file__).resolve().parents[2]
PATH = ROOT / "perfbench/configs/deepseek_v2_lite-ep8-ideal.json"
CONFIG = json.loads(PATH.read_text())
ARCH = spec.architecture(CONFIG)

# The catalog's DeepSeek-V2-Lite ``config`` entry, as published at
# https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 10944, "kv_lora_rank": 512,
    "max_position_embeddings": 163840, "model_type": "deepseek_v2",
    "moe_intermediate_size": 1408, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 16, "num_experts_per_tok": 6, "num_hidden_layers": 27,
    "num_key_value_heads": 16, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "type": "yarn",
    },
    "rope_theta": 10000, "routed_scaling_factor": 1, "scoring_func": "softmax",
    "seq_aux": True, "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "greedy", "v_head_dim": 128, "vocab_size": 102400,
}

# The SMOKE widths in published keys: 4 of 8 routed experts held (ep_size 2).
TINY = dict(
    CONFIG["sizes"], hidden_size=64, intermediate_size=128, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_attention_heads=4,
    num_key_value_heads=4, num_hidden_layers=3, moe_intermediate_size=48,
    n_routed_experts=4, ep_size=2, num_experts_per_tok=2, n_shared_experts=1,
    vocab_size=512,
)
# Sound tiny runs read widest gaps of 0.05-0.26 over three seeds; the
# reference at 4 bits, on the same served tokens, 3.5-4.7.
LIMIT = 1.0


@pytest.mark.parametrize("group", ["top level", "sizes"])
def test_configuration_holds_the_catalog_entry_key_by_key(group):
    # The catalog's keys sit at the file's top level, where the published
    # entry is compared, and again under ``sizes``, which the harness reads.
    sizes = CONFIG if group == "top level" else CONFIG["sizes"]
    assert CONFIG["reduced"] == ["n_routed_experts"]
    if group == "sizes":
        assert set(sizes) == set(CATALOG) | {"ep_size"}
    for key, value in CATALOG.items():
        if key == "n_routed_experts":
            continue
        assert sizes[key] == value and type(sizes[key]) is type(value), key
    assert sizes["n_routed_experts"] == 8 and sizes["ep_size"] == 8
    assert sizes["n_routed_experts"] * sizes["ep_size"] == CATALOG["n_routed_experts"]
    assert {k: CONFIG[k] for k in CONFIG["sizes"]} == CONFIG["sizes"]
    entry = next(
        c for c in json.loads((ROOT / "BENCHMARK.json").read_text())["configs"]
        if c["file"] == "perfbench/configs/deepseek_v2_lite-ep8-ideal.json"
    )
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json"
    )
    assert entry["reduced"] == CONFIG["reduced"]


def test_program_fields_hold_the_share_and_refuse_what_is_not_implemented():
    fields = model.program_fields(ARCH, CONFIG["sizes"])
    assert (fields["num_experts"], fields["ep_size"], fields["num_layers"]) == (8, 8, 27)
    assert fields["first_dense_layers"] == 1 and fields["rope_scaling"]["factor"] == 40
    with pytest.raises(ValueError, match="expert_dim"):
        model.program_fields(ARCH, dict(CONFIG["sizes"], expert_dim=8))
    for key, value in (
        ("topk_method", "group_limited_greedy"), ("scoring_func", "sigmoid"),
        ("q_lora_rank", 1536), ("moe_layer_freq", 2),
    ):
        config = json.loads(json.dumps(CONFIG))
        config["sizes"][key] = value
        with pytest.raises(ValueError, match="not implemented"):
            model.build(config, 1, ARCH)


def test_rope_order_matches_the_interleaved_layout():
    """The program's half-split rotation of the permuted columns equals the
    published interleaved rotation, and so do query-key products."""
    from repro.models.common import apply_rope, rope_inv_freq

    rng = np.random.default_rng(0)
    t, rope = 11, 64
    inv = ARCH.yarn_inv_freq(CONFIG["sizes"])
    q, k = (jnp.asarray(rng.normal(size=(t, 3, rope)), jnp.float32) for _ in range(2))
    order = ARCH.rope_order(rope)
    ref_q, ref_k = ARCH._rope(q, jnp.asarray(inv)), ARCH._rope(k, jnp.asarray(inv))
    prog_inv = rope_inv_freq(rope, 10000.0, tuple(CONFIG["sizes"]["rope_scaling"].items()))
    np.testing.assert_allclose(np.asarray(prog_inv), inv, rtol=1e-6)
    pos = jnp.arange(t)
    got_q = apply_rope(q[..., order], pos, 10000.0, prog_inv)
    got_k = apply_rope(k[..., order], pos, 10000.0, prog_inv)
    np.testing.assert_allclose(np.asarray(got_q), np.asarray(ref_q), atol=1e-5)
    dots = lambda a, b: jnp.einsum("thd,shd->hts", a, b)
    np.testing.assert_allclose(
        np.asarray(dots(got_q, got_k)), np.asarray(dots(ref_q, ref_k)), atol=1e-4
    )


def test_scheduler_prefill_and_decode_agree_with_the_reference():
    """Chunked prefill, then paged decode, through the ``Scheduler`` in
    float32 with the GEMMs digital, against the reference's full forward
    at every emitted position.  The two differ only in the order of float
    sums (chunk attention, the absorbed decode, the grouped expert GEMM):
    1e-4 on logits of magnitude about 4, where a wrong rope pair, scale or
    expert reads 0.1 and more."""
    from repro.serving import Request, Scheduler, ServingConfig

    config = dict(CONFIG, sizes=TINY)
    prog, cfg = model.build(config, 1, ARCH)
    cfg = dataclasses.replace(
        cfg, photonic=None, param_dtype=jnp.float32, compute_dtype=jnp.float32, remat=False
    )
    seed = 5
    w = weights.published_weights(ARCH, TINY, seed)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), weights.program_weights(ARCH, TINY, seed))
    sched = Scheduler(prog, cfg, params, ServingConfig(
        batch_size=2, max_seq=48, block_size=4, chunk_tokens=8, record_logits=True,
    ))
    rng = np.random.default_rng(0)
    reqs = [
        Request(uid=i, prompt=rng.integers(0, 512, n).astype(np.int32), max_new_tokens=6)
        for i, n in enumerate((13, 5, 21))
    ]
    sched.run(reqs)
    assert sched.stats["prefill_chunks"] > len(reqs)  # prompts were chunked
    for r in reqs:
        tokens = np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)])
        ref = np.asarray(ARCH.logits(w, tokens, TINY, len(r.prompt) - 1))
        np.testing.assert_allclose(np.stack(r.logits), ref, atol=1e-4, rtol=0)


def _tree(tmp: Path) -> Path:
    (tmp / "perfbench/configs").mkdir(parents=True)
    (tmp / "perfbench/mixes").mkdir(parents=True)
    shutil.copy(ROOT / "perfbench/reference_deepseek_v2.py", tmp / "perfbench")
    config = dict(CONFIG, sizes=TINY)
    config["check"] = dict(CONFIG["check"], served_tokens=48, widest_gap_limit=LIMIT)
    (tmp / "perfbench/configs/tinyds.json").write_text(json.dumps(config))
    mix = json.loads((ROOT / "perfbench/mixes/ep_batch.json").read_text())
    mix.update(serving={"batch_size": 4, "block_size": 16, "chunk_tokens": 32, "max_seq": 96})
    mix["prompt"].update(buckets=[16, 32, 64], lognormal_median=32)
    mix["output"].update(lognormal_median=8, lognormal_sigma=0.5, min=2, max=16)
    mix.update(pool_requests=16, queue_min=8, drain_limit_s=30)
    (tmp / "perfbench/mixes/tinyb.json").write_text(json.dumps(mix))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tinyds", "source": "x", "file": "perfbench/configs/tinyds.json",
                         "reduced": [], "why": "x"}]
    bench["workloads"] = [{"name": "tinyds.tinyb", "config": "tinyds", "traffic": "tinyb",
                           "chips": 1, "why": "x"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tinyds.tinyb"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def test_tiny_run_is_correct_traced_and_its_control_is_not(tmp_path, capsys):
    """A whole traced run of the cell's program at the tiny size on the CPU:
    ``correct``, with the span-log metrics read; and the control's reading
    on the same run lies above the limit the program's lies below."""
    tree = _tree(tmp_path)
    argv = ["--workload", "tinyds.tinyb", "--seed", str(2**31 + 9), "--seconds", "3",
            "--trace", "1"]
    assert harness.main(argv, t_start=time.perf_counter(), require_chip=False, root=tree) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    assert 0 < out["metrics"]["decode_kv_useful.tps"]["value"] <= 100
    cell = spec.find_cell("tinyds.tinyb", tree)
    got = control.readings(cell, 777, 3.0)
    assert got["program"] < LIMIT < got["control"]


def test_counts_hold_the_held_expert_sites():
    sites = {s[0]: s[1:] for s in ARCH.gemm_sites(CONFIG["sizes"])}
    assert sites["ffn.experts.wi"] == (2048, 2816, 0.75, 8, 26)
    assert sites["ffn.experts.wo"] == (1408, 2048, 0.75, 8, 26)
    assert sites["ffn.shared.wi"] == (2048, 5632, 1, 1, 26)
    assert sites["ffn.wi"] == (2048, 21888, 1, 1, 1)
    assert sites["attn.wq"] == (2048, 3072, 1, 1, 27)
    assert "attn.wuk" not in sites and "lm_head" not in sites
    assert ARCH.attention_widths(CONFIG["sizes"]) == [(3072, 2048, 27)]
    assert ARCH.unembed_shape(CONFIG["sizes"]) == (2048, 102400)
