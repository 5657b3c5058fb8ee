"""Every mix's generator is deterministic per seed, emits only bucket
lengths, and gives every seed the same set of sizes."""

import collections
import json
from itertools import islice
from pathlib import Path

import numpy as np
import pytest

from perfbench import traffic

ROOT = Path(__file__).resolve().parents[2]
MIXES = sorted(p.stem for p in (ROOT / "perfbench/mixes").glob("*.json"))
SEEDS = [0, 12_345, 2**31 + 77, 2**40 + 3, -5]
VOCAB = 151_936


def load(name):
    return json.loads((ROOT / f"perfbench/mixes/{name}.json").read_text())


def requests(mix, seed):
    if mix["loop"] == "closed":
        return list(islice(traffic.closed_loop(mix, seed, VOCAB), mix["pool_requests"]))
    return traffic.open_loop(mix, seed, VOCAB, 30.0, mix["rate_per_s"])


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    mix = load(name)
    for seed in SEEDS:
        a, b = requests(mix, seed), requests(mix, seed)
        assert [r.max_new_tokens for r in a] == [r.max_new_tokens for r in b]
        assert [r.arrival_s for r in a] == [r.arrival_s for r in b]
        assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_only_bucket_lengths_and_bounded_outputs(name):
    mix = load(name)
    buckets = set(mix["prompt"]["buckets"])
    for seed in SEEDS:
        for r in requests(mix, seed):
            assert len(r.prompt) in buckets
            assert mix["output"]["min"] <= r.max_new_tokens <= mix["output"]["max"]
            assert len(r.prompt) + r.max_new_tokens <= mix["serving"]["max_seq"]
            assert r.prompt.dtype == np.int32 and 0 <= r.prompt.min() and r.prompt.max() < VOCAB


@pytest.mark.parametrize("name", MIXES)
def test_seeds_shuffle_one_set_of_work(name):
    mix = load(name)
    runs = [requests(mix, seed) for seed in SEEDS]
    sizes = [collections.Counter((len(r.prompt), r.max_new_tokens) for r in run) for run in runs]
    assert all(s == sizes[0] for s in sizes)
    gaps = [sorted(np.round(np.diff([r.arrival_s for r in run]), 9)) for run in runs]
    assert [len(g) for g in gaps] == [len(gaps[0])] * len(gaps)
    orders = [tuple(len(r.prompt) for r in run) for run in runs]
    assert len(set(orders)) > 1
    assert not np.array_equal(runs[0][0].prompt, runs[1][0].prompt)


def test_open_loop_rate_and_first_arrival():
    mix = load("chat")
    reqs = traffic.open_loop(mix, 3, VOCAB, 40.0, 5.0)
    arrivals = [r.arrival_s for r in reqs]
    assert len(reqs) == 200 and arrivals[0] == 0.0
    assert arrivals == sorted(arrivals)
    assert 35.0 < arrivals[-1] < 40.0


def test_prefill_shapes_by_hand():
    # 256-token budget over prompts of 128..1024: a 128-token prompt leaves
    # 128 of budget, so every longer prompt can start half-way.
    want = {(128, 128, True), (256, 256, True)}
    for n in (256, 512, 1024):
        want |= {(128, n, False), (128, n, True)}
    for n in (512, 1024):
        want |= {(256, n, False), (256, n, True)}
    assert traffic.prefill_shapes([128, 256, 512, 1024], 256) == want
    assert traffic.prefill_shapes([512, 1024, 2048], 512) == {
        (512, 512, True), (512, 1024, False), (512, 1024, True),
        (512, 2048, False), (512, 2048, True),
    }


def test_block_counts():
    mix = {"serving": {"block_size": 16}}
    assert traffic.block_counts(mix, [(128, 16), (128, 17), (256, 1)]) == {9, 10, 17}
