"""Host spans of ``Scheduler.step`` and the device scopes of the model.

Every step writes one ``serve.step`` span with its children nested inside
it; the counts on the spans are the program's own arguments (prompt tokens,
the decode positions it passes, the rows it gathers); requests carry their
admission time; and the traced decode program names the attention core and
each GEMM site in its ``op_name`` metadata.
"""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dpu import DPUConfig
from repro.models import registry
from repro.models.common import init_tree
from repro.serving import Request, Scheduler, ServingConfig, spans

ARCH = registry.get("qwen2-0.5b")
CFG = dataclasses.replace(
    ARCH.smoke_config, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
    d_ff=128, vocab_size=64, remat=False,
)
SERVING = ServingConfig(batch_size=3, max_seq=64, block_size=8, chunk_tokens=16)
PROMPTS = [5, 20, 11, 33, 7]
CHILDREN = {"serve.admit", "serve.prefill", "serve.decode", "serve.wait", "serve.emit"}


def _requests():
    rng = np.random.default_rng(3)
    return [
        Request(uid=10 + i, prompt=rng.integers(0, CFG.vocab_size, n).astype(np.int32),
                max_new_tokens=4 + i)
        for i, n in enumerate(PROMPTS)
    ]


@pytest.fixture(scope="module")
def served():
    """A run of five requests through three slots, the decode program's
    arguments at every call, and the steps the run logged."""
    params = init_tree(ARCH.param_defs(CFG), jax.random.PRNGKey(0), CFG.param_dtype)
    sched = Scheduler(ARCH, CFG, params, SERVING)
    calls = []
    decode = sched._decode

    def decode_call(p, tok, pool, table, pos, active):
        calls.append((np.asarray(pos).copy(), np.asarray(active).copy()))
        return decode(p, tok, pool, table, pos, active)

    sched._decode = decode_call
    reqs = _requests()
    for r in reqs:
        sched.submit(r)
    steps = 0
    while sched.pending:
        sched.step()
        steps += 1
    return sched, reqs, calls, steps, list(spans.LOG)[-steps:]


def test_one_step_span_per_step_with_its_children_nested(served):
    _, _, _, steps, logged = served
    assert len(logged) == steps
    for step in logged:
        root = step[0]
        assert root.name == "serve.step" and root.parent == -1
        assert root.start <= root.end
        for sp in step[1:]:
            assert sp.name in CHILDREN
            assert sp.parent == 0  # every child sits directly in the step
            assert root.start <= sp.start <= sp.end <= root.end
        assert step[1].name == "serve.admit"
        # Children follow one another: none overlaps the next.
        for a, b in zip(step[1:], step[2:]):
            assert a.end <= b.start
        # A wait follows each dispatch that makes a token, then its emit.
        names = [sp.name for sp in step[1:]]
        for i, name in enumerate(names):
            if name == "serve.wait":
                assert names[i + 1] == "serve.emit"
                assert names[i - 1] in ("serve.prefill", "serve.decode")


def test_counts_are_the_programs_own_arguments(served):
    sched, reqs, calls, _, logged = served
    prefill = [sp for st in logged for sp in st if sp.name == "serve.prefill"]
    by_uid = collections.defaultdict(int)
    for sp in prefill:
        by_uid[sp.counts["uid"]] += sp.counts["tc"]
        c = sp.counts
        assert c["final"] == int(c["t0"] + c["tc"] == c["t_full"])
    assert dict(by_uid) == {r.uid: len(r.prompt) for r in reqs}
    assert sum(sp.counts["tc"] for sp in prefill) == sum(PROMPTS)

    decode = [sp for st in logged for sp in st if sp.name == "serve.decode"]
    assert len(decode) == len(calls)
    for sp, (pos, active) in zip(decode, calls):
        assert sp.counts["rows"] == int(active.sum())
        assert sp.counts["kv_rows_attended"] == int((pos[active] + 1).sum())
        assert sp.counts["kv_rows_gathered"] == SERVING.batch_size * SERVING.max_seq

    emit = [sp for st in logged for sp in st if sp.name == "serve.emit"]
    assert sum(sp.counts["tokens"] for sp in emit) == sum(len(r.output) for r in reqs)
    admit = [sp for st in logged for sp in st if sp.name == "serve.admit"]
    assert sum(sp.counts["admitted"] for sp in admit) == len(reqs)

    # Scheduler.stats holds the same counts, summed.
    st = sched.stats
    assert st["prefills"] == len(reqs) and st["completed"] == len(reqs)
    assert st["prefill_tokens"] == sum(PROMPTS)
    assert st["prefill_chunks"] == len(prefill)
    assert st["decode_steps"] == len(decode)
    assert st["blocks_zeroed"] == sum(sp.counts["blocks_zeroed"] for sp in admit)
    for key in ("rows", "kv_rows_attended", "kv_rows_gathered"):
        total = sum(sp.counts[key] for sp in decode)
        assert st["decode_rows" if key == "rows" else key] == total


def test_spans_of_a_request_share_its_uid(served):
    _, reqs, _, _, logged = served
    for step in logged:
        for i, sp in enumerate(step):
            if sp.name == "serve.prefill" and sp.counts["final"]:
                uid = sp.counts["uid"]
                assert [s.counts.get("uid") for s in step[i + 1 : i + 3]] == [uid, uid]


def test_admission_is_stamped_between_submit_and_first_token(served):
    _, reqs, _, _, _ = served
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_first <= r.t_done


def test_the_log_is_bounded_and_keeps_the_newest_steps():
    log = collections.deque(maxlen=4)
    rec = spans.Recorder(iter(range(1000)).__next__, log)
    for _ in range(6):
        with rec.span("serve.step"):
            with rec.span("serve.decode", rows=1) as add:
                add(kv_rows_attended=2)
    assert len(log) == 4
    assert [st[0].start for st in log] == [8, 12, 16, 20]
    assert log[-1][1].counts == {"rows": 1, "kv_rows_attended": 2}
    assert log[-1][1].parent == 0 and log[-1][1].end == 22 and log[-1][0].end == 23
    assert spans.LOG.maxlen == spans.STEPS_KEPT


def test_traced_decode_names_the_attention_core_and_each_gemm_site():
    cfg = dataclasses.replace(
        CFG, photonic=DPUConfig(organization="SMWA", bits=4, dpe_size=16),
        photonic_backend="pallas",
    )
    params = init_tree(ARCH.param_defs(cfg), jax.random.PRNGKey(1), cfg.param_dtype)
    sched = Scheduler(ARCH, cfg, params, SERVING)
    b = SERVING.batch_size
    text = sched._decode_program.lower(
        sched.params, jnp.asarray(sched._tokens), sched.kv_pool,
        jnp.asarray(sched._table), jnp.asarray(sched._pos), jnp.ones((b,), bool),
    ).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]*)"', text))
    scopes = {part for name in names for part in name.split("/")}
    sites = {"attn.wq", "attn.wk", "attn.wv", "attn.wo", "ffn.wi", "ffn.wo", "lm_head"}
    assert {"attn.core"} | sites <= scopes
    # The core holds the KV gather and the scores; it holds no GEMM site.
    assert any("attn.core" in n and "dot_general" in n for n in names)
    assert not any("attn.core" in n and set(n.split("/")) & sites for n in names)
