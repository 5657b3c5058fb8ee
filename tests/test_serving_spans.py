"""Host spans of ``Scheduler.step`` and the device scopes of the model.

Every step writes one ``serve.step`` span with its children nested inside
it; the counts on the spans are the program's own arguments (prompt tokens,
the decode positions it passes, the rows it gathers); requests carry their
admission time; and the traced decode program names the attention core and
each GEMM site in its ``op_name`` metadata.

The emit loop is host work: with ``record_logits`` off a step indexes one
device array per pick, however many slots decode, and with it on the rows
recorded are, bit for bit, the rows the program returned.
"""

import collections
import dataclasses
import re

import jax
import jax._src.array as jax_array
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


def _count_indexing(monkeypatch):
    """Count ``__getitem__`` calls on device arrays from here on."""
    calls = [0]
    getitem = jax_array.ArrayImpl.__getitem__

    def counted(self, idx):
        calls[0] += 1
        return getitem(self, idx)

    monkeypatch.setattr(jax_array.ArrayImpl, "__getitem__", counted)
    return calls


@pytest.mark.parametrize("batch_size", [2, 4])
def test_a_step_indexes_one_device_array_per_pick(batch_size, monkeypatch):
    """With ``record_logits`` off, a step slices the logits once per pick
    (each final prefill chunk, the decode) and takes no per-token slice:
    a decode-only step indexes one device array at 2 live slots and at 4."""
    params = init_tree(ARCH.param_defs(CFG), jax.random.PRNGKey(0), CFG.param_dtype)
    scfg = dataclasses.replace(SERVING, batch_size=batch_size)
    sched = Scheduler(ARCH, CFG, params, scfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, CFG.vocab_size, 3).astype(np.int32)] * batch_size

    def requests():  # one wave: every slot prefills in the first step
        return [Request(uid=10 + i, prompt=p, max_new_tokens=6)
                for i, p in enumerate(prompts)]

    sched.run(requests())  # compiles every program the measured run calls
    calls = _count_indexing(monkeypatch)
    for r in requests():
        sched.submit(r)
    per_step, decode_only, widest = [], [], 0
    while sched.pending:
        before = calls[0]
        sched.step()
        step = spans.LOG[-1]
        names = [sp.name for sp in step]
        waits = [sp for sp in step if sp.name == "serve.wait"]
        assert all(sp.counts["logits_rows"] == 0 for sp in waits)
        per_step.append((calls[0] - before, len(waits)))
        if "serve.decode" in names and "serve.prefill" not in names:
            decode_only.append(calls[0] - before)
            rows = next(sp for sp in step if sp.name == "serve.decode").counts["rows"]
            widest = max(widest, rows)
    assert all(n == picks for n, picks in per_step)
    assert decode_only and set(decode_only) == {1}
    assert widest == batch_size  # every slot decoded in one of those steps
    assert sched.stats["logits_rows_fetched"] == 0


def _tiny(kind):
    if kind == "dense":
        return ARCH, CFG
    ds = registry.get("deepseek-v2-lite-16b")
    return ds, dataclasses.replace(
        ds.smoke_config, remat=False, param_dtype=jnp.float32,
        compute_dtype=jnp.float32,
    )


@pytest.mark.parametrize("kind", ["dense", "moe_mla"])
def test_recorded_rows_are_the_programs_rows_bit_for_bit(kind):
    """With ``record_logits`` on, each emitted token's recorded row is the
    row the program returned for it, and the outputs are those of a run
    with recording off."""
    arch, cfg = _tiny(kind)
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(2), cfg.param_dtype)
    outputs = {}
    for record in (True, False):
        sched = Scheduler(
            arch, cfg, params, dataclasses.replace(SERVING, record_logits=record)
        )
        returned = {}
        pick = sched._pick

        def pick_call(rows, uids, ns):
            host = np.asarray(rows)
            for row, uid, n in zip(host, uids, ns):
                if uid:  # idle decode rows carry uid 0; requests start at 10
                    returned[(uid, n)] = row.copy()
            return pick(rows, uids, ns)

        sched._pick = pick_call
        reqs, kept = _requests(), 0
        for r in reqs:
            sched.submit(r)
        while sched.pending:
            sched.step()
            waits = [sp for sp in spans.LOG[-1] if sp.name == "serve.wait"]
            kept += sum(sp.counts["logits_rows"] for sp in waits)
        outputs[record] = [r.output for r in reqs]
        emitted = sum(len(r.output) for r in reqs)
        assert kept == sched.stats["logits_rows_fetched"] == (emitted if record else 0)
        for r in reqs:
            assert len(r.logits) == (len(r.output) if record else 0)
            for n, row in enumerate(r.logits):
                want = returned[(r.uid, n)]
                assert row.dtype == want.dtype and row.tobytes() == want.tobytes()
    assert outputs[True] == outputs[False]
