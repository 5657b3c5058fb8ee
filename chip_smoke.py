"""Serve qwen2-0.5b at its published widths on a TPU, through the compiled
Pallas photonic GEMM kernels, and check what comes out.

Run from the root of a checkout:

    python chip_smoke.py            # one chip: the phases below
    python chip_smoke.py --tp 4     # four chips: the tensor-parallel path

The path is the one a serving user drives: ``configs/qwen2_0_5b.FULL``
(24 layers, d_model 896, 14 query / 2 KV heads, d_ff 4864, vocab 151936,
bf16) with weights from ``init_tree`` and ``--seed`` -> ``Scheduler``
(paged KV cache, chunked prefill, which prepacks every routed weight) ->
``PhotonicEngine`` with the ``pallas`` backend -> the compiled
``photonic_gemm_fused_pallas`` kernel.  Prompts of 128 and 512 tokens
(only two lengths: each prompt length compiles its own prefill program)
are served 8 at a time, 16 new tokens each, with 256-token prefill chunks,
so the long prompts chunk.

One chip:

* ``ideal`` — the SMWA operating point (4 bits, 5 GS/s) with no analog
  channel.  An ideal channel is bit-identical across backends (DESIGN.md
  §8/§14): every routed GEMM of the first and last layer, run through the
  model's ``dense`` on the ``pallas`` and on the ``ref`` prepacked
  weights, must agree bit for bit.  Then the requests are served on both
  backends; token agreement and the largest logit differences are
  printed.  They are not required to be equal: outside the GEMMs the two
  backends' programs are compiled differently, and XLA rounds some of
  the surrounding bf16 ops differently on a TPU (PERF.md, PR 11).
* ``analog`` — the SMWA channel at its calibrated DPE size N = 42 with a
  fixed noise seed: outputs must be finite, and identical in both passes.

``--tp 4``: the same config padded for a 4-way model axis
(``pad_for_mesh(4)``: 16 query / 4 KV heads), ideal channel.  Under an
ideal channel K-sharded execution is bitwise the unsharded GEMM
(DESIGN.md §10): the routed GEMMs of the first and last layer must agree
bit for bit between a 4-chip ``("data", "model")`` mesh and one chip.
Then the requests are served both ways in the same process; token
agreement, logit differences and where the KV pool and packed weights
live are printed.

Every serve runs its requests twice on one scheduler: the first pass
compiles, the second is timed (each step ends in ``block_until_ready``);
both must give the same tokens and logits, bit for bit.  Earlier lines print observations from the
chip (compiles, step times, tokens/s); they are not benchmark metrics.
The last line is ``{"ok": true, "device": {...}}``.  Any failed check or
phase exits non-zero before it, and without a TPU the script exits
non-zero at once.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

PROMPT_LENS = (128, 512)
REQUESTS_PER_LEN = 4
NEW_TOKENS = 16
BATCH = 8
CHUNK_TOKENS = 256
BLOCK_SIZE = 16
NOISE_SEED = 7


class CompileLog:
    """Counts backend compiles, their seconds and persistent-cache hits,
    from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.count, self.seconds, self.cache_hits


def prompts(vocab: int, seed: int):
    """Short prompts first, in pairs, so every prefill chunk is 128 or 256
    tokens long: three prefill programs and one decode program per serve."""
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, vocab, size=n, dtype=np.int32)
        for n in PROMPT_LENS
        for _ in range(REQUESTS_PER_LEN)
    ]


def serve(arch, cfg, params, prompt_list, log: CompileLog, *, mesh=None):
    """Serve ``prompt_list`` twice on one ``Scheduler``; return the first
    pass's tokens and logits and the observations."""
    import jax

    from repro.serving import Request, Scheduler, ServingConfig

    scfg = ServingConfig(
        batch_size=BATCH,
        max_seq=max(PROMPT_LENS) + NEW_TOKENS,
        block_size=BLOCK_SIZE,
        chunk_tokens=CHUNK_TOKENS,
        record_logits=True,
    )
    c0, s0, h0 = log.snapshot()
    t = time.perf_counter()
    sched = Scheduler(arch, cfg, params, scfg, mesh=mesh, tp_axis="model")
    jax.block_until_ready((sched.params, sched.kv_pool))
    obs = {"setup_s": time.perf_counter() - t, "engine": str(sched.photonic.describe())}

    passes = []
    uid = 0
    for _ in range(2):
        reqs = []
        for p in prompt_list:
            reqs.append(Request(uid=uid, prompt=p, max_new_tokens=NEW_TOKENS))
            uid += 1
        for r in reqs:
            sched.submit(r)
        prefill_s, decode_s = [], []
        t_pass = time.perf_counter()
        while sched.pending:
            chunks = sched.stats["prefill_chunks"]
            t = time.perf_counter()
            sched.step()
            jax.block_until_ready(sched.kv_pool)
            dt = time.perf_counter() - t
            (prefill_s if sched.stats["prefill_chunks"] > chunks else decode_s).append(dt)
        passes.append((reqs, time.perf_counter() - t_pass, prefill_s, decode_s))
        if len(passes) == 1:
            c1, s1, h1 = log.snapshot()

    (first, _, _, _), (second, pass_s, prefill_s, decode_s) = passes
    tokens = [r.output for r in first]
    logits = _logits(first)
    check(
        [r.output for r in second] == tokens
        and np.array_equal(_logits(second), logits),
        "the second pass gave other tokens or logits than the first",
    )
    c2, _, _ = log.snapshot()
    new_tokens = sum(len(t) for t in tokens)
    prompt_tokens = sum(len(p) for p in prompt_list)
    obs.update(
        compiles=c1 - c0,
        compile_s=s1 - s0,
        persistent_cache_hits=h1 - h0,
        compiles_in_timed_pass=c2 - c1,
        timed_pass_s=pass_s,
        prefill_step_s=prefill_s,
        decode_step_s_median=statistics.median(decode_s) if decode_s else None,
        decode_steps=len(decode_s),
        tokens_per_s=(prompt_tokens + new_tokens) / pass_s,
        output_tokens_per_s=new_tokens / pass_s,
    )
    placement = {
        "kv_pool": str(jax.tree.leaves(sched.kv_pool)[0].sharding),
        "packed_weight": str(
            jax.tree.leaves(sched.params["layers"]["ffn"])[0].sharding
        ),
    }
    del sched
    return tokens, logits, obs, placement


def _logits(reqs) -> np.ndarray:
    """(requests, new tokens, vocab) float32 logits of a served pass."""
    return np.stack(
        [np.stack([np.asarray(row, np.float32) for row in r.logits]) for r in reqs]
    )


def gemm_sites_mismatch(arch, variants, params, seed: int):
    """Run every routed GEMM of the first and last layer through the model's
    own ``dense``, once per ``(config, mesh)`` variant on that variant's
    prepacked weights (``mesh`` K-shards them and runs the GEMM tensor-
    parallel), at a decode batch and a prefill chunk of activations; return
    (output elements that differ between the variants, elements compared).
    """
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.models.common import dense
    from repro.photonic import sharded
    from repro.serving.scheduler import prepack_serving_params

    packed = [
        prepack_serving_params(arch, cfg, params, mesh=mesh)[1]["layers"]
        for cfg, mesh in variants
    ]
    rng = np.random.default_rng(seed)
    differ = total = 0
    for layer in (0, variants[0][0].num_layers - 1):
        for site in ("attn.wq", "attn.wk", "attn.wv", "attn.wo", "ffn.wi", "ffn.wo"):
            mod, leaf = site.split(".")
            for rows in (BATCH, CHUNK_TOKENS):
                outs = []
                for (cfg, mesh), layers in zip(variants, packed):
                    p = jax.tree.map(lambda a: a[layer], layers[mod][leaf])
                    if not outs:
                        x = rng.normal(size=(1, rows, p["w"].k))
                        x = jnp.asarray(x, cfg.compute_dtype)
                    scope = (
                        sharded.tensor_parallel(mesh, "model")
                        if mesh is not None
                        else contextlib.nullcontext()
                    )
                    with scope:
                        y = jax.jit(
                            lambda p, x, i, cfg=cfg: dense(p, x, cfg, site=site, layer=i)
                        )(p, x, jnp.int32(layer))
                    outs.append(np.asarray(y, np.float32))
                for y in outs[1:]:
                    differ += int((y != outs[0]).sum())
                    total += y.size
    return differ, total


def photonic_config(base, *, backend: str, analog: bool):
    from repro.core.dpu import DPUConfig
    from repro.noise import build_channel_model

    dpu = DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0)
    if analog:
        channel = build_channel_model("SMWA", n=42, bits=4, datarate_gs=5.0)
        dpu = dataclasses.replace(dpu, channel=channel, noise_seed=NOISE_SEED)
    return dataclasses.replace(
        base, photonic=dpu, photonic_backend=backend, photonic_scope="weights"
    )


def report(phase: str, obs: dict) -> None:
    print(f"[{phase}] " + json.dumps(obs, default=str), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"check failed: {msg}")


def run_one_chip(arch, base, seed: int, log: CompileLog) -> dict:
    import jax

    from repro.models.common import init_tree

    params = init_tree(arch.param_defs(base), jax.random.PRNGKey(seed), base.param_dtype)
    plist = prompts(base.vocab_size, seed)
    out = {}

    pallas = photonic_config(base, backend="pallas", analog=False)
    ref = photonic_config(base, backend="ref", analog=False)
    differ, total = gemm_sites_mismatch(arch, [(pallas, None), (ref, None)], params, seed)
    print(
        f"[ideal] routed GEMMs of layers 0 and {base.num_layers - 1}, pallas vs ref: "
        f"{differ} of {total} output elements differ",
        flush=True,
    )
    check(differ == 0, "ideal-channel GEMMs differ between pallas and ref")

    tok_p, log_p, obs, _ = serve(arch, pallas, params, plist, log)
    report("ideal/pallas", obs)
    tok_r, log_r, obs_r, _ = serve(arch, ref, params, plist, log)
    report("ideal/ref", obs_r)
    agree = sum(a == b for ta, tb in zip(tok_p, tok_r) for a, b in zip(ta, tb))
    n_tok = sum(map(len, tok_p))
    first = float(np.max(np.abs(log_p[:, 0] - log_r[:, 0])))
    diff = float(np.max(np.abs(log_p - log_r)))
    print(
        f"[ideal] served tokens pallas vs ref: {agree}/{n_tok} equal, "
        f"identical: {tok_p == tok_r}; max |logit(pallas) - logit(ref)|: "
        f"{first!r} on the first token, {diff!r} overall",
        flush=True,
    )
    check(np.isfinite(log_p).all() and np.isfinite(log_r).all(), "ideal logits finite")
    out["ideal"] = {
        "pallas": obs,
        "ref": obs_r,
        "tokens_equal": agree,
        "tokens": n_tok,
        "max_abs_logit_diff_first_token": first,
        "max_abs_logit_diff": diff,
    }

    # serve() has already checked that its two passes agree bit for bit.
    analog = photonic_config(base, backend="pallas", analog=True)
    tok_a, log_a, obs_a, _ = serve(arch, analog, params, plist, log)
    report("analog/pallas", obs_a)
    moved = float(np.max(np.abs(log_a - log_p)))
    agree = sum(a == b for ta, tb in zip(tok_a, tok_p) for a, b in zip(ta, tb))
    print(
        f"[analog] two runs identical under noise seed {NOISE_SEED}; "
        f"max |logit(analog) - logit(ideal)| = {moved!r}; "
        f"{agree}/{sum(map(len, tok_p))} tokens equal the ideal run's",
        flush=True,
    )
    check(np.isfinite(log_a).all(), "analog logits finite")
    out["analog"] = {"pallas": obs_a, "max_abs_logit_shift_vs_ideal": moved}
    return out


def run_tp(arch, base, seed: int, tp: int, log: CompileLog) -> dict:
    import jax

    from repro.launch.mesh import make_tp_smoke_mesh
    from repro.models.common import init_tree

    cfg = photonic_config(base.pad_for_mesh(tp), backend="pallas", analog=False)
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(seed), cfg.param_dtype)
    plist = prompts(cfg.vocab_size, seed)

    mesh = make_tp_smoke_mesh(tp)
    differ, total = gemm_sites_mismatch(arch, [(cfg, None), (cfg, mesh)], params, seed)
    print(
        f"[tp] routed GEMMs of layers 0 and {cfg.num_layers - 1}, {tp}-chip mesh vs "
        f"one chip: {differ} of {total} output elements differ",
        flush=True,
    )
    check(differ == 0, "ideal-channel tensor-parallel GEMMs differ from one chip")

    tok_1, log_1, obs_1, place_1 = serve(arch, cfg, params, plist, log)
    report("tp/one-chip", obs_1)
    tok_t, log_t, obs_t, place_t = serve(arch, cfg, params, plist, log, mesh=mesh)
    report(f"tp/mesh-{tp}", obs_t)
    print(f"[tp] placement one chip: {place_1}", flush=True)
    print(f"[tp] placement {tp}-chip mesh: {place_t}", flush=True)
    agree = sum(a == b for ta, tb in zip(tok_1, tok_t) for a, b in zip(ta, tb))
    n_tok = sum(map(len, tok_1))
    first = float(np.max(np.abs(log_1[:, 0] - log_t[:, 0])))
    diff = float(np.max(np.abs(log_1 - log_t)))
    print(
        f"[tp] served tokens mesh vs one chip: {agree}/{n_tok} equal, "
        f"identical: {tok_1 == tok_t}; max |logit(mesh) - logit(one chip)|: "
        f"{first!r} on the first token, {diff!r} overall",
        flush=True,
    )
    check(np.isfinite(log_t).all(), "tensor-parallel logits finite")
    return {
        "one_chip": obs_1,
        "mesh": obs_t,
        "tokens_equal": agree,
        "tokens": n_tok,
        "max_abs_logit_diff_first_token": first,
        "max_abs_logit_diff": diff,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0, help="weights and prompts")
    ap.add_argument(
        "--tp",
        type=int,
        default=0,
        help="run only the tensor-parallel path on this many chips",
    )
    ap.add_argument("--report", help="also write the observations as JSON here")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(
            f"chip_smoke needs a TPU; JAX's backend is {jax.default_backend()!r}",
            file=sys.stderr,
        )
        return 2

    from repro.configs.qwen2_0_5b import ARCH, FULL
    from repro.launch.profile import enable_compilation_cache

    cache = enable_compilation_cache()
    devices = jax.devices()
    print(
        f"device: {devices[0].platform} {devices[0].device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}",
        flush=True,
    )
    log = CompileLog()
    t = time.perf_counter()
    if args.tp:
        check(len(devices) >= args.tp, f"--tp {args.tp} needs {args.tp} devices")
        out = {"tp": run_tp(ARCH, FULL, args.seed, args.tp, log)}
    else:
        out = run_one_chip(ARCH, FULL, args.seed, log)
    out["wall_s"] = time.perf_counter() - t
    out["compiles"], out["compile_s"], out["persistent_cache_hits"] = log.snapshot()
    print(
        f"total: {out['compiles']} compiles, {out['compile_s']:.1f} s compiling, "
        f"{out['persistent_cache_hits']} persistent-cache hits, "
        f"{out['wall_s']:.1f} s wall",
        flush=True,
    )
    if args.report:
        Path(args.report).parent.mkdir(parents=True, exist_ok=True)
        Path(args.report).write_text(json.dumps(out, indent=1, default=str))
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
