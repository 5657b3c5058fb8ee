"""Fault-tolerant training loop.

Production behaviors, all exercised by tests/test_runtime.py:

* **checkpoint/restart** — periodic async checkpoints (params + optimizer +
  step); on start, resumes from the latest complete checkpoint; the
  stateless data pipeline replays the exact batch sequence.
* **preemption handling** — SIGTERM/SIGINT trigger a final checkpoint and a
  clean exit (the SLURM/Borg eviction pattern).
* **straggler watchdog** — per-step wall times feed an EWMA; steps slower
  than ``straggler_factor`` x the EWMA are logged with a mitigation hook
  (on real fleets: re-shard / hot-spare swap; here: recorded + surfaced).
* **elastic resume** — checkpoints are mesh-independent (host arrays), so a
  job may resume on a different mesh shape; shardings are re-derived.
* **microbatching** — gradient accumulation splits the global batch into
  ``microbatches`` sequential chunks (jax.lax.scan), trading step time for
  activation memory.
"""

from __future__ import annotations

import dataclasses
import signal
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import ckpt
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.models.common import axes_tree, init_tree
from repro.optim import adamw
from repro.runtime import sharding as shd


@dataclasses.dataclass
class TrainConfig:
    steps: int = 100
    ckpt_every: int = 20
    ckpt_dir: str = "/tmp/repro_ckpt"
    log_every: int = 10
    microbatches: int = 1
    straggler_factor: float = 3.0
    async_checkpoint: bool = True
    keep_checkpoints: int = 3
    seed: int = 0


@dataclasses.dataclass
class TrainState:
    params: Any
    opt_state: adamw.OptState
    step: int


def build_train_step(
    loss_fn: Callable, opt_cfg: adamw.AdamWConfig, microbatches: int = 1
):
    """jit-able (params, opt_state, batch) -> (params, opt_state, metrics)."""

    def step(params, opt_state, batch):
        if microbatches == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            def micro(carry, mb):
                acc, _ = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (jax.tree.map(jnp.add, acc, g), l), None

            mbs = jax.tree.map(
                lambda x: x.reshape(microbatches, -1, *x.shape[1:]), batch
            )
            zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, loss), _ = jax.lax.scan(micro, (zero, jnp.zeros(())), mbs)
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
        params, opt_state, metrics = adamw.update(opt_cfg, params, grads, opt_state)
        metrics["loss"] = loss
        return params, opt_state, metrics

    return step


class StragglerWatchdog:
    def __init__(self, factor: float):
        self.factor = factor
        self.ewma: Optional[float] = None
        self.events: list = []

    def observe(self, step: int, dt: float) -> bool:
        straggled = self.ewma is not None and dt > self.factor * self.ewma
        if straggled:
            self.events.append((step, dt, self.ewma))
        # EWMA excludes straggler samples so one hiccup doesn't mask the next
        if not straggled:
            self.ewma = dt if self.ewma is None else 0.9 * self.ewma + 0.1 * dt
        return straggled


def train(
    *,
    arch,
    model_cfg,
    data_cfg: DataConfig,
    train_cfg: TrainConfig,
    opt_cfg: Optional[adamw.AdamWConfig] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    tp_axis: Optional[str] = None,  # K-shard photonic GEMMs over this axis
    fail_at_step: Optional[int] = None,  # test hook: simulated crash
    log: Callable[[str], None] = print,
) -> Dict[str, Any]:
    """Run (or resume) a training job. Returns final state + metrics."""
    opt_cfg = opt_cfg or adamw.AdamWConfig(total_steps=train_cfg.steps)
    stream = SyntheticTokens(data_cfg)
    # Photonic QAT: construct the engine up front so an invalid operating
    # point / scope / backend fails here with a readable error instead of
    # mid-trace inside the first jitted step, and the operator can see
    # which sites run photonically (STE backward keeps dense gradients).
    from repro.models.common import engine_from_model_config

    photonic_engine = engine_from_model_config(model_cfg)
    if photonic_engine is not None:
        log(f"[train] photonic engine: {photonic_engine.describe()}")
    loss_fn = lambda p, b: arch.loss(p, b, model_cfg)  # noqa: E731
    step_fn = build_train_step(loss_fn, opt_cfg, train_cfg.microbatches)

    defs = arch.param_defs(model_cfg)
    param_axes = axes_tree(defs)

    if mesh is not None:
        ctx = shd.use_rules(mesh)
        ctx.__enter__()
        params_sh = shd.tree_shardings(
            mesh,
            jax.eval_shape(
                lambda k: init_tree(defs, k, model_cfg.param_dtype),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            ),
            param_axes,
        )
    else:
        ctx = None
        params_sh = None

    # ---- init or resume -----------------------------------------------------
    start = ckpt.latest_step(train_cfg.ckpt_dir)
    params = init_tree(defs, jax.random.PRNGKey(train_cfg.seed), model_cfg.param_dtype)
    opt_state = adamw.init(params)
    step0 = 0
    if start is not None:
        state_like = {"params": params, "opt": opt_state}
        restored = ckpt.restore(train_cfg.ckpt_dir, start, state_like)
        params, opt_state = restored["params"], restored["opt"]
        step0 = start
        log(f"[train] resumed from checkpoint step {start}")

    jitted = jax.jit(step_fn, donate_argnums=(0, 1))

    # ---- preemption handling -------------------------------------------------
    preempted = {"flag": False}

    def _handler(signum, frame):
        preempted["flag"] = True

    old_handlers = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            old_handlers[sig] = signal.signal(sig, _handler)
        except ValueError:
            pass  # non-main thread (tests)

    watchdog = StragglerWatchdog(train_cfg.straggler_factor)
    losses = []
    pending_save = None
    # Tensor-parallel photonic QAT: the TP scope must be live whenever the
    # jitted step (re)traces, i.e. across the whole loop.  Entered as the
    # last statement before the try so the matching __exit__ in `finally`
    # cannot be skipped by a setup failure (a leaked thread-local scope
    # would silently re-route every later dense() in this process).
    tp_ctx = None
    if tp_axis is not None and mesh is not None and photonic_engine is not None:
        from repro.photonic import sharded as tp_sharded

        log(f"[train] photonic tensor-parallel over mesh axis {tp_axis!r}")
        tp_ctx = tp_sharded.tensor_parallel(mesh, tp_axis)
        tp_ctx.__enter__()
    try:
        for step in range(step0, train_cfg.steps):
            if fail_at_step is not None and step == fail_at_step:
                raise RuntimeError(f"simulated node failure at step {step}")
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in stream.batch_at(step).items()}
            params, opt_state, metrics = jitted(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.time() - t0
            losses.append(loss)
            if watchdog.observe(step, dt):
                log(
                    f"[train] straggler at step {step}: {dt:.3f}s "
                    f"(ewma {watchdog.ewma:.3f}s)"
                )
            if step % train_cfg.log_every == 0:
                log(f"[train] step {step} loss {loss:.4f} ({dt:.3f}s)")
            is_last = step == train_cfg.steps - 1
            if (step + 1) % train_cfg.ckpt_every == 0 or is_last or preempted["flag"]:
                if pending_save is not None:
                    pending_save.join()
                pending_save = ckpt.save(
                    train_cfg.ckpt_dir,
                    step + 1,
                    {"params": params, "opt": opt_state},
                    blocking=not train_cfg.async_checkpoint,
                )
                ckpt.cleanup(train_cfg.ckpt_dir, train_cfg.keep_checkpoints)
            if preempted["flag"]:
                log(f"[train] preempted at step {step}; checkpointed and exiting")
                break
    finally:
        if pending_save is not None:
            pending_save.join()
        for sig, h in old_handlers.items():
            signal.signal(sig, h)
        if tp_ctx is not None:
            tp_ctx.__exit__(None, None, None)
        if ctx is not None:
            ctx.__exit__(None, None, None)

    return {
        "params": params,
        "opt_state": opt_state,
        "losses": losses,
        "straggler_events": watchdog.events,
        "final_step": step0 + len(losses),
        "preempted": preempted["flag"],
    }
