"""shard_map-pinned data-parallel train step.

GSPMD occasionally picks pathological reshard points inside `lax.scan`
bodies ("[SPMD] Involuntary full rematerialization" — observed on the
xlstm/zamba2 train cells, EXPERIMENTS.md §Perf HC-B).  For replicated-param
(DP) training the communication pattern is fully known: per-device gradients,
ONE all-reduce, replicated update.  This module pins exactly that with
`shard_map`, bypassing the partitioner's choices:

* params + optimizer state replicated (P());
* batch sharded over every mesh axis (pod x data x model ways of DP);
* gradients all-reduced once — optionally int8-compressed
  (`repro.optim.compress`, max-scale-consistent quantized psum), which
  halves the wire bytes of the only collective in the step.

Fits models whose replicated params+moments fit HBM (<= ~1.5B params bf16 +
f32 moments per v5e chip) — exactly the small-dense/SSM regime where the
GSPMD pathology bites.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax

from repro import compat
from repro.compat import PartitionSpec as P
from repro.optim import adamw
from repro.optim.compress import ring_int8_allreduce
from repro.runtime import sharding as shd


def make_dp_train_step(
    loss_fn: Callable,            # (params, batch) -> scalar loss
    opt_cfg: adamw.AdamWConfig,
    mesh: jax.sharding.Mesh,
    *,
    compress_grads: bool = False,
    tp_axis: Optional[str] = None,
) -> Callable:
    """Returns jit-able (params, opt_state, batch) -> (params, opt, loss, gnorm).

    ``tp_axis`` reserves one mesh axis for photonic tensor parallelism:
    the batch shards over the remaining axes only, and inside the body
    every routed dense GEMM K-shards over ``tp_axis`` with shard-local
    channel models (``repro.photonic.sharded.manual_tp`` — collectives
    only, since a nested shard_map is illegal here).  Params stay
    replicated, so TP-axis peers hold identical grads and the single
    all-reduce below stays correct unchanged.
    """
    axes: Tuple[str, ...] = tuple(mesh.axis_names)
    if tp_axis is not None and tp_axis not in axes:
        raise ValueError(f"tp_axis {tp_axis!r} not in mesh axes {axes}")
    n_dev = 1
    for a in axes:
        n_dev *= mesh.shape[a]

    dp_axes = tuple(a for a in axes if a != tp_axis)
    batch_spec = P(dp_axes)  # leading (batch) dim sharded over the DP axes

    def step(params, opt_state, batch):
        # constraints are GSPMD-only; inside shard_map all axes are manual
        with shd.no_constraints():
            if tp_axis is not None:
                from repro.photonic import sharded as tp_sharded

                with tp_sharded.manual_tp(tp_axis):
                    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            else:
                loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if compress_grads:
            # int8-wire ring all-reduce: halves the only collective's bytes
            grads = ring_int8_allreduce(grads, axes)
            grads = jax.tree.map(lambda g: (g / n_dev).astype(g.dtype), grads)
        else:
            grads = jax.lax.pmean(grads, axes)
        loss = jax.lax.pmean(loss, axes)
        params, opt_state, metrics = adamw.update(opt_cfg, params, grads, opt_state)
        return params, opt_state, loss, metrics["grad_norm"]

    return compat.shard_map(
        step,
        mesh=mesh,
        in_specs=(P(), P(), batch_spec),
        out_specs=(P(), P(), P(), P()),
        check_vma=False,
    )
