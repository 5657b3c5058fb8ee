"""The system under test, built from a configuration file.

The configuration file names the program's model module and states the
published sizes and the photonic operating point; this module turns them
into the program's ``ModelConfig``, through the size map of the
configuration's architecture module (``arch.SIZE_FIELDS``).  The weights
are the benchmark's own (``perfbench/weights.py``).
"""

from __future__ import annotations

import dataclasses
import importlib

import jax


def noise_seed(seed: int) -> int:
    """The analog channel's noise seed, derived from ``--seed``."""
    return (seed * 2654435761 + 97) % (1 << 31)


def program_fields(arch, sizes: dict) -> dict:
    """The ``ModelConfig`` fields that the published ``sizes`` set.  A key
    that ``arch`` neither maps to a field nor lists as shaping nothing the
    program takes is an error: dropped, it would leave the program at its
    preset's value under the published name."""
    unknown = sorted(k for k in sizes if k not in arch.SIZE_FIELDS and k not in arch.UNSHAPED)
    if unknown:
        raise ValueError(
            f"published keys {unknown} are neither mapped to a ModelConfig field "
            f"nor listed as unshaped by {arch.__file__}"
        )
    return {f: sizes[k] for k, f in arch.SIZE_FIELDS.items() if k in sizes}


def build(config: dict, seed: int, arch):
    """``(program arch, ModelConfig)`` of a configuration file at ``seed``;
    ``arch`` is the configuration's architecture module."""
    from repro.core.dpu import DPUConfig
    from repro.noise import build_channel_model

    mod = importlib.import_module(config["model"])
    cfg = dataclasses.replace(mod.FULL, **program_fields(arch, config["sizes"]))
    ph = config["photonic"]
    dpu = DPUConfig(
        organization=ph["organization"],
        bits=ph["bits"],
        datarate_gs=ph["datarate_gs"],
        platform=ph["platform"],
    )
    ch = ph.get("channel")
    if ch is not None:
        channel = build_channel_model(
            ch["organization"],
            n=ch["n"],
            bits=ch["bits"],
            datarate_gs=ch["datarate_gs"],
            platform=ph["platform"],
        )
        dpu = dataclasses.replace(dpu, channel=channel, noise_seed=noise_seed(seed))
    cfg = dataclasses.replace(
        cfg, photonic=dpu, photonic_backend=ph["backend"], photonic_scope="weights"
    )
    return mod.ARCH, cfg


def check_layout(arch, cfg, params) -> None:
    """Raise unless ``params`` has the tree, shapes and dtypes of the
    program's own parameter definition."""
    from repro.models.common import init_tree

    want = jax.eval_shape(
        lambda: init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    )
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError(f"weight tree differs from the program's: {got} vs {want}")
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if (a.shape, a.dtype) != (b.shape, b.dtype):
            raise ValueError(f"weight leaf {b.shape}/{b.dtype}, program wants {a.shape}/{a.dtype}")
