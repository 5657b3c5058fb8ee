"""The system under test, built from a configuration file.

The configuration file names the program's model module and states the
published sizes and the photonic operating point; this module turns them
into the program's ``ModelConfig``.  The weights are the benchmark's own
(``perfbench/weights.py``).
"""

from __future__ import annotations

import dataclasses
import importlib

import jax

# Published key -> the program's ModelConfig field.
_SIZE_FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
}


def noise_seed(seed: int) -> int:
    """The analog channel's noise seed, derived from ``--seed``."""
    return (seed * 2654435761 + 97) % (1 << 31)


def build(config: dict, seed: int):
    """``(arch, ModelConfig)`` of a configuration file at ``seed``."""
    from repro.core.dpu import DPUConfig
    from repro.noise import build_channel_model

    mod = importlib.import_module(config["model"])
    sizes = config["sizes"]
    cfg = dataclasses.replace(
        mod.FULL, **{f: sizes[k] for k, f in _SIZE_FIELDS.items() if k in sizes}
    )
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
