"""Tuned process-launch profile: allocator, logging, XLA and compilation
cache environment for benchmarks, examples and the dry-run sweep.

The upstream JAX training harnesses this repo draws idiom from launch
through a shell profile (``export LD_PRELOAD=...libtcmalloc.so.4``,
``TF_CPP_MIN_LOG_LEVEL``, curated ``XLA_FLAGS``) before ever touching
Python.  We keep the same knobs but make them a library so every entry
point — ``benchmarks/run.py``, ``examples/*``, ``repro.launch.dryrun``
— applies one *identical, recorded* profile instead of whatever the
invoking shell happened to export:

* **tcmalloc** — detected, never injected in-process: ``LD_PRELOAD`` is
  read by the dynamic linker at ``exec`` time, so :func:`apply` can only
  report whether it is active; :func:`child_env` builds the environment
  for subprocess launches (the dry-run sweep) where it *can* take
  effect.
* **env hygiene** — ``TF_CPP_MIN_LOG_LEVEL`` and the tcmalloc
  large-alloc report threshold are defaulted (never overridden) so
  benchmark stdout is the measurement, not the log stream.
* **XLA_FLAGS** — curated flags are *merged*: anything the user already
  set wins, flags are only appended if the option is absent.  Nothing in
  the curated set changes numerics — the bitwise contracts
  (DESIGN.md §7/§14) hold with or without the profile.
* **persistent compilation cache** — ``jax_compilation_cache_dir``
  pointed at ``JAX_COMPILATION_CACHE_DIR`` when it is set, else at
  ``<checkout>/.jax_cache``, so repeat runs (and CI, which restores the
  directory from its cache action) skip recompilation; the first trace of
  a decode step dominates cold wall-clock.

:func:`describe` snapshots the resolved profile; ``benchmarks/run.py``
embeds it in ``results/BENCH_photonic.json`` so every committed number
names the environment that produced it.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, List, Optional

# Known tcmalloc install paths (Debian/Ubuntu multiarch, RH, conda).
TCMALLOC_CANDIDATES = (
    "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4",
    "/usr/lib/x86_64-linux-gnu/libtcmalloc_minimal.so.4",
    "/usr/lib/libtcmalloc.so.4",
    "/usr/lib64/libtcmalloc.so.4",
    "/opt/conda/lib/libtcmalloc.so",
)

# Suppress absl/TF chatter and tcmalloc's large-allocation reports (60 GB
# threshold — big weight buffers are expected, not leaks).
ENV_DEFAULTS = {
    "TF_CPP_MIN_LOG_LEVEL": "3",
    "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
}

# Merged into XLA_FLAGS only when the option is not already present.
# Numerics-neutral by construction: no fast-math, no contraction changes.
XLA_FLAG_DEFAULTS: List[str] = [
    # CPU hosts: keep the compilation parallelism bounded so benchmark
    # processes don't oversubscribe the cores the benchmark is timing.
    "--xla_cpu_parallel_codegen_split_count=8",
]

# The persistent compilation cache's home when JAX_COMPILATION_CACHE_DIR is
# unset: one fixed, gitignored directory inside the checkout.  A cache hit
# needs the same path every run, so it is never temporary or per-process.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def find_tcmalloc() -> Optional[str]:
    """Path of an installed tcmalloc shared object, or ``None``."""
    for path in TCMALLOC_CANDIDATES:
        if os.path.exists(path):
            return path
    return None


def tcmalloc_active() -> bool:
    """Whether this process was launched with tcmalloc preloaded."""
    return "tcmalloc" in os.environ.get("LD_PRELOAD", "")


def _merge_xla_flags(extra: List[str]) -> str:
    """Append ``extra`` to ``XLA_FLAGS``, user-set options winning."""
    current = os.environ.get("XLA_FLAGS", "")
    present = {
        tok.split("=", 1)[0] for tok in current.split() if tok.startswith("--")
    }
    added = [f for f in extra if f.split("=", 1)[0] not in present]
    merged = " ".join(filter(None, [current, *added]))
    if merged:
        os.environ["XLA_FLAGS"] = merged
    return merged


def compilation_cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else :data:`DEFAULT_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compilation_cache_dir` and return that directory.

    Call before the first compile.  Every compile is cached: the default
    one-second floor would skip exactly the small kernels whose compiles
    dominate a cold start.
    """
    import jax

    cache = compilation_cache_dir()
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def apply(
    *,
    xla_flags: Optional[List[str]] = None,
    compilation_cache: bool = True,
) -> Dict[str, object]:
    """Apply the launch profile to the current process and return
    :func:`describe`'s snapshot of what was resolved.

    Idempotent, and safe to call after ``jax`` is imported (the
    compilation-cache config is applied through ``jax.config``; the env
    defaults only matter pre-import but are harmless after).  Call sites
    that must pin ``--xla_force_host_platform_device_count`` first
    (``repro.launch.dryrun``) keep their flag: merging never overrides
    an option that is already set.
    """
    for key, val in ENV_DEFAULTS.items():
        os.environ.setdefault(key, val)
    _merge_xla_flags(XLA_FLAG_DEFAULTS if xla_flags is None else xla_flags)
    if compilation_cache:
        enable_compilation_cache()
    return describe()


def describe() -> Dict[str, object]:
    """Snapshot of the effective launch profile (recorded into benchmark
    JSON so committed numbers name their environment)."""
    import jax

    return {
        "tcmalloc_found": find_tcmalloc(),
        "tcmalloc_active": tcmalloc_active(),
        "ld_preload": os.environ.get("LD_PRELOAD") or None,
        "tf_cpp_min_log_level": os.environ.get("TF_CPP_MIN_LOG_LEVEL"),
        "xla_flags": os.environ.get("XLA_FLAGS") or None,
        "jax_compilation_cache_dir": jax.config.jax_compilation_cache_dir,
    }


def child_env(base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """Environment for launching a child process under the full profile —
    including ``LD_PRELOAD=tcmalloc``, which only the *next* ``exec`` can
    honour.  Used by the dry-run sweep's per-cell subprocesses."""
    env = dict(os.environ if base is None else base)
    for key, val in ENV_DEFAULTS.items():
        env.setdefault(key, val)
    tc = find_tcmalloc()
    if tc and "tcmalloc" not in env.get("LD_PRELOAD", ""):
        env["LD_PRELOAD"] = ":".join(filter(None, [env.get("LD_PRELOAD"), tc]))
    env.setdefault("JAX_COMPILATION_CACHE_DIR", compilation_cache_dir())
    return env
