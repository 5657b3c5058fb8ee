"""Pallas TPU kernels: the photonic GEMM and flash attention."""

from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on this backend.

    The one place the mode is chosen, from the backend alone: compiled on
    a TPU, interpreted on the CPU (where the tests run).  Any other
    backend is an error rather than a silent fallback to the interpreter.
    """
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for TPU and interpret on CPU; the "
        f"{backend!r} backend has neither"
    )
