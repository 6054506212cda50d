"""Pallas kernels for the compute hot-spots the paper optimizes."""
from __future__ import annotations

import jax


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Whether a ``pallas_call`` runs in the interpreter.

    Derived from the platform: compiled on a TPU, interpreted anywhere
    else (the CPU test runs).  An explicit value is for compile tests
    that lower for a described TPU from a CPU process."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret
