"""Pallas TPU kernels and their dispatching wrappers."""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(impl: Optional[str]) -> bool:
    """Whether a Pallas kernel runs in the interpreter.

    Only when asked (``impl="interpret"``) or on the CPU backend, where
    tests run. Any other backend that is not a TPU raises: a kernel that
    silently interprets there would hide that the device was never used.
    """
    if impl == "interpret":
        return True
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on the {backend!r} backend; "
        f"pass impl='interpret' to run them in the interpreter")
