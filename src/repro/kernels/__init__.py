"""Pallas kernels of the index's hot paths (DESIGN.md §3, §6)."""
from __future__ import annotations

import jax

__all__ = ["interpret_mode"]


def interpret_mode() -> bool:
    """How a kernel runs on the current JAX backend: compiled through Mosaic
    on ``tpu`` (False), through the Pallas interpreter on ``cpu`` (True,
    the test backend). Any other backend raises: these kernels have no
    lowering for it, and silently interpreting on an accelerator would hide
    the device behind the interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here compile for TPU or run interpreted on CPU; "
        f"the {backend!r} backend has neither")
