"""Execution mode of the Pallas kernels, chosen from the platform.

On a TPU every kernel compiles with Mosaic; anywhere else (the CPU test
runs) it runs in Pallas interpret mode.  The choice is made when a kernel
is traced, never when a module is imported, so nothing an import sees can
send a TPU kernel to the interpreter.
"""
from __future__ import annotations

import jax

__all__ = ["interpret_mode"]


def interpret_mode(interpret: bool | None = None) -> bool:
    """``interpret`` when the caller chose one (tests, compile checks for a
    described chip), else True exactly when the default backend is not a
    TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
