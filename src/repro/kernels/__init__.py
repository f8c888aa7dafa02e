"""Pallas kernels: the paper's suite (gaussian, binomial, mandelbrot, nbody)
and the model stack's (flash_attention, flash_decode, mamba_scan).

Every kernel entry takes ``interpret``.  ``None`` (the default) follows the
platform: compiled Mosaic on a TPU backend, the Pallas interpreter on the
CPU backend.  A TPU therefore never runs the interpreter unless a caller
asks for it by name.
"""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``interpret`` if given, else True exactly on the CPU backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
