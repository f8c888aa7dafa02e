"""Selective-scan op: jit'd wrapper dispatching Pallas kernel vs the
chunked associative-scan jnp path used by the portable model stack."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.mamba_scan import kernel as K
from repro.kernels.mamba_scan import ref as R
from repro.models.layers import _ssm_scan_chunked


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "chunk"))
def selective_scan(a, b, C, *, use_pallas: bool = False,
                   interpret: Optional[bool] = None, chunk: int = 128):
    if use_pallas:
        return K.selective_scan(a, b, C, chunk=min(chunk, 64),
                                interpret=interpret)
    B, S, di, ds = a.shape
    h0 = jnp.zeros((B, di, ds), jnp.float32)
    y, h = _ssm_scan_chunked(a, b, C, h0, chunk)
    return y, h


selective_scan_ref = R.selective_scan_ref
