"""Flash-attention op: jit'd wrapper, dispatching between the Pallas kernel
(TPU target / interpret validation) and the blocked-jnp path used by the
portable model stack (models/layers.py)."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention import ref as R
from repro.models.layers import blocked_causal_attention


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "chunk"))
def attention(q, k, v, *, use_pallas: bool = False,
              interpret: Optional[bool] = None, chunk: int = 2048):
    if use_pallas:
        return K.flash_attention(q, k, v, interpret=interpret)
    return blocked_causal_attention(q, k, v, chunk)


attention_ref = R.attention_ref
