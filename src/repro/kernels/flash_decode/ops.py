"""Flash-decode op: jit'd wrapper dispatching the Pallas kernel (TPU
target / interpret validation) vs the portable mixed-precision jnp path
used by models/layers.py::cached_decode_attention."""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax

from repro.kernels.flash_decode import kernel as K
from repro.kernels.flash_decode import ref as R
from repro.models.layers import cached_decode_attention


@partial(jax.jit, static_argnames=("use_pallas", "interpret", "bk"))
def decode_attention(q, k_cache, v_cache, pos, *, use_pallas: bool = False,
                     interpret: Optional[bool] = None, bk: int = 512):
    """q: (B,H,D); caches: (B,S,KH,D); pos: () -> (B,H,D)."""
    if use_pallas:
        return K.flash_decode(q, k_cache, v_cache, pos, bk=bk,
                              interpret=interpret)
    out = cached_decode_attention(q[:, None], k_cache, v_cache, pos)
    return out[:, 0]


decode_attention_ref = R.decode_attention_ref
