"""Binomial op: jit'd wrapper + range-partitionable entry.
One work-group = LWS options (the paper's one-option-per-work-group with
lws=255 turns into option tiles on TPU)."""
from __future__ import annotations

from functools import partial

import jax
import numpy as np

from repro.kernels.binomial import kernel as K
from repro.kernels.binomial import ref as R

LWS = 128
STEPS = R.STEPS


def make_inputs(n_options: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    s0 = rng.uniform(5.0, 30.0, n_options).astype(np.float32)
    strike = rng.uniform(1.0, 100.0, n_options).astype(np.float32)
    ty = rng.uniform(0.25, 10.0, n_options).astype(np.float32)
    return s0, strike, ty


@partial(jax.jit, static_argnames=("size", "use_pallas"))
def _run(s0, strike, ty, offset, *, size: int, use_pallas: bool):

    def sl(x):
        return jax.lax.dynamic_slice(x, (offset,), (size,))

    a, b, c = sl(s0), sl(strike), sl(ty)
    if use_pallas:
        return K.price_options(a, b, c, steps=STEPS, tile=min(128, size),
                               interpret=False)
    return R.price_options(a, b, c, steps=STEPS)


def run_range(s0, strike, ty, offset: int, size: int, *,
              use_pallas: bool = False):
    """Price options [offset*LWS, (offset+size)*LWS).  ``use_pallas`` picks
    the compiled Pallas kernel (TPU only) over the jnp path."""
    return _run(s0, strike, ty, offset * LWS, size=size * LWS,
                use_pallas=use_pallas)


def total_work(n_options: int) -> int:
    assert n_options % LWS == 0
    return n_options // LWS
