"""Pallas TPU kernel: binomial option pricing, option-tile blocked.

TPU adaptation: OpenCL maps one option per work-group and one tree level
per 255-work-item local array with barriers between backward-induction
steps.  On TPU a grid step prices ``tile`` options at once: options lie on
the lanes and the tree levels on the sublanes of one (256, tile) value
plane, and each induction step is one fused VPU update of the plane —
barriers become data flow.  The step's ``v[j + 1]`` is a sublane rotation
of the plane; the rotation wraps level 255 into level 254, but level 0
after ``steps`` updates reads only levels 0..steps, so the wrapped values
never reach the price.  The per-option tree constants come in from
``ref.tree_coefficients``, the jnp path's own code.
tile=128 options x 256 levels x 4 B = 128 KiB."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.binomial.ref import tree_coefficients

# names the kernel's custom call in the compiled program
KERNEL_NAME = "binomial_tree"


def _binomial_kernel(s0_ref, strike_ref, vdt_ref, pu_ref, pd_ref, disc_ref,
                     out_ref, *, steps: int, levels: int):
    s0 = s0_ref[...]                          # (1, tile)
    strike = strike_ref[...]
    vdt = vdt_ref[...]
    pu = pu_ref[...]
    pd = pd_ref[...]
    disc = disc_ref[...]
    shape = (levels, s0.shape[1])
    j = jax.lax.broadcasted_iota(jnp.int32, shape, 0).astype(jnp.float32)
    sT = s0 * jnp.exp(vdt * (2.0 * j - steps))
    v = jnp.maximum(sT - strike, 0.0)

    def body(_, v):
        up = pltpu.roll(v, levels - 1, 0)     # up[j] = v[j + 1]
        return disc * (pd * v + pu * up)

    v = jax.lax.fori_loop(0, steps, body, v)
    out_ref[...] = v[0:1, :]


def price_options(s0, strike, t_years, *, steps: int = 254,
                  tile: int = 128, interpret: Optional[bool] = None):
    """s0/strike/t_years: (n,) -> (n,) option values; n % tile == 0."""
    n = s0.shape[0]
    assert n % tile == 0, (n, tile)
    levels = -(-(steps + 1) // 8) * 8
    kernel = functools.partial(_binomial_kernel, steps=steps, levels=levels)
    rows = (s0, strike, *tree_coefficients(t_years, steps=steps))
    spec = pl.BlockSpec((1, tile), lambda i: (0, i))
    out = pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[spec] * len(rows),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(*(r.reshape(1, n) for r in rows))
    return out.reshape(n)
