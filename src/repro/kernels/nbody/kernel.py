"""Pallas TPU kernel: all-pairs NBody accelerations, target-tile blocked.

TPU adaptation: the OpenCL kernel tiles sources through local memory with
barriers.  Here one grid step owns ``tile_t`` targets laid along the lanes
(a (4, tile_t) block of x, y, z, m rows); sources stream through the second
grid dimension as (tile_s, 4) blocks with bodies on the sublanes.  Each
step forms the (tile_s, tile_t) interaction planes component by component
(2-D values only), reduces them over the sources (sublanes) and adds the
three (1, tile_t) partial accelerations into the (3, tile_t) output block,
which stays resident across the source dimension (the standard Pallas
reduction pattern).  VMEM at 128 x 512: ~6 planes of 256 KiB."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.nbody.ref import EPS2

# names the kernel's custom call in the compiled program
KERNEL_NAME = "nbody_accel"


def _nbody_kernel(tgt_ref, src_ref, out_ref):
    j = pl.program_id(1)
    tgt = tgt_ref[...]                        # (4, tile_t): x, y, z, m rows
    src = src_ref[...]                        # (tile_s, 4): bodies on rows
    dx = src[:, 0:1] - tgt[0:1, :]            # (tile_s, tile_t)
    dy = src[:, 1:2] - tgt[1:2, :]
    dz = src[:, 2:3] - tgt[2:3, :]
    r2 = dx * dx + dy * dy + dz * dz + EPS2
    inv_r3 = jax.lax.rsqrt(r2) / r2 * src[:, 3:4]
    acc = jnp.concatenate([(dx * inv_r3).sum(0, keepdims=True),
                           (dy * inv_r3).sum(0, keepdims=True),
                           (dz * inv_r3).sum(0, keepdims=True)], axis=0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += acc


def accelerations(targets, sources, *, tile_t: int = 512, tile_s: int = 128,
                  interpret: Optional[bool] = None):
    """targets: (T, 4); sources: (N, 4) -> (T, 3).  Targets are padded to
    whole tiles (results cropped) and sources with zero-mass bodies, which
    add exactly nothing."""
    T = targets.shape[0]
    N = sources.shape[0]
    if T <= tile_t:
        tile_t = T                            # one block spans all targets
    else:
        assert tile_t % 128 == 0, tile_t
    Tp = -(-T // tile_t) * tile_t
    Np = -(-N // tile_s) * tile_s
    tgt = jnp.pad(targets, ((0, Tp - T), (0, 0))).T
    src = jnp.pad(sources, ((0, Np - N), (0, 0)))
    acc = pl.pallas_call(
        _nbody_kernel,
        grid=(Tp // tile_t, Np // tile_s),
        in_specs=[
            pl.BlockSpec((4, tile_t), lambda i, j: (0, i)),
            pl.BlockSpec((tile_s, 4), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((3, tile_t), lambda i, j: (0, i)),
        out_shape=jax.ShapeDtypeStruct((3, Tp), jnp.float32),
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(tgt, src)
    return acc.T[:T]
