"""Pallas TPU kernel: separable Gaussian blur, tiled in rows and columns.

TPU adaptation (vs the OpenCL per-pixel NDRange): one grid step produces a
``tile_h x tile_w`` output tile.  The filter needs a K-1 halo below and to
the right of the tile; Pallas blocks do not overlap, so the kernel reads
the padded image four times — the tile itself, an ``hr``-row strip below
it, an ``hc``-column strip to its right and the ``hr x hc`` corner (``hr``
and ``hc`` are K-1 rounded up to the (8, 128) tiling) — and assembles the
``(tile_h + hr) x (tile_w + hc)`` band in VMEM.  Both passes slide within
the band with static slices => unrolled VPU vector ops.  The 1-D weights
sit in SMEM.  Scoped VMEM at the defaults (64 x 512 tile, 31-tap filter):
the double-buffered input and output blocks (~0.8 MiB) plus the band,
vertical-pass and output values (~0.6 MiB), whatever the image width.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

# names the kernel's custom call in the compiled program
KERNEL_NAME = "gaussian_blur"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _blur_kernel(cur_ref, below_ref, right_ref, corner_ref, w_ref, out_ref,
                 *, K: int):
    tile_h, tile_w = out_ref.shape
    band = jnp.concatenate([
        jnp.concatenate([cur_ref[...], right_ref[...]], axis=1),
        jnp.concatenate([below_ref[...], corner_ref[...]], axis=1)], axis=0)
    tmp = jnp.zeros((tile_h, band.shape[1]), jnp.float32)
    for k in range(K):                       # vertical pass (static unroll)
        tmp = tmp + w_ref[k] * band[k:k + tile_h, :]
    out = jnp.zeros((tile_h, tile_w), jnp.float32)
    for k in range(K):                       # horizontal pass
        out = out + w_ref[k] * tmp[:, k:k + tile_w]
    out_ref[...] = out


def blur_rows(img_padded, w1d, *, tile_h: int = 64, tile_w: int = 512,
              interpret: Optional[bool] = None):
    """img_padded: (H + K - 1, W + K - 1) with edge padding; returns (H, W).
    Any H and W: the image is zero-extended to whole tiles and the result
    cropped."""
    K = w1d.shape[0]
    Hp, Wp = img_padded.shape
    H, W = Hp - (K - 1), Wp - (K - 1)
    hr = _round_up(max(K - 1, 1), 8)         # halo rows, sublane-aligned
    hc = _round_up(max(K - 1, 1), 128)       # halo columns, lane-aligned
    tile_h = _round_up(min(tile_h, H), hr)
    tile_w = _round_up(min(tile_w, W), hc)
    Ho, Wo = _round_up(H, tile_h), _round_up(W, tile_w)
    img = jnp.pad(img_padded, ((0, Ho + hr - Hp), (0, Wo + hc - Wp)))
    rh, rw = tile_h // hr, tile_w // hc      # tile size in halo blocks
    out = pl.pallas_call(
        functools.partial(_blur_kernel, K=K),
        grid=(Ho // tile_h, Wo // tile_w),
        in_specs=[
            pl.BlockSpec((tile_h, tile_w), lambda i, j: (i, j)),
            pl.BlockSpec((hr, tile_w), lambda i, j: ((i + 1) * rh, j)),
            pl.BlockSpec((tile_h, hc), lambda i, j: (i, (j + 1) * rw)),
            pl.BlockSpec((hr, hc), lambda i, j: ((i + 1) * rh, (j + 1) * rw)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((tile_h, tile_w), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Ho, Wo), jnp.float32),
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(img, img, img, img, w1d)
    return out[:H, :W]
