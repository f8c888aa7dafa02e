"""Pallas TPU kernel: Mandelbrot escape iterations, tiled in rows and columns.

TPU adaptation: the OpenCL kernel is one work-item per pixel with early
exit; SIMD lanes on the VPU can't exit early, so the kernel runs the fixed
``max_iter`` loop over a (tile_h, tile_w) tile with a liveness mask — the
exact shape a TPU vector unit wants, small enough that the loop state stays
in vector registers.  The pixel coordinates come in as a (1, tile_w) row of
real parts and a (tile_h, 1) column of imaginary parts, computed by the jnp
path's own ``ref.pixel_centres``, so one compiled kernel serves every
packet of a shape and counts exactly as the jnp path does.  The
irregularity the paper exploits (work varies per region) is flattened
here: every pixel pays all ``max_iter`` iterations, so a packet's cost
depends only on its size.  This is a TPU-vs-GPU behavioural difference;
the co-execution figures model the GPU-style early-exit cost profile in
the simulator (configs/paper_suite.py)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret
from repro.kernels.mandelbrot.ref import pixel_centres

# names the kernel's custom call in the compiled program
KERNEL_NAME = "mandelbrot_escape"


def _mandel_kernel(cr_ref, ci_ref, out_ref, *, max_iter: int):
    tile_h, tile_w = out_ref.shape
    cr = jnp.broadcast_to(cr_ref[...], (tile_h, tile_w))
    ci = jnp.broadcast_to(ci_ref[...], (tile_h, tile_w))

    def body(_, st):
        zr, zi, cnt = st
        zr2, zi2 = zr * zr, zi * zi
        alive = (zr2 + zi2) <= 4.0
        new_zr = jnp.where(alive, zr2 - zi2 + cr, zr)
        new_zi = jnp.where(alive, 2 * zr * zi + ci, zi)
        return new_zr, new_zi, cnt + alive.astype(jnp.int32)

    # the first iteration is peeled: from z = 0 every pixel is alive and
    # z becomes c exactly.  Starting the carry from c (not from a constant
    # zero splat) also gives Mosaic a loop-carry layout it can keep.
    cnt = jnp.ones((tile_h, tile_w), jnp.int32)
    _, _, cnt = jax.lax.fori_loop(1, max_iter, body, (cr, ci, cnt))
    out_ref[...] = cnt if max_iter > 0 else jnp.zeros_like(cnt)


def escape_counts(row0, n_rows: int, width: int, height: int,
                  max_iter: int, *, col0=0, n_cols: int = 0,
                  tile_h: int = 8, tile_w: int = 512,
                  interpret: Optional[bool] = None):
    """Iteration counts for the pixel tile rows [row0, row0+n_rows) x cols
    [col0, col0+n_cols) of a width x height image; n_cols=0 means the full
    width.  ``row0``/``col0`` may be traced.  Ragged edges are computed on
    whole tiles and cropped."""
    n_cols = n_cols or width
    tile_w = min(tile_w, -(-n_cols // 128) * 128)
    Ho = -(-n_rows // tile_h) * tile_h
    Wo = -(-n_cols // tile_w) * tile_w
    xs, ys = pixel_centres(row0, Ho, col0, Wo, width, height)
    out = pl.pallas_call(
        functools.partial(_mandel_kernel, max_iter=max_iter),
        grid=(Ho // tile_h, Wo // tile_w),
        in_specs=[pl.BlockSpec((1, tile_w), lambda i, j: (0, j)),
                  pl.BlockSpec((tile_h, 1), lambda i, j: (i, 0))],
        out_specs=pl.BlockSpec((tile_h, tile_w), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Ho, Wo), jnp.int32),
        interpret=resolve_interpret(interpret),
        name=KERNEL_NAME,
    )(xs.reshape(1, Wo), ys.reshape(Ho, 1))
    return out[:n_rows, :n_cols]
