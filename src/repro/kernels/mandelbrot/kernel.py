"""Pallas TPU kernel: Mandelbrot escape iterations, tiled in rows and columns.

TPU adaptation: the OpenCL kernel is one work-item per pixel with early
exit; SIMD lanes on the VPU can't exit one by one, so the kernel iterates
a whole (tile_h, tile_w) tile under a liveness mask — the exact shape a
TPU vector unit wants, small enough that the loop state stays in vector
registers — and the tile exits early instead.  It runs ``CHUNK``
unrolled iterations at a time, and stops once none of its pixels is
still inside the radius, or at ``max_iter``.  The pixel coordinates come
in as a (1, tile_w) row of real parts and a (tile_h, 1) column of
imaginary parts, computed by the jnp path's own ``ref.pixel_centres``, so
one compiled kernel serves every packet of a shape and counts exactly as
the jnp path does.

The exit is exact: the mask freezes an escaped pixel (its z stays outside
the radius, its count stops), so the iterations a dead tile skips cannot
change its output, nor can the ones it runs.  The liveness is reduced
from the state at the start of each chunk, so the reduction runs under
the chunk's iterations rather than stalling the loop on its result; a
tile therefore stops one chunk after its last pixel escaped.  The kernel
never runs past ``max_iter``: the ``(max_iter - 1) % CHUNK`` iterations
that do not fill a chunk run first, right after the peeled first one, and
the chunks then fill ``max_iter`` exactly.  A tile costs its largest
count rounded up to the chunk, plus one chunk, so the irregularity the
paper exploits (work varies per region) shows on the chip too.  The worst
case is a tile wholly inside the set: it runs all ``max_iter``
iterations plus one reduction of |z|^2 over the tile per chunk."""
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
# iterations between two looks at a tile's liveness, unrolled
CHUNK = 64


def _mandel_kernel(cr_ref, ci_ref, out_ref, *, max_iter: int):
    tile_h, tile_w = out_ref.shape
    cr = jnp.broadcast_to(cr_ref[...], (tile_h, tile_w))
    ci = jnp.broadcast_to(ci_ref[...], (tile_h, tile_w))
    n_chunks, head = divmod(max(max_iter - 1, 0), CHUNK)

    def body(_, st):
        zr, zi, cnt = st
        zr2, zi2 = zr * zr, zi * zi
        alive = (zr2 + zi2) <= 4.0
        new_zr = jnp.where(alive, zr2 - zi2 + cr, zr)
        new_zi = jnp.where(alive, 2 * zr * zi + ci, zi)
        return new_zr, new_zi, cnt + alive.astype(jnp.int32)

    def chunk(st):
        k, _, zr, zi, cnt = st
        # liveness at the chunk's start (an escaped pixel's z stays where
        # it left the radius): the reduction overlaps the chunk instead of
        # stalling the loop on its result, and the tile stops one chunk
        # late, on frozen pixels
        live = jnp.min(zr * zr + zi * zi) <= 4.0
        zc = jax.lax.fori_loop(0, CHUNK, body, (zr, zi, cnt), unroll=True)
        return (k + 1, live.astype(jnp.int32), *zc)

    # the first iteration is peeled: from z = 0 every pixel is alive and
    # z becomes c exactly.  Starting the carry from c (not from a constant
    # zero splat) also gives Mosaic a loop-carry layout it can keep.
    cnt = jnp.ones((tile_h, tile_w), jnp.int32)
    zc = jax.lax.fori_loop(0, head, body, (cr, ci, cnt))
    *_, cnt = jax.lax.while_loop(lambda st: (st[0] < n_chunks) & (st[1] > 0),
                                 chunk, (0, jnp.int32(1), *zc))
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
