"""Pure-jnp oracle for Mandelbrot (paper Table I: lws=256, 14336px,
5000 max iterations, 4:1 out pattern, irregular workload)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

# view window matching the classic AMD APP SDK sample
X0, X1 = -2.25, 0.75
Y0, Y1 = -1.5, 1.5


def pixel_centres(row0, n_rows: int, col0, n_cols: int, width: int,
                  height: int):
    """c of the tile's pixel centres: real parts ``xs`` (n_cols,) and
    imaginary parts ``ys`` (n_rows,).  The Pallas kernel takes them from
    here too: Mosaic and XLA need not round this arithmetic alike, and one
    ulp of c changes the escape count of pixels near the set's boundary."""
    ys = Y0 + (Y1 - Y0) * (jnp.arange(n_rows) + row0 + 0.5) / height
    xs = X0 + (X1 - X0) * (jnp.arange(n_cols) + col0 + 0.5) / width
    return xs, ys


def escape_counts(row0: int, n_rows: int, width: int, height: int,
                  max_iter: int, col0: int = 0, n_cols: int = 0):
    """Iteration counts for the pixel tile rows [row0, row0+n_rows) x
    cols [col0, col0+n_cols); n_cols=0 means the full width."""
    if not n_cols:
        n_cols = width
    xs, ys = pixel_centres(row0, n_rows, col0, n_cols, width, height)
    cr = jnp.broadcast_to(xs[None, :], (n_rows, n_cols))
    ci = jnp.broadcast_to(ys[:, None], (n_rows, n_cols))

    def body(_, st):
        zr, zi, cnt = st
        zr2, zi2 = zr * zr, zi * zi
        alive = (zr2 + zi2) <= 4.0
        new_zr = jnp.where(alive, zr2 - zi2 + cr, zr)
        new_zi = jnp.where(alive, 2 * zr * zi + ci, zi)
        return new_zr, new_zi, cnt + alive.astype(jnp.int32)

    zr = jnp.zeros_like(cr)
    zi = jnp.zeros_like(ci)
    cnt = jnp.zeros(cr.shape, jnp.int32)
    zr, zi, cnt = jax.lax.fori_loop(0, max_iter, body, (zr, zi, cnt))
    return cnt
