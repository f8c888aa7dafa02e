"""Plain reference of the Mandelbrot escape counts: every pixel of the
requested rows iterated ``max_iter`` times from z = 0 in
straightforward ``jax.numpy``, in blocks of rows, on the default device.
The view is fixed (the AMD APP SDK sample's), so the output does not
depend on the seed.  It shares no code with the program.

``compare`` gives one number, ``row_mismatch``: the largest share of
pixels in one sampled row whose count differs from the reference's.  A
count is discontinuous in c: one ulp of difference moves boundary pixels
by many iterations, so the number is a share of pixels, not a gap."""
from __future__ import annotations

from functools import partial
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

X0, X1 = -2.25, 0.75
Y0, Y1 = -1.5, 1.5
BLOCK = 64          # rows per reference call


@partial(jax.jit, static_argnames=("width", "height", "max_iter", "dtype"))
def _counts(rows, *, width: int, height: int, max_iter: int, dtype):
    dt = jnp.dtype(dtype)
    ci = (Y0 + (Y1 - Y0) * (rows + 0.5) / height).astype(dt)
    cr = (X0 + (X1 - X0) * (jnp.arange(width) + 0.5) / width).astype(dt)
    ci = jnp.broadcast_to(ci[:, None], (rows.shape[0], width))
    cr = jnp.broadcast_to(cr[None, :], (rows.shape[0], width))

    def body(_, st):
        zr, zi, cnt = st
        zr2, zi2 = zr * zr, zi * zi
        alive = (zr2 + zi2) <= 4.0
        zr_next = jnp.where(alive, zr2 - zi2 + cr, zr)
        zi_next = jnp.where(alive, 2 * zr * zi + ci, zi)
        return zr_next, zi_next, cnt + alive.astype(jnp.int32)

    zero = jnp.zeros_like(cr)
    _, _, cnt = jax.lax.fori_loop(
        0, max_iter, body, (zero, zero, jnp.zeros(cr.shape, jnp.int32)))
    return cnt


class Reference:
    """Escape counts of any rows of the image, computed in ``dtype``."""

    def __init__(self, config: Dict, seed: int, dtype: str = "float32"):
        self.height, self.width = config["image"]
        self.max_iter = int(config["max_iter"])
        self.dtype = dtype

    def rows(self, rows: np.ndarray) -> np.ndarray:
        rows = np.asarray(rows, np.int32)
        out = np.empty((len(rows), self.width), np.int32)
        for b in range(0, len(rows), BLOCK):
            blk = rows[b:b + BLOCK]
            padded = np.zeros(BLOCK, np.int32)
            padded[:len(blk)] = blk
            cnt = _counts(jnp.asarray(padded), width=self.width,
                          height=self.height, max_iter=self.max_iter,
                          dtype=self.dtype)
            out[b:b + len(blk)] = np.asarray(cnt)[:len(blk)]
        return out


def compare(pairs: Iterable[Tuple[np.ndarray, np.ndarray]]) -> Dict:
    """``row_mismatch``: the largest share of one row's pixels whose
    count differs from the reference's, over every (got, want) pair."""
    worst = 0.0
    for got, want in pairs:
        if got.size:
            worst = max(worst, float((got != want).mean(axis=-1).max()))
    return {"row_mismatch": worst}
