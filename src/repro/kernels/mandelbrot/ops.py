"""Mandelbrot op: jit'd wrapper + range-partitionable entries.  One
work-group = LWS image rows (the paper's lws=256 work-items become row
blocks of the 14336px image).

The image is computed from scalars alone, so the tile origin is placed on
the caller's ``device`` explicitly: it is the only array argument, and it
decides where the compiled kernel runs.

The tile's row count is static, so every distinct count is an executable
of its own on each device.  ``run_range`` therefore runs a packet as
pieces whose lengths are powers of two, the binary digits of its size:
whatever sizes a scheduler carves out of ``n`` work-groups, a device
compiles at most ``ceil(log2 n) + 1`` shapes, and no row is computed or
copied back twice."""
from __future__ import annotations

from functools import partial
from typing import List, Tuple

import jax
import numpy as np

from repro.kernels.mandelbrot import kernel as K
from repro.kernels.mandelbrot import ref as R

LWS = 8            # rows per work-group (alignment unit for packets)
MAX_ITER = 5000


@partial(jax.jit, static_argnames=("n_rows", "n_cols", "width", "height",
                                   "max_iter", "use_pallas"))
def _run_tile(origin, *, n_rows: int, n_cols: int, width: int,
              height: int, max_iter: int, use_pallas: bool):
    fn = partial(K.escape_counts, interpret=False) if use_pallas \
        else R.escape_counts
    return fn(origin[0], n_rows, width, height, max_iter,
              col0=origin[1], n_cols=n_cols)


def run_region(row0: int, n_rows: int, col0: int, n_cols: int, *,
               width: int, height: int, max_iter: int = MAX_ITER,
               use_pallas: bool = False, device=None):
    """Escape counts for the pixel tile [row0, row0+n_rows) x
    [col0, col0+n_cols) (the NDRange entry, coordinates in pixels), run on
    ``device`` (None: the default device).  ``use_pallas`` picks the
    compiled Pallas kernel (TPU only) over the jnp path."""
    origin = jax.device_put(np.asarray([row0, col0], np.int32), device)
    return _run_tile(origin, n_rows=n_rows, n_cols=n_cols, width=width,
                     height=height, max_iter=max_iter,
                     use_pallas=use_pallas)


def pieces(size: int) -> List[Tuple[int, int]]:
    """(start, length) of the power-of-two pieces of ``size``
    work-groups, largest first: 897 is 512 + 256 + 128 + 1."""
    size, out, start = int(size), [], 0
    for bit in reversed(range(size.bit_length())):
        if size >> bit & 1:
            out.append((start, 1 << bit))
            start += 1 << bit
    return out


def run_range(offset: int, size: int, *, width: int, height: int,
              max_iter: int = MAX_ITER, use_pallas: bool = False,
              device=None) -> Tuple:
    """Escape counts of work-groups [offset, offset+size), full rows, as a
    tuple of row pieces in row order (``pieces``).  The pieces launch
    back to back, with no sync between them."""
    return tuple(run_region((offset + start) * LWS, length * LWS, 0, width,
                            width=width, height=height, max_iter=max_iter,
                            use_pallas=use_pallas, device=device)
                 for start, length in pieces(size))


def total_work(height: int) -> int:
    assert height % LWS == 0
    return height // LWS
