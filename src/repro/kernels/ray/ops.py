"""Ray op: jit'd wrapper + range-partitionable entry (lws=128 -> one
work-group = 1 pixel row; paper scene sizes 4096px)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.ray import ref as R

LWS = 4            # rows per work-group


@partial(jax.jit, static_argnames=("n_rows", "width", "height"))
def _run(centers, radii, colors, row0, *, n_rows: int, width: int,
         height: int):
    scene = {"centers": centers, "radii": radii, "colors": colors}
    return R.render_rows(scene, row0, n_rows, width, height)


def run_range(scene, offset: int, size: int, *, width: int, height: int,
              **_):
    return _run(scene["centers"], scene["radii"], scene["colors"],
                offset * LWS, n_rows=size * LWS, width=width,
                height=height)


@partial(jax.jit, static_argnames=("n_rows", "n_cols", "width", "height"))
def _run_tile(centers, radii, colors, row0, col0, *, n_rows: int,
              n_cols: int, width: int, height: int):
    scene = {"centers": centers, "radii": radii, "colors": colors}
    return R.render_rows(scene, row0, n_rows, width, height,
                         col0=col0, n_cols=n_cols)


def run_region(scene, row0: int, n_rows: int, col0: int, n_cols: int, *,
               width: int, height: int):
    """Render the pixel tile [row0, row0+n_rows) x [col0, col0+n_cols)
    -> (n_rows, n_cols, 3) (the NDRange entry, coordinates in pixels)."""
    return _run_tile(scene["centers"], scene["radii"], scene["colors"],
                     row0, col0, n_rows=n_rows,
                     n_cols=n_cols, width=width, height=height)


def total_work(height: int) -> int:
    assert height % LWS == 0
    return height // LWS
