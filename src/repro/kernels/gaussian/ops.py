"""Gaussian blur op: jit'd wrapper + range-partitionable co-execution entry.

``run_range(img_padded, w, offset, size)`` computes work-groups
[offset, offset+size) where one work-group = ``lws`` output rows — the unit
the schedulers partition (paper Table I: lws=128).
"""
from __future__ import annotations

from functools import partial

import jax
import numpy as np

from repro.kernels.gaussian import kernel as K
from repro.kernels.gaussian import ref as R

LWS = 128          # output rows per work-group (paper: local work size)
KSIZE = 31


def prepare(img: np.ndarray, ksize: int = KSIZE):
    """Host-side setup: pad once (read-only input buffer)."""
    pad = ksize // 2
    ip = np.pad(img, pad, mode="edge").astype(np.float32)
    w = R.gaussian_weights(ksize)
    return ip, w


@partial(jax.jit, static_argnames=("n_rows", "n_cols", "use_pallas"))
def _run_tile(img_padded, w, row0, col0, *, n_rows: int, n_cols: int,
              use_pallas: bool):
    Ks = w.shape[0]
    block = jax.lax.dynamic_slice(
        img_padded, (row0, col0), (n_rows + Ks - 1, n_cols + Ks - 1))
    if use_pallas:
        return K.blur_rows(block, w, interpret=False)
    tmp = sum(w[k] * block[k:k + n_rows, :] for k in range(Ks))
    return sum(w[k] * tmp[:, k:k + n_cols] for k in range(Ks))


def run_range(img_padded, w, offset: int, size: int, *,
              use_pallas: bool = False):
    """Blur output work-groups [offset, offset+size); returns
    (size*LWS, W) rows.  ``use_pallas`` picks the compiled Pallas
    kernel (TPU only) over the jnp path."""
    n_cols = img_padded.shape[1] - (w.shape[0] - 1)
    return _run_tile(img_padded, w, offset * LWS, 0, n_rows=size * LWS,
                     n_cols=n_cols, use_pallas=use_pallas)


def run_region(img_padded, w, row0: int, n_rows: int,
               col0: int, n_cols: int, *, use_pallas: bool = False):
    """Blur the output tile [row0, row0+n_rows) x [col0, col0+n_cols)
    (the NDRange entry: coordinates in output pixels).  One compiled
    executable serves every same-shape tile — re-offloading an ROI pays
    only the kernel, as the paper's ROI mode requires."""
    return _run_tile(img_padded, w, row0, col0, n_rows=n_rows,
                     n_cols=n_cols, use_pallas=use_pallas)


def total_work(img: np.ndarray) -> int:
    assert img.shape[0] % LWS == 0
    return img.shape[0] // LWS
