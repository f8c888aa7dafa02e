"""Operations and bytes of the Gaussian blur kernel, from shapes.

One packet blurs an ``n_rows x n_cols`` output tile with a separable
``ksize``-tap filter.  The vertical pass makes ``n_rows x (n_cols +
ksize - 1)`` intermediates and the horizontal pass ``n_rows x n_cols``
outputs, each a ``ksize``-term multiply-add (2 operations a term).  The
least traffic is the padded input tile read once and the output written
once, in float32.  Halo re-reads inside the kernel are not counted: they
are the kernel's cost, not the algorithm's."""
from __future__ import annotations

from typing import Optional, Tuple

# The kernel's events in the device trace: every pattern is in the name.
# The pallas_call carries no name=, so a TPU trace shows it as the
# ``tpu_custom_call`` of its jit (``%_run_tile.1 = f32[...]
# custom-call(...)``); the blur is the cell's only float32 custom call.
TRACE_NAMES = ("tpu_custom_call", "= f32[")
NEEDS_ROW_SUMS = False
ITEMSIZE = 4


def work(n_rows: int, n_cols: int, config: dict,
         row_sum: Optional[float] = None) -> Tuple[float, float]:
    """(operations, bytes) of one ``n_rows x n_cols`` output tile."""
    k = int(config["ksize"])
    ops = 2 * k * n_rows * (n_cols + k - 1) + 2 * k * n_rows * n_cols
    nbytes = ITEMSIZE * ((n_rows + k - 1) * (n_cols + k - 1)
                         + n_rows * n_cols)
    return float(ops), float(nbytes)
