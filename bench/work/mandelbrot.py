"""Operations and bytes of the Mandelbrot kernel.

The operations are the escape iterations that the output holds: a
pixel's count is the number of iterations in which it was still inside
the radius, and each such iteration costs 8 floating-point operations
(two squares, their sum, ``zr2 - zi2 + cr`` and ``2 zr zi + ci``).  The
count is then the same whatever kernel computes it; iterations a kernel
spends on pixels that have already escaped are not counted.  The bytes
are the int32 counts written and the float32 coordinates read."""
from __future__ import annotations

from typing import Optional, Tuple

# the kernel's events in the device trace (every pattern in the name):
# the pallas_call has no name=, so it shows as its jit's
# ``tpu_custom_call`` with int32 counts out
TRACE_NAMES = ("tpu_custom_call", "= s32[")
NEEDS_ROW_SUMS = True
FLOPS_PER_ITERATION = 8


def work(n_rows: int, n_cols: int, config: dict,
         row_sum: Optional[float] = None) -> Tuple[float, float]:
    """(operations, bytes) of one tile whose counts sum to ``row_sum``."""
    if row_sum is None:
        raise ValueError("mandelbrot work needs the tile's count sum")
    return (float(FLOPS_PER_ITERATION * row_sum),
            float(4 * n_rows * n_cols + 4 * (n_rows + n_cols)))
