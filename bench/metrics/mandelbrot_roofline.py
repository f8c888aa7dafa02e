"""The Mandelbrot kernel's share of the chip's roofline, in percent: the
escape iterations the output holds (``work/mandelbrot.py``) at the
chip's peak, over the kernel's device time in the trace.  It needs each
output row's count sum, which the traffic records in traced runs."""
import sys

from bench.roofline import kernel_share

NEEDS_ROW_SUMS = True


def read(run):
    got = kernel_share(run, "mandelbrot")
    if got is None:
        return None
    print(f"mandelbrot_roofline: {got[1]}-bound", file=sys.stderr)
    return got[0]
