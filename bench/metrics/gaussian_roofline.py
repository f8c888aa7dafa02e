"""The Gaussian blur kernel's share of the chip's roofline, in percent:
the least time its work could take (``work/gaussian.py``, peaks from
``peaks.json``) over its device time in the trace."""
import sys

from bench.roofline import kernel_share


def read(run):
    got = kernel_share(run, "gaussian")
    if got is None:
        return None
    print(f"gaussian_roofline: {got[1]}-bound", file=sys.stderr)
    return got[0]
