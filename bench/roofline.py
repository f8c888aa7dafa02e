"""A kernel's share of its roofline on the chip that ran it.

The least time the chip could take for the work is the larger of
operations over peak FLOP/s and bytes over peak bytes/s
(``peaks.json``, keyed by ``device_kind``); the share is that time over
the kernel's measured device time."""
from __future__ import annotations

from typing import Dict, Optional, Tuple

from bench import registry


def peaks(device_kind: str) -> Dict:
    table = registry.load_json(registry.BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def share(ops: float, nbytes: float, kernel_s: float,
          peak: Dict) -> Tuple[float, str]:
    """(percent of the roofline, the bound that applies: "compute" or
    "memory")."""
    if kernel_s <= 0:
        raise ValueError(f"kernel time must be positive, got {kernel_s}")
    t_ops = ops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    bound = "compute" if t_ops >= t_bytes else "memory"
    return 100.0 * max(t_ops, t_bytes) / kernel_s, bound


def kernel_share(run, kernel: str) -> Optional[Tuple[float, str]]:
    """(percent, bound) of ``kernel`` over the traced window of ``run``
    (a ``drive.RunRecord``): the work of every packet that ran on a chip,
    from ``work/<kernel>.py``, against the device time of the kernel's
    events.  None without a trace or without the kernel in it."""
    if run.trace is None:
        return None
    work = registry.load_module("work", kernel)
    t = run.trace.kernel_s(work.TRACE_NAMES)
    if t <= 0:
        return None
    ops = nbytes = 0.0
    for s in run.submits:
        for rows, cols, first in s.packet_tiles(run.chip_groups):
            count = (float(s.row_sums[first:first + rows].sum())
                     if work.NEEDS_ROW_SUMS else None)
            o, b = work.work(rows, cols, run.config, count)
            ops += o
            nbytes += b
    if ops + nbytes <= 0:
        return None
    return share(ops, nbytes, t, peaks(run.device_kind))
