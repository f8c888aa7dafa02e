"""Reduce a JAX profiler trace to device busy and idle time, time per
device operation, and idle gaps named by what the host was doing.

Device operations are the events on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  (A CPU backend has no device plane; there
the operations are the events of the XLA CPU client's threads, which is
what the recorded test trace uses.)  Busy time is the union of a
device's operation intervals inside the traced window, the span the
harness names ``bench.window``; it is averaged over the devices that ran
anything.  Each gap between busy intervals is named after the innermost
``bench.*`` span around its midpoint, and the innermost other host event
there, if any: the harness puts those spans around its own submit,
result wait and sampling.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
NAMED_GAPS = 2000          # longest gaps named for the breakdown
TOP = 10                   # entries of each breakdown list

Interval = Tuple[float, float, str]        # (start_ns, end_ns, name)


@contextlib.contextmanager
def record(log_dir: str):
    """Profile the body into ``log_dir``, without the Python tracer."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


@dataclass
class Events:
    """The parts of a trace the reduction reads, as plain intervals."""
    devices: Dict[str, List[Interval]] = field(default_factory=dict)
    host: List[Interval] = field(default_factory=list)


def _is_cpu_op(line: str, name: str, dur: float) -> bool:
    """An XLA op on a CPU client thread (not the runtime's own events,
    which are named ``Class::method``, nor an op's ``end:`` marker)."""
    return (line.startswith("tf_XLAPjRtCpuClient") and dur > 0
            and "::" not in name and not name.startswith("end: "))


def load(path: str, platform: str) -> Events:
    """Read an ``.xplane.pb`` file; ``platform`` picks the device planes
    ("tpu": the TPU planes' ``XLA Ops``; "cpu": the CPU client)."""
    from jax.profiler import ProfileData
    ev = Events()
    for plane in ProfileData.from_file(path).planes:
        pname = plane.name
        if platform == "tpu" and pname.startswith("/device:TPU:"):
            ops = ev.devices.setdefault(pname, [])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.end_ns, e.name)
                               for e in line.events)
        elif pname.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    iv = (e.start_ns, e.end_ns, e.name)
                    ev.host.append(iv)
                    if platform == "cpu" and _is_cpu_op(
                            line.name, e.name, e.duration_ns):
                        ev.devices.setdefault(pname, []).append(iv)
    ev.devices = {k: v for k, v in ev.devices.items() if v}
    return ev


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merge intervals into disjoint, sorted ``[start, end]`` pairs."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: Sequence[Sequence[float]], w0: float,
         w1: float) -> List[Tuple[float, float]]:
    """The idle intervals of ``[w0, w1]`` between disjoint busy pairs."""
    out, t = [], w0
    for s, e in busy:
        if s > t:
            out.append((t, min(s, w1)))
        t = max(t, e)
        if t >= w1:
            break
    if t < w1:
        out.append((t, w1))
    return [g for g in out if g[1] > g[0]]


def name_points(points: np.ndarray, host: Sequence[Interval]) -> List[str]:
    """For each time in ``points``: the innermost ``bench.*`` span and
    the innermost other host event that contain it, as ``a > b``."""
    order = np.argsort(points)
    pts = points[order]
    best = {"bench": (np.full(len(pts), np.inf), [""] * len(pts)),
            "host": (np.full(len(pts), np.inf), [""] * len(pts))}
    for s, e, name in host:
        lo, hi = np.searchsorted(pts, s), np.searchsorted(pts, e,
                                                          side="right")
        if lo >= hi:
            continue
        if name == WINDOW_SPAN:
            continue
        dur, names = best["bench" if name.startswith("bench.") else "host"]
        for j in np.nonzero(e - s < dur[lo:hi])[0] + lo:
            dur[j] = e - s
            names[j] = name
    out = [""] * len(pts)
    for j, k in enumerate(order):
        b, h = best["bench"][1][j], best["host"][1][j]
        out[k] = " > ".join(x for x in (b or "outside bench spans", h) if x)
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float                    # mean over devices that ran an op
    n_devices: int
    op_s: Dict[str, float]           # device seconds per op name
    idle_gaps: List[List]            # [[host activity, idle seconds]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, patterns: Sequence[str]) -> float:
        """Device seconds of ops whose name contains every pattern."""
        return sum(t for n, t in self.op_s.items()
                   if all(p in n for p in patterns))

    def breakdown(self) -> Dict:
        ops: Dict[str, float] = {}
        for n, t in self.op_s.items():
            ops[short_op(n)] = ops.get(short_op(n), 0.0) + t
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, t] for n, t in top],
                "idle_gaps": self.idle_gaps[:TOP]}


_HLO = re.compile(r"^(%[\w.\-]+) = ([a-z0-9]+\[[\d,]*\])\S* ([\w\-]+)\(")


def short_op(name: str) -> str:
    """``%name shape op`` of an HLO instruction's text (a TPU trace names
    each op by its whole instruction), else the name itself."""
    m = _HLO.match(name)
    return " ".join(m.groups()) if m else name[:120]


def summarize(ev: Events) -> Optional[Summary]:
    """Busy, idle and per-op time inside the ``bench.window`` span (the
    whole trace when the span is missing); None when no device op ran."""
    if not ev.devices:
        return None
    spans = [iv for iv in ev.host if iv[2] == WINDOW_SPAN]
    if spans:
        w0, w1 = spans[0][0], spans[0][1]
    else:
        allops = [iv for ops in ev.devices.values() for iv in ops]
        w0, w1 = min(iv[0] for iv in allops), max(iv[1] for iv in allops)
    busy_total, op_s, all_gaps = 0.0, {}, []
    n = 0
    for ops in ev.devices.values():
        inside = [(max(s, w0), min(e, w1), nm) for s, e, nm in ops
                  if e > w0 and s < w1]
        if not inside:
            continue
        n += 1
        for s, e, nm in inside:
            op_s[nm] = op_s.get(nm, 0.0) + (e - s) * 1e-9
        busy = union((s, e) for s, e, _ in inside)
        busy_total += sum(e - s for s, e in busy)
        all_gaps.extend(gaps(busy, w0, w1))
    if n == 0:
        return None
    all_gaps.sort(key=lambda g: g[0] - g[1])
    named = all_gaps[:NAMED_GAPS]
    mids = np.array([(s + e) / 2 for s, e in named], np.float64)
    idle: Dict[str, float] = {}
    for (s, e), nm in zip(named, name_points(mids, ev.host)):
        idle[nm] = idle.get(nm, 0.0) + (e - s) * 1e-9
    return Summary(window_s=(w1 - w0) * 1e-9,
                   busy_s=busy_total / n * 1e-9, n_devices=n, op_s=op_s,
                   idle_gaps=[[k, v] for k, v in
                              sorted(idle.items(), key=lambda kv: -kv[1])])
