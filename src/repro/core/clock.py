"""The runtime's timing: phase marks on the host clock, spans on the
profiler's clock, and the compiles each packet paid.

* :class:`PhaseClock` stamps one run's phase boundaries, read back as
  ``RunResult.phases``; its ``phase`` marks also bound the run's phase
  spans.
* :func:`span` puts one of the runtime's steps into the JAX profiler
  trace as a named range (``coexec.*``), so that it shares the device
  trace's clock.  Every span the runtime opens goes through it.  With no
  profiler recording, a span costs one ``TraceMe`` construction, about a
  microsecond.
* :class:`Tally` sums the seconds of one step over a run, from any
  thread: the run's commit time (``RunResult.commit_s``) is the summed
  length of its ``coexec.commit`` spans.
* :func:`count_compiles` and :func:`packet_compiles` attribute jit
  lowerings to the packet dispatched on the calling thread: one
  process-wide ``jax.monitoring`` listener adds each lowering to the
  counts of the (group, packet size) in scope on that thread.  Lowerings
  outside any packet are not counted.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, Optional, Tuple

import jax

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def span(name: str, **meta) -> jax.profiler.TraceAnnotation:
    """A profiler range around one runtime step, opened and closed on the
    thread that does the work.  ``meta`` (e.g. ``group``, ``size``) lands
    as stats on the trace event; the event's name stays ``name``."""
    return jax.profiler.TraceAnnotation(name, **meta)


class PhaseClock:
    """Named wall-clock marks for one run's phase accounting.

    The runtime's single timing implementation: every phase boundary is a
    ``mark``; durations are read back with ``between``/``since``.  Unset
    marks read as 0.0 so partial runs (e.g. scheduler construction
    failures) never crash the accounting path.
    """

    def __init__(self):
        self._t: Dict[str, float] = {}
        self._once = threading.Lock()
        self._span: Optional[jax.profiler.TraceAnnotation] = None

    def mark(self, name: str) -> float:
        t = time.perf_counter()
        self._t[name] = t
        return t

    def phase(self, name: str, opens: Optional[str] = None) -> float:
        """``mark(name)`` at a phase boundary: the phase span opened by
        the previous ``phase`` ends here and ``opens`` (a span name), if
        given, starts here, so the trace's phase spans are the windows
        that ``between`` reads.  Every ``phase`` of a clock is called on
        one thread."""
        self.close()
        t = self.mark(name)
        if opens is not None:
            self._span = span(opens)
            self._span.__enter__()
        return t

    def close(self) -> None:
        """End the open phase span, if any (a run that raised)."""
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def mark_once(self, name: str) -> float:
        """Set ``name`` only if unset (first caller wins; thread-safe) —
        e.g. the ROI mark stamped by whichever device computes first."""
        with self._once:
            t = self._t.get(name)
            if t is None:
                t = self.mark(name)
            return t

    def at(self, name: str) -> Optional[float]:
        return self._t.get(name)

    def since(self, name: str) -> float:
        t = self._t.get(name)
        return 0.0 if t is None else time.perf_counter() - t

    def between(self, a: str, b: str) -> float:
        ta, tb = self._t.get(a), self._t.get(b)
        if ta is None or tb is None:
            return 0.0
        return max(0.0, tb - ta)


class Tally:
    """Seconds summed over many timed steps, added from any thread."""

    def __init__(self):
        self.total_s = 0.0
        self._lock = threading.Lock()

    def add(self, seconds: float) -> None:
        with self._lock:
            self.total_s += seconds


PacketKey = Tuple[str, int]                 # (group name, packet size)


class _Scope(threading.local):
    counts: Optional[Dict[PacketKey, int]] = None   # the run's, this thread
    key: Optional[PacketKey] = None                 # packet in dispatch


_scope = _Scope()


def _on_duration(event: str, duration: float, **_) -> None:
    if event == LOWERING:
        counts, key = _scope.counts, _scope.key
        if counts is not None and key is not None:
            counts[key] = counts.get(key, 0) + 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


@contextlib.contextmanager
def count_compiles(counts: Dict[PacketKey, int]) -> Iterator[None]:
    """Add the lowerings of every packet this thread dispatches inside the
    block to ``counts``."""
    _scope.counts = counts
    try:
        yield
    finally:
        _scope.counts = None


@contextlib.contextmanager
def packet_compiles(group: str, size: int) -> Iterator[None]:
    """Charge lowerings on this thread inside the block to the packet
    ``(group, size)``."""
    _scope.key = (group, size)
    try:
        yield
    finally:
        _scope.key = None
