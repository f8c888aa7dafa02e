"""``bench/trace.py`` on synthetic intervals and on a small trace
recorded on the CPU (``testdata/cpu_trace.xplane.pb``, made by
``testdata/make_cpu_trace.py``)."""
from __future__ import annotations

import numpy as np
import pytest

from bench import trace as T
from bench.registry import BENCH

RECORDED = str(BENCH / "testdata" / "cpu_trace.xplane.pb")


def test_union_merges_overlaps_and_keeps_disjoint_intervals():
    assert T.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == \
        [[0, 4], [5, 7], [9, 9]]


def test_gaps_are_the_window_minus_busy():
    busy = [[2, 4], [6, 7]]
    assert T.gaps(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert T.gaps(busy, 3, 6.5) == [(4, 6)]
    assert T.gaps([], 0, 1) == [(0, 1)]


def test_points_take_the_innermost_spans_around_them():
    host = [(0, 100, "bench.window"), (10, 50, "bench.result"),
            (20, 30, "bench.sample"), (12, 40, "Transfer"),
            (14, 16, "Copy"), (60, 70, "Other")]
    names = T.name_points(np.array([25.0, 15.0, 45.0, 65.0, 90.0]), host)
    assert names == ["bench.sample > Transfer", "bench.result > Copy",
                     "bench.result", "outside bench spans > Other",
                     "outside bench spans"]


def test_summary_of_synthetic_events():
    ev = T.Events(
        devices={"/device:TPU:0": [(10, 20, "fusion"), (15, 30, "k"),
                                   (50, 60, "k"), (95, 120, "late")]},
        host=[(0, 100, "bench.window"), (30, 50, "bench.sample")])
    s = T.summarize(ev)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(35e-9)        # 10-30, 50-60, 95-100
    assert s.idle_share == pytest.approx(0.65)
    assert s.kernel_s(["k"]) == pytest.approx(25e-9)
    assert s.op_s["late"] == pytest.approx(5e-9)   # clipped to the window
    gaps = dict(s.breakdown()["idle_gaps"])
    assert gaps == pytest.approx({"outside bench spans": 45e-9,
                                  "bench.sample": 20e-9})


def test_no_device_op_gives_no_summary():
    assert T.summarize(T.Events(host=[(0, 1, "bench.window")])) is None


def test_recorded_cpu_trace_reduces_to_its_window_and_idle_gaps():
    ev = T.load(RECORDED, "cpu")
    s = T.summarize(ev)
    spans = {n for _, _, n in ev.host if n.startswith("bench.")}
    assert spans == {"bench.window", "bench.submit", "bench.sample"}
    # three 30 ms sleeps and a 10 ms one inside the window
    assert 0.1 <= s.window_s <= 0.5
    assert 0 < s.busy_s < s.window_s - 0.09
    assert s.n_devices == 1
    assert s.kernel_s(["dot_general"]) > 0
    ops = dict(s.breakdown()["device_ops"])
    assert max(ops, key=ops.get).startswith("dot_general")
    longest = s.breakdown()["idle_gaps"][0]
    assert longest[0] == "bench.sample" and longest[1] >= 0.09
    assert not any("::" in n for n in ops)
