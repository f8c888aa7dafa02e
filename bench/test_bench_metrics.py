"""The readers of the runtime's own counters, ``queue_s``, ``commit_s``
and ``packet_compiles``, on synthetic records and on a traced run of a
cell at its tiny size; each reads nothing from a program that lacks
what it reads."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench import drive, registry
from bench.conftest import tiny
from bench.run import run_cell

NEW = ("queue_s", "commit_s", "packet_compiles")


def _reader(name):
    return registry.load_module("metrics", name).read


def _record(results):
    submits = [SimpleNamespace(result=r, wall_s=0.1) for r in results]
    return drive.RunRecord(setup_s=1.0, submits=submits, compiles=0,
                           chip_groups=[0], config={}, device_kind="cpu")


def test_queue_s_commit_s_and_packet_compiles_read_the_run_results():
    results = [SimpleNamespace(queue_s=0.002, commit_s=0.25, compiles={}),
               SimpleNamespace(queue_s=0.004, commit_s=0.75,
                               compiles={("tpu0", 8): 1, ("host0", 1): 2})]
    run = _record(results)
    assert _reader("queue_s")(run) == pytest.approx(0.003)
    assert _reader("commit_s")(run) == pytest.approx(0.5)
    assert _reader("packet_compiles")(run) == 3.0


@pytest.mark.parametrize("name", NEW)
def test_reader_reads_nothing_the_program_does_not_report(name):
    """A runtime without the counter, or a window with no submit: the
    reader gives None, so a run's line leaves the metric out."""
    assert _reader(name)(_record([SimpleNamespace(sched_wait_s=[0.0])])) \
        is None
    assert _reader(name)(_record([])) is None


def test_traced_run_reads_the_runtimes_counters(cpu):
    """Over the chip-alone binary cell at its tiny size: every submit
    commits, set-up leaves no packet shape to lower, and the breakdown
    names idle after the runtime's spans."""
    cell = tiny(registry.load_cell("gaussian-8192.binary.chip"))
    result, _ = run_cell(cell, 2**33 + 13, 0.3, True, [cpu])
    got = result["metrics"]
    assert set(NEW) <= set(got)
    assert got["commit_s"]["value"] > 0
    assert got["queue_s"]["value"] >= 0
    assert got["packet_compiles"]["value"] == 0
    assert any("> coexec." in name
               for name, _ in result["breakdown"]["idle_gaps"])
