"""The four-chip Mandelbrot cell: its readers, ``finish_spread_s`` and
``commit_max_s``, on hand-made records, and the cell at its tiny size
over four virtual CPU devices through the harness's ``run_cell``."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from bench import drive, registry

CELL = "mandelbrot-14336.binary.4chip"


def _reader(name):
    return registry.load_module("metrics", name).read


def _record(results, chip_groups=(0, 1, 2, 3)):
    submits = [SimpleNamespace(result=r, wall_s=0.1) for r in results]
    return drive.RunRecord(setup_s=1.0, submits=submits, compiles=0,
                           chip_groups=list(chip_groups), config={},
                           device_kind="cpu")


def test_finish_spread_s_is_the_mean_gap_between_first_and_last_chip():
    run = _record([SimpleNamespace(device_finish=[0.5, 0.7, 0.6, 0.9]),
                   SimpleNamespace(device_finish=[1.0, 1.0, 1.2, 1.1])])
    assert _reader("finish_spread_s")(run) == pytest.approx(0.3)


def test_finish_spread_s_reads_only_the_chip_groups():
    """A host group after the chips (as in a chip-host fleet) is left
    out; a chip that ran no packet finished at 0."""
    run = _record([SimpleNamespace(device_finish=[0.4, 0.0, 9.0])],
                  chip_groups=(0, 1))
    assert _reader("finish_spread_s")(run) == pytest.approx(0.4)


def test_commit_max_s_is_the_mean_of_each_submits_slowest_device():
    run = _record([SimpleNamespace(device_commit_s=[0.1, 0.4, 0.2, 0.3],
                                   commit_s=1.0),
                   SimpleNamespace(device_commit_s=[0.2, 0.2, 0.6, 0.2],
                                   commit_s=1.2)])
    assert _reader("commit_max_s")(run) == pytest.approx(0.5)


@pytest.mark.parametrize("name", ["finish_spread_s", "commit_max_s"])
def test_reader_reads_nothing_the_program_does_not_report(name):
    """A runtime without the counter (commit_max_s on a parent that has
    no ``device_commit_s``), or a window with no submit: None, so the
    run's line leaves the metric out."""
    assert _reader(name)(_record([SimpleNamespace(commit_s=0.5)])) is None
    assert _reader(name)(_record([])) is None


def test_the_cells_new_metrics_are_its_alone():
    bm = registry.load_json(registry.ROOT / "BENCHMARK.json")
    cell = registry.load_cell(CELL, bm)
    assert cell.chips == 4
    names = {m["name"] for m in cell.per_layer}
    assert {"finish_spread_s", "commit_max_s", "commit_s"} <= names
    for other in bm["workloads"]:
        if other["name"] != CELL:
            got = {m["name"] for m in
                   registry.load_cell(other["name"], bm).per_layer}
            assert not got & {"finish_spread_s", "commit_max_s"}


FOUR_DEVICES = r"""
import json, sys
import jax
from bench import registry
from bench.conftest import tiny
from bench.run import run_cell

chips = jax.devices()
assert len(chips) == 4, chips
cell = tiny(registry.load_cell(sys.argv[1]))
result, _ = run_cell(cell, 2**40 + 17, 0.5, True, chips)
print(json.dumps(result))
"""


def test_cell_runs_over_four_devices_at_its_tiny_size():
    """Four groups, one per virtual CPU device: every answer right, and
    the traced run reports the new metrics next to ``commit_s``, and the
    scheduler's ``balance`` and the build's ``init_s`` of this cell."""
    root = registry.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(root), str(root / "src")]))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES, CELL], cwd=root,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] and result["failed"] == 0, result["checks"]
    assert result["device"]["count"] == 4
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert got["finish_spread_s"] >= 0
    assert 0 < got["commit_max_s"] <= got["commit_s"]
    assert got["init_s"] > 0 and 0 < got["balance"] <= 1
    assert got["packet_compiles"] == 0
