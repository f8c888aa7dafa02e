"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference in the next lower precision, in the
program's place) fails a limit.  Runs the harness on the CPU at a tiny
size, past its look for a chip."""
from __future__ import annotations

import numpy as np
import pytest

from bench import check, drive, registry
from bench.conftest import tiny
from bench.run import run_cell

GAUSS = "gaussian-8192.binary.chip"
MANDEL = "mandelbrot-14336.binary.chip"
ROI = "gaussian-8192.roi.chip"
HOST = "gaussian-8192.binary.chip-host"


def _failing(result):
    return {k for k, v in result["checks"].items()
            if not v["value"] <= v["limit"]}


def _altered(monkeypatch, cell):
    """Every packet's answer altered where the kernel produces it."""
    from repro.core import programs as P
    adapter = cell.config["programs"][cell.traffic["mode"]]["adapter"]
    make = P.PROGRAMS[adapter]

    def broken(**kw):
        prog = make(**kw)
        build = prog.build

        def bad_build(dev):
            fn = build(dev)
            return lambda *a: fn(*a) + (1 if prog.out_dtype == np.int32
                                        else 1e-2)
        prog.build = bad_build
        return prog
    monkeypatch.setitem(P.PROGRAMS, adapter, broken)


def _half_left_out(monkeypatch, cell):
    """The runtime leaves the second half of the work-groups out: their
    packets are dropped and their rows never written."""
    from repro.core import runtime
    execute = runtime._RunContext.execute

    def broken(self):
        res = execute(self)
        half = self.run_region.dims[0].size // 2
        rows_per_unit = self.program.out_rows_per_wg
        res.packets = [p for p in res.packets if p.offset < half]
        res.output[half * rows_per_unit:] = 0
        return res
    monkeypatch.setattr(runtime._RunContext, "execute", broken)


def _stale_output(monkeypatch, cell):
    """Each submit hands back the previous submit's output."""
    from repro.core import runtime
    execute = runtime._RunContext.execute
    last = {}

    def broken(self):
        res = execute(self)
        prev = last.get("out")
        last["out"] = np.array(res.output, copy=True)
        if prev is not None and prev.shape == res.output.shape:
            res.output[...] = prev
        return res
    monkeypatch.setattr(runtime._RunContext, "execute", broken)


# the mandelbrot view is fixed in its program, so every submit has the
# same answer and a stale one cannot show there
FAULTS = [(GAUSS, _altered), (MANDEL, _altered), (HOST, _altered),
          (ROI, _altered), (GAUSS, _half_left_out),
          (MANDEL, _half_left_out), (ROI, _half_left_out),
          (ROI, _stale_output), (GAUSS, _stale_output),
          (HOST, _stale_output)]


@pytest.mark.parametrize("name", [GAUSS, MANDEL, HOST, ROI])
def test_sound_run_is_correct(name, cpu):
    cell = tiny(registry.load_cell(name))
    result, _ = run_cell(cell, 2**35 + 1, 0.3, False, [cpu], cpu)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0


@pytest.mark.parametrize("name,fault", FAULTS,
                         ids=[f"{n}-{f.__name__[1:]}" for n, f in FAULTS])
def test_broken_timed_path_is_not_correct(name, fault, cpu, monkeypatch):
    cell = tiny(registry.load_cell(name))
    fault(monkeypatch, cell)
    result, lines = run_cell(cell, 2**35 + 2, 0.3, False, [cpu], cpu)
    assert not result["correct"]
    assert _failing(result), result["checks"]
    assert any(line.startswith("check ") for line in lines)


@pytest.mark.parametrize("name", [GAUSS, MANDEL])
def test_control_fails_a_limit(name, cpu):
    cell = tiny(registry.load_cell(name))
    groups = drive.build_fleet(cell.traffic, [cpu], cpu)
    traffic = drive.Traffic(cell.config, cell.traffic, groups, 5)
    try:
        traffic.setup()
        submits, _ = traffic.window(0.2)
    finally:
        traffic.close()
    program = check.numbers(cell.config, submits, [])
    control = check.control_numbers(cell.config, submits)
    limits = check.limits(cell.config)
    assert all(program[k] <= limits[k] for k in program)
    assert any(v > limits[k] for k, v in control.items())
