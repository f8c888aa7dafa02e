"""The harness on the CPU: every cell of BENCHMARK.json resolves by
name, every traffic drives its program at a tiny size, the command
refuses to run without a TPU, and a new cell, configuration, traffic or
metric needs only new files and entries."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import check, drive, registry
from bench.conftest import tiny, workload_names
from bench.run import run_cell

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BM = registry.load_json(registry.ROOT / "BENCHMARK.json")


def test_benchmark_json_keeps_the_contract_shape():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["command"] == ["python3", "bench/run.py"]
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    cells = [w["name"] for w in BM["workloads"]]
    assert len(set(cells)) == len(cells)
    for w in BM["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        cell = registry.load_cell(w["name"], BM)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


@pytest.mark.parametrize("name", workload_names())
def test_every_name_resolves_to_its_file(name):
    cell = registry.load_cell(name)
    configs = {c["name"]: c for c in BM["configs"]}
    w = next(w for w in BM["workloads"] if w["name"] == name)
    assert configs[w["config"]]["file"].startswith("bench/configs/")
    assert (registry.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    ref = registry.load_module("reference", cell.config["reference"])
    assert callable(ref.compare) and callable(ref.Reference)
    for m in cell.end_to_end + cell.per_layer:
        assert callable(registry.load_module("metrics", m["name"]).read)
        if m["name"].endswith("_roofline"):
            kernel = m["name"][:-len("_roofline")]
            work = registry.load_module("work", kernel)
            assert work.TRACE_NAMES and callable(work.work)
    assert set(check.limits(cell.config)) >= {"failed", "wg_not_once"}


@pytest.mark.parametrize("name", workload_names())
def test_traffic_drives_its_program_on_cpu(name, cpu):
    cell = tiny(registry.load_cell(name))
    groups = drive.build_fleet(cell.traffic, [cpu], cpu)
    traffic = drive.Traffic(cell.config, cell.traffic, groups, 2**40 + 3)
    try:
        traffic.setup()
        submits, errors = traffic.window(0.2)
    finally:
        traffic.close()
    assert submits and not errors
    nums = check.numbers(cell.config, submits, errors)
    ok, table = check.verdict(nums, check.limits(cell.config))
    assert ok, table
    if cell.traffic["mode"] == "roi":
        assert len({(s.row0, s.col0) for s in submits}) > 1


@pytest.mark.parametrize("name", ["gaussian-8192.binary.chip",
                                  "mandelbrot-14336.binary.chip"])
def test_binary_submits_rotate_their_inputs(name, cpu):
    """Consecutive gaussian submits blur different images; mandelbrot,
    whose program takes no seed, is built once."""
    cell = tiny(registry.load_cell(name))
    groups = drive.build_fleet(cell.traffic, [cpu], cpu)
    traffic = drive.Traffic(cell.config, cell.traffic, groups, 2**40 + 5)
    try:
        traffic.setup()
        submits = [traffic.submit_once() for _ in range(4)]
    finally:
        traffic.close()
    seeds = [s.input_seed for s in submits]
    if "seed_arg" in cell.config["programs"]["binary"]:
        n = cell.traffic["distinct_inputs"]
        assert len(set(seeds)) == n > 1
        assert all(a != b for a, b in zip(seeds, seeds[1:]))
        assert not np.array_equal(submits[0].sample, submits[1].sample)
    else:
        assert seeds == [2**40 + 5] * 4


@pytest.mark.parametrize("total,groups,want", [
    (64, 1, [{1, 2, 4, 8, 16, 32}]),
    (64, 2, [set(range(1, 33))] * 2),
    (56, 1, [{1, 2, 3, 4, 7, 8, 14, 15, 28, 29}])])
def test_packet_sizes_are_what_hguided_carves(total, groups, want):
    """Halving a power of two is exact; halving 56 or 28 through a float
    power ratio can round up to the next size."""
    from repro.core.region import as_region
    got = drive.packet_sizes("hguided_opt", {}, as_region(total, 1), groups)
    assert got == want


def test_warmed_packet_sizes_leave_nothing_to_compile(cpu):
    """Over two groups the window compiles nothing: set-up ran every
    packet size the scheduler hands out."""
    cell = tiny(registry.load_cell("gaussian-8192.binary.chip-host"))
    result, _ = run_cell(cell, 2**33 + 9, 0.5, True, [cpu], cpu)
    assert result["metrics"]["compiles"]["value"] == 0


@pytest.mark.parametrize("trace", [0, 1])
def test_run_cell_reports_the_cells_metrics(trace, cpu):
    name = "gaussian-8192.binary.chip-host"
    cell = tiny(registry.load_cell(name))
    result, lines = run_cell(cell, 7, 0.3, bool(trace), [cpu], cpu)
    assert result["correct"] and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    want = {m["name"] for m in cell.metrics(bool(trace))}
    # the CPU runs the jnp path: no Pallas kernel for a roofline
    want.discard("gaussian_roofline")
    assert set(result["metrics"]) == want
    assert result["device"]["platform"] == "cpu"
    if trace:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] >= 0.3
        assert result["breakdown"]["device_ops"]
    assert all(line.startswith("check ") for line in lines)


def _run_command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gaussian-8192.binary.chip", "--seed", "1", "--seconds", "1",
         "--trace", "0", *extra], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_command_refuses_to_run_without_a_tpu():
    p = _run_command(registry.ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_command_fails_with_only_the_benchmarks_files(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(registry.BENCH, tmp_path / "bench")
    env_path = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "gaussian-8192.binary.chip", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=dict(env_path,
                                                 JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_new_cell_config_traffic_and_metric_need_only_new_files(
        tmp_path, monkeypatch, cpu):
    """Copy bench/, add one file of each kind and the entries naming
    them; the unchanged harness runs the new cell and reads the new
    metric."""
    root = tmp_path / "checkout"
    shutil.copytree(registry.BENCH, root / "bench")
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    cfg = registry.load_json(registry.BENCH / "configs/gaussian-8192.json")
    cfg["name"] = "gaussian-384x512"
    cfg["image"] = [384, 512]
    for spec in cfg["programs"].values():
        spec["args"].update(h=384, w=512)
    (root / "bench/configs/gaussian-384x512.json").write_text(
        json.dumps(cfg))
    (root / "bench/traffic/binary.chip.warm2.json").write_text(json.dumps(
        {"mode": "binary", "host_cpu": False, "warm_submits": 2}))
    (root / "bench/metrics/submits_done.py").write_text(
        "def read(run):\n    return float(len(run.submits))\n")
    bm = json.loads(json.dumps(BM))
    bm["configs"].append({"name": "gaussian-384x512",
                          "source": "https://arxiv.org/abs/2010.12607",
                          "file": "bench/configs/gaussian-384x512.json",
                          "reduced": ["h"], "why": "a test"})
    bm["workloads"].append({"name": "new.cell", "config":
                            "gaussian-384x512", "traffic":
                            "binary.chip.warm2", "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "submits_done", "unit": "count",
                            "better": "higher", "source": "host_clock",
                            "layer": "api session", "moves": "submit_s",
                            "workloads": ["new.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    monkeypatch.setattr(registry, "ROOT", root)
    monkeypatch.setattr(registry, "BENCH", root / "bench")
    cell = registry.load_cell("new.cell")
    assert cell.config["image"] == [384, 512]
    assert tiny(cell).config["image"] == cfg["tiny"]["image"]
    result, _ = run_cell(cell, 11, 0.3, True, [cpu])
    assert result["correct"]
    assert result["metrics"]["submits_done"]["value"] == \
        result["attempted"]
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*") if p.is_file()
             and p.relative_to(root) in before}
    assert after == before
