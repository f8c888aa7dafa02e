"""Fixtures of the benchmark's CPU tests: every cell cut to a size a test
run holds, on the CPU devices JAX sees here."""
from __future__ import annotations

import copy

import pytest

from bench import registry


def tiny(cell: registry.Cell) -> registry.Cell:
    """A copy of ``cell`` at the tiny size its files give: a
    configuration's ``tiny`` block replaces its top-level keys, and its
    ``args`` update every program's arguments; a traffic's ``tiny``
    block replaces the traffic's keys (same programs, traffic shape and
    checks)."""
    cell = copy.deepcopy(cell)
    small = dict(cell.config["tiny"])
    args = small.pop("args", {})
    cell.config.update(small)
    for spec in cell.config["programs"].values():
        spec["args"].update(args)
    cell.traffic.update(cell.traffic.get("tiny", {}))
    return cell


@pytest.fixture(scope="session")
def cpu():
    import jax
    return jax.devices("cpu")[0]


def workload_names():
    return [w["name"] for w in
            registry.load_json(registry.ROOT / "BENCHMARK.json")["workloads"]]
