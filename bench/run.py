"""Run one benchmark cell once on the chip(s) this process sees.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; everything it names is found
by name under ``bench/`` (see ``bench/__init__.py``).  Set-up builds the
fleet, makes the program's data from ``--seed`` and warms every shape
the traffic uses; the window then submits in a closed loop for
``--seconds``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of the window.  Once the window has closed and the
program's state is freed, the plain reference checks the answers the
window kept (``bench/check.py``).

The last line of stdout is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``: each compared number with its limit); the last
lines of stderr repeat the checks.  With no TPU visible, or fewer chips
than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # libtpu logs to a fixed directory under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # the checkout root replaces bench/ on the path, so that bench's
    # modules are imported as a package and never shadow the standard
    # library's (trace)
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import check, drive, registry  # noqa: E402
from bench import trace as tracing  # noqa: E402

LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileCounter:
    """Counts jit lowerings (new executables) while ``on``."""

    def __init__(self):
        self.on = False
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if self.on and event == LOWERING:
            self.count += 1


def memory_peak(chips) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in chips]
    return int(max(peaks, default=0))


def packet_counts(submits, groups) -> dict:
    """{group: [packets, work-groups, smallest, largest]} over the
    window (work-groups in dim-0 units)."""
    out = {}
    for s in submits:
        for p in s.result.packets:
            c = out.setdefault(groups[p.device].name,
                               [0, 0, p.size, p.size])
            c[0] += 1
            c[1] += p.size
            c[2], c[3] = min(c[2], p.size), max(c[3], p.size)
    return out


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             chips, host=None, t_start: float = None):
    """Set up, run the window, check; returns (result dict, check lines).
    ``chips`` are the devices the cell's chip groups run on."""
    import jax
    t_start = time.perf_counter() if t_start is None else t_start
    entries = cell.metrics(trace)
    readers = {m["name"]: registry.load_module("metrics", m["name"])
               for m in entries}
    row_sums = any(getattr(r, "NEEDS_ROW_SUMS", False)
                   for r in readers.values())
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    summary = None
    try:
        groups = drive.build_fleet(cell.traffic, chips, host)
        traffic = drive.Traffic(cell.config, cell.traffic, groups, seed,
                                record_row_sums=row_sums)
        try:
            traffic.setup()
            setup_s = time.perf_counter() - t_start
            if trace:
                with tempfile.TemporaryDirectory() as d:
                    with tracing.record(d):
                        counter.on = True
                        submits, errors = traffic.window(seconds)
                        counter.on = False
                    summary = tracing.summarize(tracing.load(
                        tracing.xplane_file(d), chips[0].platform))
            else:
                counter.on = True
                submits, errors = traffic.window(seconds)
                counter.on = False
            peak = memory_peak(chips)
        finally:
            traffic.close()
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    print("set-up: " + ", ".join(f"{k} {v:.3f} s" for k, v in
                                   traffic.setup_log), file=sys.stderr)
    print(f"packets per group: {packet_counts(submits, groups)}",
          file=sys.stderr)
    if submits:
        walls = sorted(x.wall_s for x in submits)
        q = [walls[int(f * (len(walls) - 1))] for f in (0, .5, .9, .99, 1)]
        slow = max(range(len(submits)), key=lambda i: submits[i].wall_s)
        print("submit wall s, min p50 p90 p99 max: "
              + " ".join(f"{x:.4f}" for x in q)
              + f" (slowest: submit {slow} of {len(submits)}; "
              f"{submits[slow].result.phases})", file=sys.stderr)
    for e in errors[:5]:
        print(f"failed submit: {e}", file=sys.stderr)
    nums = check.numbers(cell.config, submits, errors)
    correct, table = check.verdict(nums, check.limits(cell.config))
    record = drive.RunRecord(
        setup_s=setup_s, submits=submits, compiles=counter.count,
        chip_groups=list(range(len(chips))), config=cell.config,
        device_kind=chips[0].device_kind, trace=summary)
    metrics = {}
    for m in entries:
        v = readers[m["name"]].read(record)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": chips[0].platform, "kind": chips[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": bool(correct),
              "attempted": len(submits) + len(errors),
              "failed": len(errors), "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = table
    lines = [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
             for k, v in table.items()]
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} TPU chip(s); JAX sees "
              f"{[d.platform for d in devices]}: nothing run",
              file=sys.stderr)
        return 2
    chips = devices[:cell.chips]
    host = jax.devices("cpu")[0] if cell.traffic.get("host_cpu") else None
    print(f"{args.workload}: {chips[0].device_kind} x{len(chips)}, seed "
          f"{args.seed}, {args.seconds:g} s, trace {args.trace}",
          file=sys.stderr)
    result, lines = run_cell(cell, args.seed, args.seconds,
                             bool(args.trace), chips, host,
                             t_start=T_START)
    print(f"{args.workload}: {result['attempted']} submits, correct "
          f"{result['correct']}", file=sys.stderr)
    for line in lines:
        print(line, file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
