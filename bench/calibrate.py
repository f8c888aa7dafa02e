"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --seconds 4

In one process (set-up is paid once per seed, compiles once): for each
seed a short window of the cell's own traffic at its own sizes, then the
compared numbers of the program (the lower readings) and of the control,
the reference in the next lower precision put in the program's place
(the upper readings).  One JSON line per seed, then the largest program
reading and the smallest control reading of each number.  The benchmark's
own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import check, drive, registry  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=7_000_000_001)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args(argv)
    cell = registry.load_cell(args.workload)

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"needs {cell.chips} TPU chip(s); JAX sees {devices}",
              file=sys.stderr)
        return 2
    host = jax.devices("cpu")[0] if cell.traffic.get("host_cpu") else None
    groups = drive.build_fleet(cell.traffic, devices[:cell.chips], host)
    program, control = {}, {}
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        traffic = drive.Traffic(cell.config, cell.traffic, groups, seed)
        try:
            traffic.setup()
            submits, errors = traffic.window(args.seconds)
        finally:
            traffic.close()
        got = check.numbers(cell.config, submits, errors)
        low = check.control_numbers(cell.config, submits)
        print(json.dumps({"seed": seed, "submits": len(submits),
                          "program": got, "control": low}), flush=True)
        for k, v in got.items():
            program[k] = max(program.get(k, v), v)
        for k, v in low.items():
            control[k] = min(control.get(k, v), v)
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "program_max": program, "control_min": control,
                      "limits": check.limits(cell.config)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
