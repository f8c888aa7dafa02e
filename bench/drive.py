"""The one traffic generator: it reads a traffic mix's parameters
(``traffic/<name>.json``) and drives ``EngineSession.submit(...).result()``
in a closed loop, one caller, with the session's default scheduler.

A traffic file holds:

* ``mode``: ``"binary"`` (every submit builds, offloads and tears down
  the whole image) or ``"roi"`` (the program is registered once in
  set-up; each submit offloads a sub-region against it);
* ``host_cpu``: whether the host CPU joins the chips as a device group;
* ``roi``: for ``"roi"``, the region's ``rows`` and ``cols`` and the
  ``align``-ment of its offsets, which are drawn from the seed;
* ``distinct_inputs``: how many programs, each with its own input drawn
  from the seed, the submits rotate through (users send a new image
  with each binary submit); programs whose adapter takes no seed
  compute the same output whatever the seed, and are built once;
* ``warm_submits``: submits run in set-up before the window;
* ``tiny``: keys that replace the traffic's own in the CPU tests.

Set-up also runs, on every group, each packet size that the scheduler
can hand it and that the warm submits did not run, since the sizes
follow the measured powers.

A configuration (``configs/<name>.json``) names the program adapter of
each mode, its arguments and the image it computes.  Every submit's
output is sampled for the correctness check: one row, drawn from the
seed, in each of up to ``check.sample_wgs`` work-groups, drawn from the
seed too.

A new arrival process (an open loop, a deadline) is new code in this
file, not a traffic file: a mix only sets the parameters above.
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


@dataclass
class Submit:
    """One timed submit: its wall time, what the runtime reported, and
    the output rows kept for the correctness check."""
    wall_s: float
    result: object                  # RunResult, its output dropped
    row0: int                       # run region, in image pixels
    n_rows: int
    col0: int
    n_cols: int
    unit_rows: int                  # image rows per dim-0 unit
    sample_rows: np.ndarray         # absolute image rows kept
    sample: np.ndarray              # (len(sample_rows), n_cols)
    input_seed: int                 # seed of the program's input
    row_sums: Optional[np.ndarray] = None   # per run row, when asked

    def packet_tiles(self, groups: Sequence[int]):
        """(rows, cols, first run row) of each packet run on ``groups``."""
        for p in self.result.packets:
            if p.device in groups:
                yield (p.size * self.unit_rows, self.n_cols,
                       p.offset * self.unit_rows)


def build_fleet(traffic: Dict, chips: Sequence, host=None) -> List:
    """One device group per chip, then the host CPU's if the traffic
    asks for it."""
    from repro.core.device import DeviceGroup
    groups = [DeviceGroup(f"{d.platform}{d.id}", device=d) for d in chips]
    if traffic.get("host_cpu"):
        if host is None:
            raise ValueError("traffic asks for the host CPU; none given")
        groups.append(DeviceGroup(f"host{host.id}", device=host))
    return groups


def make_program(config: Dict, mode: str, seed: int):
    from repro.core import programs as P
    spec = config["programs"][mode]
    args = dict(spec["args"])
    for k, v in args.items():
        if isinstance(v, list):
            args[k] = tuple(v)
    if spec.get("seed_arg"):
        args[spec["seed_arg"]] = seed
    return P.PROGRAMS[spec["adapter"]](**args)


def input_seeds(config: Dict, traffic: Dict, mode: str,
                seed: int) -> List[int]:
    """The seeds of the inputs the submits rotate through: ``seed``
    first, then seeds drawn from it."""
    n = int(traffic.get("distinct_inputs", 1))
    if n == 1 or not config["programs"][mode].get("seed_arg"):
        return [seed]
    drawn = np.random.default_rng([seed, 3]).integers(0, 2**62, size=n - 1)
    return [seed] + [int(x) for x in drawn]


SIZE_TRIALS = 300


def packet_sizes(scheduler: str, kwargs: Dict, region,
                 n_groups: int) -> List[set]:
    """The packet sizes, in dim-0 units, that ``scheduler`` carves for
    each of ``n_groups`` groups over ``region``: it is drained
    ``SIZE_TRIALS`` times, with powers drawn afresh before every packet, since the
    runtime's measured powers move between packets and runs and the
    carve rounds their ratio (a float, so that even one group can be
    handed either of two sizes)."""
    from repro.core.scheduler import DeviceProfile, make_scheduler
    rng = np.random.default_rng(0)
    sizes = [set() for _ in range(n_groups)]
    for _ in range(SIZE_TRIALS):
        profiles = [DeviceProfile(f"g{i}", float(10 ** x)) for i, x in
                    enumerate(rng.uniform(-3, 3, n_groups))]
        sched = make_scheduler(scheduler, region, region.dims[0].lws,
                               profiles, **kwargs)
        while True:
            for i in range(n_groups):
                sched.update_power(i, float(10 ** rng.uniform(-3, 3)))
            g = int(rng.integers(n_groups))
            pkt = sched.next_packet(g)
            if pkt is None:
                break
            sizes[g].add(pkt.size)
    return sizes


class Traffic:
    """Set-up and closed-loop submits of one cell's traffic."""

    def __init__(self, config: Dict, traffic: Dict, groups: List,
                 seed: int, *, record_row_sums: bool = False):
        self.config, self.traffic = config, traffic
        self.groups = groups
        self.mode = traffic["mode"]
        self.record_row_sums = record_row_sums
        self.seed = seed
        self._regions = np.random.default_rng([seed, 0])
        self._samples = np.random.default_rng([seed, 1])
        self._warm_regions = np.random.default_rng([seed, 2])
        self.session = None
        self.programs: List = []        # (input seed, program)
        self.program = None             # the first; what set-up warms
        self._n_submits = 0
        self.setup_log: List[Tuple[str, float]] = []   # (step, seconds)

    def _step(self, name: str, t0: float) -> float:
        t = time.perf_counter()
        self.setup_log.append((name, t - t0))
        return t

    # -- set-up ---------------------------------------------------------
    def setup(self) -> None:
        from repro.api import EngineSession
        t = time.perf_counter()
        seeds = input_seeds(self.config, self.traffic, self.mode, self.seed)
        self.programs = [(s, make_program(self.config, self.mode, s))
                         for s in seeds]
        self.program = self.programs[0][1]
        t = self._step(f"programs ({len(self.programs)})", t)
        region = self.program.work_region
        self.image_rows, self.image_cols = self.config["image"]
        self.unit_rows = self.image_rows // region.dims[0].size
        self.wg_rows = region.dims[0].lws * self.unit_rows
        self.session = EngineSession(self.groups)
        if self.mode == "roi":
            for _, program in self.programs:
                self.session.register_workload(program)
        t = self._step("session", t)
        ran = [set() for _ in self.groups]
        for _ in range(int(self.traffic.get("warm_submits", 1))):
            kw = self._submit_kw(self._warm_regions)
            res = self.session.submit(self._next_program()[1], **kw).result()
            for p in res.packets:
                ran[p.device].add(p.size)
        t = self._step("warm submits", t)
        if region.ndim == 1:
            self._warm_packet_sizes(ran)
            self._step("warm packet sizes", t)

    def _warm_packet_sizes(self, ran: List[set]) -> None:
        """Run once on each group every packet size the scheduler can
        hand it that is not in ``ran`` (1-D programs)."""
        import jax
        sizes = packet_sizes(self.session.scheduler,
                             self.session.scheduler_kwargs,
                             self.program.work_region, len(self.groups))
        for g, want, done in zip(self.groups, sizes, ran):
            if want - done:
                fn = self.program.build(g)
                for size in sorted(want - done):
                    jax.block_until_ready(fn(0, size))
                del fn

    def _next_program(self):
        """(input seed, program) of the next submit, in rotation."""
        pick = self.programs[self._n_submits % len(self.programs)]
        self._n_submits += 1
        return pick

    # -- submits --------------------------------------------------------
    def _submit_kw(self, rng: np.random.Generator) -> Dict:
        from repro.api import OffloadMode
        if self.mode == "binary":
            return {"mode": OffloadMode.BINARY}
        from repro.core.region import Region
        roi = self.traffic["roi"]
        a, rows, cols = int(roi["align"]), int(roi["rows"]), int(roi["cols"])
        r0 = a * int(rng.integers(0, (self.image_rows - rows) // a + 1))
        c0 = a * int(rng.integers(0, (self.image_cols - cols) // a + 1))
        lws = tuple(d.lws for d in self.program.work_region.dims)
        return {"mode": OffloadMode.ROI,
                "region": Region.rect(rows, cols, lws=lws, offset=(r0, c0))}

    def submit_once(self) -> Submit:
        import jax
        kw = self._submit_kw(self._regions)
        input_seed, program = self._next_program()
        with jax.profiler.TraceAnnotation("bench.submit"):
            t0 = time.perf_counter()
            handle = self.session.submit(program, **kw)
        with jax.profiler.TraceAnnotation("bench.result"):
            res = handle.result()
            wall = time.perf_counter() - t0
        with jax.profiler.TraceAnnotation("bench.sample"):
            return self._keep(res, wall, kw.get("region"), input_seed)

    def _keep(self, res, wall: float, region,
              input_seed: int) -> Submit:
        if region is None:
            row0, n_rows, col0, n_cols = (0, self.image_rows, 0,
                                          self.image_cols)
        else:
            (row0, col0), (n_rows, n_cols) = region.offsets, region.shape
        out = np.asarray(res.output).reshape(n_rows, -1)
        n_wg = -(-n_rows // self.wg_rows)
        k = min(int(self.config["check"]["sample_wgs"]), n_wg)
        wgs = np.sort(self._samples.choice(n_wg, size=k, replace=False))
        rel = wgs * self.wg_rows + self._samples.integers(
            0, self.wg_rows, size=k)
        rel = np.minimum(rel, n_rows - 1)
        sums = (out.sum(axis=1, dtype=np.float64)
                if self.record_row_sums else None)
        sub = Submit(wall_s=wall, result=res, row0=row0, n_rows=n_rows,
                     col0=col0, n_cols=n_cols, unit_rows=self.unit_rows,
                     sample_rows=row0 + rel, sample=out[rel].copy(),
                     input_seed=input_seed, row_sums=sums)
        res.output = None
        return sub

    def window(self, seconds: float) -> Tuple[List[Submit], List[str]]:
        """Submit in a closed loop until ``seconds`` have passed; the
        last submit starts inside the window and is waited for."""
        import jax
        done: List[Submit] = []
        errors: List[str] = []
        with jax.profiler.TraceAnnotation("bench.window"):
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                try:
                    done.append(self.submit_once())
                except Exception as e:     # counted as a failed submit
                    errors.append(f"{type(e).__name__}: {e}")
        return done, errors

    def close(self) -> None:
        """Free the program's state: session, executables, buffers."""
        if self.session is not None:
            self.session.close()
        self.session = self.program = None
        self.programs = []
        gc.collect()


@dataclass
class RunRecord:
    """What one run leaves for the metric readers (``metrics/*.py``)."""
    setup_s: float
    submits: List[Submit]
    compiles: int                   # programs built inside the window
    chip_groups: List[int]          # indices of the chips' groups
    config: Dict
    device_kind: str
    trace: Optional[object] = None  # trace.Summary of a traced run
