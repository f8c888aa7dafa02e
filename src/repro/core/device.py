"""Device-group abstraction (the paper's Tier-3 ``Device``).

A DeviceGroup owns one executor (a jax.Device — on TPU deployments a mesh
sub-slice handle) and runs range-partitioned packets of a Program.  The
per-packet throughput is EWMA-tracked — that is the online computing-power
estimate fed back to HGuidedOpt.

``throttle`` (>1 slows the device down by sleeping the extra fraction of
each packet's measured compute time) provides *controlled* heterogeneity on
a host where all executors are identical CPU devices; the calibrated
co-execution figures additionally use the discrete-event simulator
(core/simulate.py) with the paper's device profiles.  ``fail_after``
injects a hard device failure after N packets (fault-tolerance tests).
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable, Optional

import jax

from repro.core.clock import packet_compiles, span
from repro.energy.model import ZERO_POWER, PowerModel


class DeviceFailure(RuntimeError):
    pass


@dataclass
class DeviceGroup:
    name: str
    device: Optional[Any] = None          # jax.Device; None = default
    throttle: float = 1.0                 # >1 => proportionally slower
    fail_after: Optional[int] = None      # fail on the Nth packet
    ewma: float = 0.5
    # energy model (busy/idle W, lock J, transfer J/byte); the all-zero
    # default keeps every joule-blind config bit-identical (energy == 0)
    power_model: PowerModel = ZERO_POWER

    # runtime state
    packets_done: int = 0
    busy_time: float = 0.0
    finish_time: float = 0.0
    throughput: Optional[float] = None    # work-groups / s (EWMA)
    dead: bool = False

    @property
    def platform(self) -> str:
        """Platform the group's packets run on ("tpu", "cpu", ...)."""
        return (self.device or jax.devices()[0]).platform

    def put(self, x):
        if self.device is None:
            return x
        return jax.device_put(x, self.device)

    def run_packet(self, fn: Callable, offset: int, size: int):
        """Execute fn(offset, size); returns (result, wg_per_s).  The
        packet is a ``coexec.packet`` span: the host dispatch (slice, pad,
        launch) is ``coexec.launch``, the wait for the device
        ``coexec.wait``; jit lowerings inside it count against (this
        group, ``size``)."""
        if (self.fail_after is not None
                and self.packets_done >= self.fail_after):
            self.dead = True
            raise DeviceFailure(f"{self.name} failed (injected)")
        with span("coexec.packet", group=self.name, size=size), \
                packet_compiles(self.name, size):
            t0 = time.perf_counter()
            with span("coexec.launch"):
                out = fn(offset, size)
            with span("coexec.wait"):
                out = jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            if self.throttle > 1.0:
                time.sleep(dt * (self.throttle - 1.0))
                dt *= self.throttle
        self.packets_done += 1
        self.busy_time += dt
        wg_per_s = size / max(dt, 1e-9)
        self.throughput = wg_per_s if self.throughput is None else (
            self.ewma * wg_per_s + (1 - self.ewma) * self.throughput)
        return out, wg_per_s
