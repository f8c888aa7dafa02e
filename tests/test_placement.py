"""Placement: every group's packets and every pinned replica's tokens come
from that group's (replica's) own device.

Runs in a child process with four virtual CPU devices (the device count is
fixed when JAX starts), pinned to the CPU so it can never take a chip."""
import json
import os
import subprocess
import sys

GROUPS = r"""
import json
from collections import defaultdict
import jax
import numpy as np
from repro.api import coexec
from repro.core import programs as P
from repro.core.device import DeviceGroup

devices = jax.devices()
assert len(devices) == 4, devices
groups = [DeviceGroup(f"g{i}", device=d) for i, d in enumerate(devices)]
report = {}
for name in ("mandelbrot", "mandelbrot2d"):
    kw = dict(px=64, max_iter=20)
    prog = P.PROGRAMS[name](**kw)
    seen = defaultdict(set)
    build = prog.build

    def wrapped(group, build=build, seen=seen):
        fn = build(group)

        def run(*args):
            out = fn(*args)
            for piece in jax.tree.leaves(out):
                seen[group.name] |= {str(d) for d in piece.devices()}
            return out
        return run

    prog.build = wrapped
    res = coexec(prog, groups, scheduler="static")
    np.testing.assert_array_equal(res.output, P.reference_output(name, **kw))
    report[name] = {g.name: sorted(seen[g.name]) for g in groups}
report["devices"] = {g.name: str(g.device) for g in groups}
print(json.dumps(report))
"""

REPLICAS = r"""
import json
import jax
import numpy as np
from repro.configs import get_smoke
from repro.launch.serve import build_replicas
from repro.models import transformer as T

cfg = get_smoke("llama3.2-1b")
params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
reps = build_replicas("a:1,b:1,c:1,d:1", cfg, params)
prompts = np.arange(16, dtype=np.int32).reshape(2, 8) % cfg.vocab_size
outs = {r.name: r.generate(prompts, 3) for r in reps}
print(json.dumps({
    "devices": {r.name: str(r.device) for r in reps},
    "token_devices": {n: sorted(str(d) for d in o.devices())
                      for n, o in outs.items()},
    "param_devices": {r.name: sorted({str(d) for leaf in
                                      jax.tree.leaves(r.params)
                                      for d in leaf.devices()})
                      for r in reps},
    "same_tokens": all(np.array_equal(np.asarray(o),
                                      np.asarray(outs["a"]))
                       for o in outs.values()),
}))
"""


def _run_on_four_cpu_devices(script: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"        # the child must never take a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_group_packets_run_on_the_groups_device():
    rep = _run_on_four_cpu_devices(GROUPS)
    home = rep.pop("devices")
    assert len(set(home.values())) == 4
    for name, seen in rep.items():
        ran = {g: devices for g, devices in seen.items() if devices}
        assert len(ran) >= 2, (name, seen)     # not all on one device
        for group, devices in ran.items():
            assert devices == [home[group]], (name, group, devices)


def test_pinned_replicas_generate_on_their_device():
    rep = _run_on_four_cpu_devices(REPLICAS)
    home = rep["devices"]
    assert len(set(home.values())) == 4
    for name, dev in home.items():
        assert rep["token_devices"][name] == [dev], name
        assert rep["param_devices"][name] == [dev], name
    assert rep["same_tokens"]
