"""Dry-run machinery on a small forced-device-count mesh, in a SUBPROCESS
(the 512-device production dry-run must not leak into this test process —
the isolation requirement itself is under test here)."""
import json
import os
import subprocess
import sys

import jax

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
import numpy as np

from repro.configs import get_smoke
from repro.configs.base import ShapeConfig
from repro.launch import specs as SP, hlo_cost
from repro.optim.adamw import OptConfig
from repro.parallel.sharding import ShardingResolver
from repro.training import step as STEP

assert len(jax.devices()) == 8
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
cfg = get_smoke("llama3.2-1b")
shape = ShapeConfig("t", 64, 8, "train", accum_steps=2)
resolver = ShardingResolver(mesh, fsdp=True)
opt = OptConfig()
state_abs, state_axes = SP.abstract_train_state(cfg, opt)
batch_abs = SP.input_specs(cfg, shape)
batch_axes = SP.batch_logical_axes(cfg, shape)


def is_ax(x):
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


st_sh = jax.tree.map(lambda ax, l: resolver.sharding(ax, l.shape, param=True),
                     state_axes, state_abs, is_leaf=is_ax)
b_sh = jax.tree.map(lambda ax, l: resolver.sharding(ax, l.shape),
                    batch_axes, batch_abs, is_leaf=is_ax)
fn = STEP.make_train_step(cfg, opt, res=resolver, accum_steps=2)
jfn = jax.jit(fn, in_shardings=(st_sh, b_sh), out_shardings=(st_sh, None),
              donate_argnums=(0,))
with mesh:
    lowered = jfn.lower(state_abs, batch_abs)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
corrected = hlo_cost.analyze(compiled.as_text())
print(json.dumps({
    "ok": True,
    "n_devices": len(jax.devices()),
    "flops": corrected["flops"],
    "wire": corrected["collective_wire_bytes"],
    "temp": getattr(mem, "temp_size_in_bytes", -1),
}))
"""


def test_small_mesh_dryrun_subprocess():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"        # the child must never take a chip
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["n_devices"] == 8
    assert rec["flops"] > 0
    assert rec["wire"] > 0            # FSDP all-gathers must appear


def test_this_process_kept_single_device():
    # the isolation contract: tests see the real single CPU device
    assert len(jax.devices()) == 1
