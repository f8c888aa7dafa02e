"""NBody op: jit'd wrapper + range-partitionable entry (lws=64 bodies)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.nbody import kernel as K
from repro.kernels.nbody import ref as R

LWS = 64
DT = R.DT


def make_inputs(n_bodies: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    pos = rng.standard_normal((n_bodies, 3)).astype(np.float32) * 10.0
    mass = rng.uniform(0.5, 2.0, (n_bodies, 1)).astype(np.float32)
    vel = rng.standard_normal((n_bodies, 3)).astype(np.float32) * 0.1
    return np.concatenate([pos, mass], 1), vel


@partial(jax.jit, static_argnames=("size", "use_pallas"))
def _run(pos_mass, vel, offset, *, size: int, use_pallas: bool):
    tgt = jax.lax.dynamic_slice(pos_mass, (offset, 0), (size, 4))
    if use_pallas:
        acc = K.accelerations(tgt, pos_mass, interpret=False)
    else:
        # component-wise (T, N) planes: no (T, N, 3) intermediate, whose
        # 3-wide minor dimension a TPU would pad to 128 lanes
        d = [pos_mass[None, :, c] - tgt[:, c, None] for c in range(3)]
        r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2] + R.EPS2
        inv_r3 = jax.lax.rsqrt(r2) / r2 * pos_mass[None, :, 3]
        acc = jnp.stack([(dc * inv_r3).sum(axis=1) for dc in d], axis=1)
    v = jax.lax.dynamic_slice(vel, (offset, 0), (size, 3)) + acc * DT
    p = tgt[:, :3] + v * DT
    return jnp.concatenate([p, tgt[:, 3:], v], axis=1)


def run_range(pos_mass, vel, offset: int, size: int, *,
              use_pallas: bool = False):
    """Returns (size*LWS, 7) rows: [x,y,z,m,vx,vy,vz] after one step.
    ``use_pallas`` picks the compiled Pallas kernel (TPU only) over the jnp
    path."""
    return _run(pos_mass, vel, offset * LWS, size=size * LWS,
                use_pallas=use_pallas)


def total_work(n_bodies: int) -> int:
    assert n_bodies % LWS == 0
    return n_bodies // LWS
