"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state; the dry-run process is
the only one that sees 512 host-platform devices.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod ("data","model") or 2x16x16 multi-pod
    ("pod","data","model") production mesh (TPU v5e target)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_test_mesh(n_devices: int | None = None, model: int = 2):
    """Small mesh over whatever devices exist (tests / smoke runs)."""
    n = n_devices or len(jax.devices())
    model = model if n % model == 0 else 1
    return jax.make_mesh((n // model, model), ("data", "model"),
                         axis_types=_auto(2))


def _auto(n: int) -> tuple:
    """Auto axes: the sharding rules constrain with
    ``with_sharding_constraint``, which Explicit axes (make_mesh's
    default in JAX 0.9) refuse."""
    return (jax.sharding.AxisType.Auto,) * n
