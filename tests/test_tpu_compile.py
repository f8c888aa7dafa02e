"""The paper-suite kernels compile for a TPU v5e at the paper's widths.

Compiled here for a v5e that is described, not attached: the TPU compiler
refuses what interpret mode accepts (unaligned tiles, scoped VMEM over the
limit, layouts Mosaic cannot keep), so these guard the chip path at no
chip time.  The topology is described inside module-scoped fixtures only —
never at import — and every test skips when it cannot be described.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.configs.paper_suite import PAPER_SIZES
from repro.kernels.binomial import kernel as binomial_kernel
from repro.kernels.binomial import ops as binomial_ops
from repro.kernels.gaussian import kernel as gaussian_kernel
from repro.kernels.gaussian import ops as gaussian_ops
from repro.kernels.mandelbrot import kernel as mandelbrot_kernel
from repro.kernels.mandelbrot import ops as mandelbrot_ops
from repro.kernels.nbody import kernel as nbody_kernel
from repro.kernels.nbody import ops as nbody_ops

HBM_BYTES = 16 * 2**30          # one v5e chip
F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the
    # persistent cache without a chip: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _gaussian(size):
    px = PAPER_SIZES["gaussian"]["w"]
    specs = [((px + 30, px + 30), F32), ((31,), F32)]
    return specs, lambda img, w: gaussian_ops.run_range(
        img, w, 0, size, use_pallas=True)


def _gaussian_roi():
    px = PAPER_SIZES["gaussian"]["w"]
    specs = [((px + 30, px + 30), F32), ((31,), F32)]
    return specs, lambda img, w: gaussian_ops.run_region(
        img, w, px // 4, px // 4, px // 8, px // 2, use_pallas=True)


def _binomial(size):
    n = PAPER_SIZES["binomial"]["n_options"]
    return [((n,), F32)] * 3, lambda s0, k, t: binomial_ops.run_range(
        s0, k, t, 0, size, use_pallas=True)


def _mandelbrot(size):
    px = PAPER_SIZES["mandelbrot"]["px"]
    it = PAPER_SIZES["mandelbrot"]["max_iter"]
    # the tile origin is the only array argument: it carries the chip
    return [((2,), jnp.int32)], lambda origin: mandelbrot_ops._run_tile(
        origin, n_rows=size * mandelbrot_ops.LWS, n_cols=px, width=px,
        height=px, max_iter=it, use_pallas=True)


def _nbody(size):
    n = PAPER_SIZES["nbody"]["n_bodies"]
    return [((n, 4), F32), ((n, 3), F32)], lambda pm, vel: nbody_ops.run_range(
        pm, vel, 0, size, use_pallas=True)


# one work-group, and the first packet HGuided carves on one chip (half of
# the work-groups)
CASES = {
    "gaussian-1wg": lambda: _gaussian(1),
    "gaussian-half": lambda: _gaussian(32),
    "gaussian-roi": _gaussian_roi,
    "binomial-1wg": lambda: _binomial(1),
    "binomial-half": lambda: _binomial(16384),
    "mandelbrot-1wg": lambda: _mandelbrot(1),
    "mandelbrot-half": lambda: _mandelbrot(896),
    "nbody-1wg": lambda: _nbody(1),
    "nbody-half": lambda: _nbody(1792),
}


# each case's kernel names its pallas_call
KERNEL_NAMES = {"gaussian": gaussian_kernel.KERNEL_NAME,
                "binomial": binomial_kernel.KERNEL_NAME,
                "mandelbrot": mandelbrot_kernel.KERNEL_NAME,
                "nbody": nbody_kernel.KERNEL_NAME}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(one_chip, case):
    specs, fn = CASES[case]()
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert KERNEL_NAMES[case.split("-")[0]] in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, used
