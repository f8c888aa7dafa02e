"""Program adapters: wrap the kernel suite's range entry points as
co-execution Programs (real execution on JAX devices).

Two geometries per the Region redesign:

* the classic 1-D adapters (``run_range``) — a flat work-group line, one
  work-group = ``LWS`` rows/options/bodies;
* 2-D NDRange adapters (``*_program_2d``, image kernels only) — the
  Program's region is ``rows x cols`` with per-dimension lws, the build
  produces a ``fn(row0, n_rows, col0, n_cols)`` tile kernel, and
  schedulers carve row panels.  These are the ROI-offloading targets
  (register once, re-submit sub-regions warm).

Each build picks its kernel from the device group's platform: the Pallas
kernel, compiled, on a TPU group; the jnp path on any other (``ray`` has
no Pallas kernel: its jnp path is its implementation).

Default sizes are scaled down from the paper's (which target a ~2 s GTX
950 run) so the real-execution benches stay fast on one CPU;
``configs/paper_suite.PAPER_SIZES`` holds the paper's own sizes."""
from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.region import Region
from repro.core.runtime import Program, RowPieces, write_rows
from repro.kernels.binomial import ops as binomial_ops
from repro.kernels.gaussian import ops as gaussian_ops
from repro.kernels.mandelbrot import ops as mandelbrot_ops
from repro.kernels.nbody import ops as nbody_ops
from repro.kernels.ray import ops as ray_ops
from repro.kernels.ray import ref as ray_ref


def _use_pallas(dev) -> bool:
    """The Pallas kernel runs on TPU groups; every other group runs jnp."""
    return dev.platform == "tpu"


def gaussian_program(h: int = 1024, w: int = 512, seed: int = 0) -> Program:
    rng = np.random.default_rng(seed)
    img = rng.standard_normal((h, w)).astype(np.float32)
    ip, wts = gaussian_ops.prepare(img)
    G = gaussian_ops.total_work(img)

    def build(dev):
        ipd = dev.put(jnp.asarray(ip))
        wd = dev.put(jnp.asarray(wts))
        use_pallas = _use_pallas(dev)

        def fn(offset, size):
            return gaussian_ops.run_range(ipd, wd, offset, size,
                                          use_pallas=use_pallas)
        return fn

    return Program("gaussian", G, 1, build,
                   out_rows_per_wg=gaussian_ops.LWS, out_cols=w,
                   in_bytes=ip.nbytes + wts.nbytes)


def gaussian_program_2d(h: int = 512, w: int = 512, seed: int = 0,
                        lws: Tuple[int, int] = (32, 32)) -> Program:
    """Gaussian blur as a 2-D NDRange (rows x cols, row-panel carving)."""
    rng = np.random.default_rng(seed)
    img = rng.standard_normal((h, w)).astype(np.float32)
    ip, wts = gaussian_ops.prepare(img)

    def build(dev):
        ipd = dev.put(jnp.asarray(ip))
        wd = dev.put(jnp.asarray(wts))
        use_pallas = _use_pallas(dev)

        def fn(row0, n_rows, col0, n_cols):
            return gaussian_ops.run_region(ipd, wd, row0, n_rows,
                                           col0, n_cols,
                                           use_pallas=use_pallas)
        return fn

    return Program("gaussian2d", build=build,
                   region=Region.rect(h, w, lws=lws),
                   in_bytes=ip.nbytes + wts.nbytes)


def mandelbrot_program_2d(px: int = 256, max_iter: int = 256,
                          lws: Tuple[int, int] = (8, 8)) -> Program:
    def build(dev):
        use_pallas = _use_pallas(dev)

        def fn(row0, n_rows, col0, n_cols):
            return mandelbrot_ops.run_region(row0, n_rows, col0, n_cols,
                                             width=px, height=px,
                                             max_iter=max_iter,
                                             use_pallas=use_pallas,
                                             device=dev.device)
        return fn

    return Program("mandelbrot2d", build=build,
                   region=Region.rect(px, px, lws=lws),
                   out_dtype=np.int32)


def ray_program_2d(which: int = 1, px: int = 256,
                   lws: Tuple[int, int] = (4, 4)) -> Program:
    scene = ray_ref.make_scene(which)

    def build(dev):
        sc = {k: dev.put(v) for k, v in scene.items()}

        def fn(row0, n_rows, col0, n_cols):
            return ray_ops.run_region(sc, row0, n_rows, col0, n_cols,
                                      width=px, height=px)
        return fn

    return Program(f"ray{which}_2d", build=build,
                   region=Region.rect(px, px, lws=lws), out_cols=3,
                   in_bytes=sum(v.nbytes for v in scene.values()))


def binomial_program(n_options: int = 65536, seed: int = 0) -> Program:
    s0, k0, ty = binomial_ops.make_inputs(n_options, seed)
    G = binomial_ops.total_work(n_options)

    def build(dev):
        a, b, c = (dev.put(jnp.asarray(x)) for x in (s0, k0, ty))
        use_pallas = _use_pallas(dev)

        def fn(offset, size):
            return binomial_ops.run_range(a, b, c, offset, size,
                                          use_pallas=use_pallas)
        return fn

    return Program("binomial", G, 1, build,
                   out_rows_per_wg=binomial_ops.LWS, out_cols=1,
                   in_bytes=s0.nbytes + k0.nbytes + ty.nbytes)


def mandelbrot_program(px: int = 512, max_iter: int = 256) -> Program:
    """Full-width row packets; each packet runs as its power-of-two pieces
    (``mandelbrot_ops.run_range``), returned as ``RowPieces``, so a device
    compiles a bounded set of kernel shapes whatever sizes the scheduler
    carves."""
    G = mandelbrot_ops.total_work(px)

    def build(dev):
        use_pallas = _use_pallas(dev)

        def fn(offset, size):
            return RowPieces(mandelbrot_ops.run_range(
                offset, size, width=px, height=px, max_iter=max_iter,
                use_pallas=use_pallas, device=dev.device))
        return fn

    return Program("mandelbrot", G, 1, build,
                   out_rows_per_wg=mandelbrot_ops.LWS * px, out_cols=1,
                   out_dtype=np.int32)


def nbody_program(n_bodies: int = 8192, seed: int = 0) -> Program:
    pm, vel = nbody_ops.make_inputs(n_bodies, seed)
    G = nbody_ops.total_work(n_bodies)

    def build(dev):
        pmd = dev.put(jnp.asarray(pm))
        vd = dev.put(jnp.asarray(vel))
        use_pallas = _use_pallas(dev)

        def fn(offset, size):
            return nbody_ops.run_range(pmd, vd, offset, size,
                                       use_pallas=use_pallas)
        return fn

    return Program("nbody", G, 1, build,
                   out_rows_per_wg=nbody_ops.LWS, out_cols=7,
                   in_bytes=pm.nbytes + vel.nbytes)


def ray_program(which: int = 1, px: int = 256) -> Program:
    scene = ray_ref.make_scene(which)
    G = ray_ops.total_work(px)

    def build(dev):
        sc = {k: dev.put(v) for k, v in scene.items()}

        def fn(offset, size):
            img = ray_ops.run_range(sc, offset, size, width=px, height=px)
            return img.reshape(-1, 3)
        return fn

    return Program(f"ray{which}", G, 1, build,
                   out_rows_per_wg=ray_ops.LWS * px, out_cols=3,
                   in_bytes=sum(v.nbytes for v in scene.values()))


PROGRAMS = {
    "gaussian": gaussian_program,
    "binomial": binomial_program,
    "mandelbrot": mandelbrot_program,
    "nbody": nbody_program,
    "ray1": lambda **kw: ray_program(1, **kw),
    "ray2": lambda **kw: ray_program(2, **kw),
    # 2-D NDRange variants (ROI-offloading targets, row-panel carving)
    "gaussian2d": gaussian_program_2d,
    "mandelbrot2d": mandelbrot_program_2d,
    "ray1_2d": lambda **kw: ray_program_2d(1, **kw),
    "ray2_2d": lambda **kw: ray_program_2d(2, **kw),
}


class _ReferenceDev:
    """Build target of the reference: the jnp path on the default device."""
    device = None
    platform = None

    def put(self, x):
        return x


def reference_output(program_name: str, packet: Optional[int] = None,
                     **kwargs) -> np.ndarray:
    """Single-device execution of the jnp path (the correctness oracle for
    co-executed outputs).  ``packet`` bounds the dim-0 work-groups per call
    (default: the whole NDRange in one call); a large program at the
    paper's sizes needs it to fit the device.  2-D programs return
    (rows, cols*out_cols)."""
    prog = PROGRAMS[program_name](**kwargs)
    fn = prog.build(_ReferenceDev())
    region = prog.work_region
    d0 = region.dims[0]
    step = packet or d0.size
    cols = (region.dims[1].size if region.ndim == 2 else 1) * prog.out_cols
    out = np.empty((d0.size * prog.out_rows_per_wg, cols), prog.out_dtype)
    for off in range(d0.offset, d0.offset + d0.size, step):
        size = min(step, d0.offset + d0.size - off)
        if region.ndim == 2:
            d1 = region.dims[1]
            got = fn(off, size, d1.offset, d1.size)
        else:
            got = fn(off, size)
        r0 = (off - d0.offset) * prog.out_rows_per_wg
        write_rows(got, out[r0:r0 + size * prog.out_rows_per_wg])
    return out
