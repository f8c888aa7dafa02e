"""Bring-up smoke test: the co-execution runtime and its serving path on TPU.

  python chip_smoke.py                # one chip: phases a-e below
  python chip_smoke.py --four-chips   # four chips: co-execution over four
                                      # chip groups and four pinned replicas

One process holds the chip(s) and starts no other.  Every phase ends in
assertions and any failure exits non-zero.  Progress lines name the device
they ran on; the last line of stdout is the JSON result.  With no TPU
visible (or run outside the repository) the script exits 1 and prints no
result.

  a. the devices JAX sees;
  b. the chip alone: the paper's suite at its own sizes through
     ``coexec`` with default discovery, each output against the jnp
     reference, plus two ROI sub-region submits of ``gaussian2d``;
  c. chip + host CPU (the paper's setting): gaussian and mandelbrot
     co-executed through ``EngineSession`` over both;
  d. serving at full width: ``launch/serve.py`` on llama3.2-1b (random
     weights from PRNGKey(0)) with ``--check-invariance``, and the chip's
     bf16 prefill logits against float32 on the host CPU;
  e. the JSON line.

The persistent compile cache is kept where ``repro.launch.compile_cache``
says (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` here).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# (rtol, atol, largest share of elements allowed outside them).  Escape
# counts and ray hits are discontinuous in the pixel coordinate: one ulp
# of difference in c can move a pixel near the set's boundary (or a
# sphere's silhouette) by many iterations (or from hit to miss).
TOLERANCE = {
    "gaussian": (1e-4, 1e-4, 0.0),
    "gaussian2d": (1e-4, 1e-4, 0.0),
    "binomial": (1e-4, 1e-3, 0.0),
    "nbody": (1e-4, 1e-3, 0.0),
    "mandelbrot": (0.0, 0.0, 1e-3),
    "ray1": (1e-4, 1e-4, 1e-4),
}
# work-groups per reference call: bounds the jnp path's device memory
REF_PACKET = {"binomial": 2048, "nbody": 64, "ray1": 128}
PALLAS = ("gaussian", "gaussian2d", "binomial", "mandelbrot", "nbody")

# phase c: each program sized so the chip + CPU run ends within a minute
HOST_SIZES = {"gaussian": dict(h=2048, w=2048),
              "mandelbrot": dict(px=1024, max_iter=1000)}
# The host CPU's XLA rounds c and z differently from the chip's, so the
# CPU group's escape counts differ from the chip's reference on boundary
# pixels: 0.08% of the image with the CPU computing 59% of it, 0.006% at
# 4%, 0.16% at 81% (TPU v5e with its host's CPU), up to about 0.2% of
# what the CPU computes.  0.5% leaves room for the CPU computing all of it.
HOST_TOLERANCE = dict(TOLERANCE, mandelbrot=(0.0, 0.0, 5e-3))
# the four-chip run: the regular and the irregular program, Mandelbrot at
# its Table I size (``paper_suite.PAPER_SIZES``)
FOUR_CHIP_SIZES = {"gaussian": dict(h=8192, w=8192),
                   "mandelbrot": dict(px=14336, max_iter=5000)}
LOGITS_RTOL = 5e-2          # ||bf16 chip - f32 host|| / ||f32 host||


def tag(*devices) -> str:
    return "[" + ",".join(f"{d.platform}:{d.id}" for d in devices) + "]"


def say(devices, msg: str) -> None:
    print(f"{tag(*devices)} {msg}", flush=True)


def compare(name: str, got, want, tolerance=TOLERANCE) -> str:
    rtol, atol, share = tolerance[name]
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    assert got.shape == want.shape, (name, got.shape, want.shape)
    assert np.isfinite(got).all(), f"{name}: non-finite output"
    bad = ~np.isclose(got, want, rtol=rtol, atol=atol)
    frac = bad.mean()
    err = np.abs(got - want)
    rel = err / np.maximum(np.abs(want), 1e-30)
    msg = (f"max|err|={err.max():.3g} max rel={rel.max():.3g} "
           f"outside-tol={frac:.3g} (allowed {share:g} at rtol={rtol:g} "
           f"atol={atol:g})")
    assert frac <= share, f"{name}: {msg}"
    return msg


def track_devices(prog):
    """Wrap ``prog.build``: record the devices every packet result of each
    group lives on, and each group's built range function."""
    seen = defaultdict(set)
    fns = {}
    build = prog.build

    def wrapped(group):
        fn = build(group)
        fns[group.name] = fn

        def run(*args):
            out = fn(*args)
            for piece in jax.tree.leaves(out):
                seen[group.name] |= piece.devices()
            return out
        return run

    prog.build = wrapped
    return seen, fns


def check_placement(seen, groups) -> None:
    for g in groups:
        want = {g.device} if g.device is not None else {jax.devices()[0]}
        assert seen[g.name] == want, (g.name, seen[g.name], want)


def runs_compiled_pallas(fn, *args) -> bool:
    """Whether a range function, called with ``args``, runs Pallas
    kernels, none of them in the interpreter (read from its jaxpr: no
    compile, no copy of the inputs it closes over)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    flags = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                flags.append(eqn.params["interpret"])
            for v in eqn.params.values():
                if isinstance(v, (ClosedJaxpr, Jaxpr)):
                    walk(getattr(v, "jaxpr", v))

    walk(jax.make_jaxpr(lambda: fn(*args))().jaxpr)
    return bool(flags) and not any(flags)


def check_run(name, res, groups, seen) -> None:
    assert res.aborted_devices == 0, (name, res.aborted_devices)
    assert res.retries == 0, (name, res.retries)
    for g in groups:
        assert seen[g.name], f"{name}: group {g.name} ran no packet"
    check_placement(seen, groups)


# ------------------------------------------------------------------ phases

def phase_chip_alone(sizes, roi_size=None) -> None:
    """b. Each program at ``sizes`` through ``coexec`` on the discovered
    devices; then (given ``roi_size``) ``gaussian2d`` registered and
    offloaded twice in ROI mode."""
    from repro.api import DevicePolicy, coexec
    from repro.core import programs as P
    failed = []
    for name, kw in sizes.items():
        groups = DevicePolicy().discover()
        devs = [g.device for g in groups]
        prog = P.PROGRAMS[name](**kw)
        seen, fns = track_devices(prog)
        t0 = time.perf_counter()
        res = coexec(prog, groups)
        wall = time.perf_counter() - t0
        check_run(name, res, groups, seen)
        packets = {g.name: g.packets_done for g in groups}
        on_tpu = [g for g in groups if g.platform == "tpu"]
        kernel = "jnp"
        if name in PALLAS and on_tpu:
            assert all(runs_compiled_pallas(fns[g.name], 0, 1)
                       for g in on_tpu), name
            kernel = "pallas"
        say(devs, f"{name} {kw}: coexec {wall:.3f}s wall, roi "
                  f"{res.total_time:.3f}s, packets {packets}, "
                  f"kernel={kernel}")
        ref = P.reference_output(name, packet=REF_PACKET.get(name), **kw)
        try:        # report every program before failing the phase
            say([jax.devices()[0]], f"{name} vs jnp reference: "
                                    f"{compare(name, res.output, ref)}")
        except AssertionError as e:
            say([jax.devices()[0]], f"FAILED {e}")
            failed.append(name)
        del prog, res, ref, fns
        gc.collect()
    if roi_size is not None:
        _roi_offloads(roi_size)
    assert not failed, f"outputs differ from the reference: {failed}"


def _roi_offloads(roi_size: int) -> None:
    from repro.api import DevicePolicy, EngineSession, OffloadMode, Region
    from repro.core import programs as P
    groups = DevicePolicy().discover()
    prog = P.PROGRAMS["gaussian2d"](h=roi_size, w=roi_size)
    ref = P.reference_output("gaussian2d", h=roi_size, w=roi_size)
    seen, fns = track_devices(prog)
    q = roi_size // 4
    with EngineSession(groups) as session:
        session.register_workload(prog)
        assert all(runs_compiled_pallas(fns[g.name], 0, 32, 0, 32)
                   for g in groups if g.platform == "tpu")
        for r0, c0 in ((q, q), (2 * q, q // 2)):
            roi = Region.rect(q, 2 * q, lws=(32, 32), offset=(r0, c0))
            res = session.submit(prog, region=roi,
                                 mode=OffloadMode.ROI).result()
            check_run("gaussian2d", res, groups, seen)
            msg = compare("gaussian2d", res.output,
                          ref[r0:r0 + q, c0:c0 + 2 * q])
            say([g.device for g in groups],
                f"gaussian2d ROI {q}x{2 * q} at ({r0},{c0}): roi "
                f"{res.total_time:.3f}s; {msg}")


def phase_chip_and_host(chip, host, sizes) -> None:
    """c. One regular and one irregular program co-executed over an
    explicit chip group and host-CPU group."""
    from repro.api import EngineSession
    from repro.core import programs as P
    from repro.core.device import DeviceGroup
    groups = [DeviceGroup(f"{chip.platform}{chip.id}", device=chip),
              DeviceGroup(f"host{host.id}", device=host)]
    with EngineSession(groups) as session:
        for name, kw in sizes.items():
            prog = P.PROGRAMS[name](**kw)
            seen, _ = track_devices(prog)
            res = session.run(prog)
            check_run(name, res, groups, seen)
            rows = {g.name: 0 for g in groups}
            for p in res.packets:
                rows[groups[p.device].name] += p.size
            ref = P.reference_output(name, **kw)
            msg = compare(name, res.output, ref, HOST_TOLERANCE)
            say([chip, host], f"{name} {kw}: roi {res.total_time:.3f}s, "
                              f"work-groups per group {rows}; {msg}")


def phase_serve(replicas: str, extra=()) -> None:
    """d (serving). ``launch/serve.py`` in-process; the invariance check
    must compare at least one fully served request."""
    from repro.launch import serve
    argv = ["--arch", "llama3.2-1b", "--requests", "8", "--slo", "60",
            "--replicas", replicas, "--check-invariance", *extra]
    t0 = time.perf_counter()
    rc = serve.main(argv)
    say(jax.devices(), f"launch/serve.py {' '.join(argv)}: rc={rc} "
                       f"({time.perf_counter() - t0:.1f}s)")
    assert rc == 0, f"serving failed (rc={rc})"


def phase_replica_placement(replicas: str, cfg) -> None:
    """Each replica of ``launch/serve.build_replicas`` generates on its
    own device."""
    from repro.launch.serve import build_replicas
    from repro.models import transformer as T
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    reps = build_replicas(replicas, cfg, params)
    prompts = np.zeros((1, 8), np.int32)
    homes = []
    for rep in reps:
        out = rep.generate(prompts, 2)
        assert out.devices() == {rep.device}, (rep.name, out.devices())
        homes.append(rep.device)
    assert len(set(homes)) == len(homes), homes
    say(homes, f"replicas {[r.name for r in reps]} each generate on their "
               "own device")


def phase_logits(chip, host, cfg, prompt_len: int = 64) -> None:
    """d (numerics). One prompt's prefill logits: the chip at the config's
    dtype against the same weights in float32 on the host CPU."""
    from repro.models import transformer as T
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, prompt_len)).astype(np.int32)
    with jax.default_device(chip):
        cache, _ = T.init_cache(cfg, 1, prompt_len)
        lg, _ = T.prefill(cfg, jax.device_put(params, chip),
                          jnp.asarray(prompt), cache)
        got = np.asarray(lg, np.float32)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree.map(
        lambda x: jax.device_put(x, host).astype(jnp.float32), params)
    del params
    with jax.default_device(host):
        cache, _ = T.init_cache(cfg32, 1, prompt_len)
        lg, _ = T.prefill(cfg32, params32, jnp.asarray(prompt), cache)
        want = np.asarray(lg, np.float32)
    assert got.shape == want.shape == (1, 1, cfg.vocab_size), got.shape
    assert np.isfinite(got).all()
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    top = int(got.argmax()) == int(want.argmax())
    say([chip, host], f"prefill logits {cfg.dtype} vs float32: rel L2 "
                      f"{rel:.3g} (limit {LOGITS_RTOL:g}), same argmax "
                      f"{top}")
    assert rel <= LOGITS_RTOL, rel


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip phases")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache_events = defaultdict(int)
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.__setitem__(
            event, cache_events[event] + 1))

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"no TPU visible (JAX sees {d0.platform}); nothing run",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    assert len(devices) >= want, f"need {want} chips, JAX sees {devices}"
    say(devices, f"platform={d0.platform} kind={d0.device_kind} "
                 f"count={len(devices)}")
    host = jax.devices("cpu")[0]

    from repro.configs import get_config
    from repro.configs.paper_suite import PAPER_SIZES
    cfg = get_config("llama3.2-1b")
    if args.four_chips:
        four = "r0:1,r1:1,r2:1,r3:1"
        phases = [
            ("co-execution", lambda: phase_chip_alone(FOUR_CHIP_SIZES)),
            ("serving", lambda: phase_serve(four)),
            ("replica placement",
             lambda: phase_replica_placement(four, cfg))]
    else:
        phases = [
            ("b chip alone",
             lambda: phase_chip_alone(PAPER_SIZES, roi_size=8192)),
            ("c chip + host",
             lambda: phase_chip_and_host(d0, host, HOST_SIZES)),
            ("d serving", lambda: phase_serve("r0:1")),
            ("d logits", lambda: phase_logits(d0, host, cfg))]
    t0 = time.perf_counter()
    failed = []
    for name, run in phases:       # every phase runs and reports
        t = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        gc.collect()
        say(devices, f"phase {name}: "
                     f"{'FAILED' if name in failed else 'passed'} "
                     f"({time.perf_counter() - t:.1f}s)")
    if failed:
        say(devices, f"failed phases: {failed}")
        return 1
    hits = cache_events["/jax/compilation_cache/cache_hits"]
    misses = cache_events["/jax/compilation_cache/cache_misses"]
    say(devices, f"all phases passed in {time.perf_counter() - t0:.1f}s; "
                 f"compile cache {cache_dir}: {hits} hits, {misses} misses")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
