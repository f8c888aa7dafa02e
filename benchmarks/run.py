"""Benchmark driver: one function per paper table/figure + the framework's
own scale/roofline benches.  Prints ``name,us_per_call,derived`` CSV lines
(one per benchmark) plus the full tables.

  fig3   speedup + efficiency per scheduler per program   (paper Fig. 3)
  fig4   balance per scheduler                            (paper Fig. 4)
  fig5   HGuided (m, k) parameter surface                 (paper Fig. 5)
  fig6   inflection points, init/buffer optimizations     (paper Fig. 6)
  kernels  per-kernel us/call (jnp path) + allclose vs oracle
  real_engine  threaded co-execution on real devices (exactness + opts)
  session_reuse  EngineSession executable-cache amortization (cold vs warm)
  offload_modes  binary vs ROI offload modes (paper's 17.4% ROI gap)
  transfer_overlap  pooled buffers + overlapped staging vs per-packet sync
  sched_overhead  lease-amortized dispatch + steal tail vs per-packet lock
  dag_pipeline  dependency-aware DAG dispatch vs level barriers + resume
  fleet_slo    deadline-aware fleet routing + elastic autoscaling SLO gates
  energy_pareto  joule/makespan frontier of the energy-capped scheduler
  autotune_gain  calibrated autotuner vs hand-picked constants + cache reuse
  scale1000    1024-group fleet scheduling (beyond paper)
  roofline     three-term roofline over the dry-run artifacts
"""
from __future__ import annotations

import sys
import time

import numpy as np


def _bench_kernels() -> int:
    import jax
    import jax.numpy as jnp
    from benchmarks import common

    rng = np.random.default_rng(0)
    rows = []

    def timeit(fn, *args, reps=5):
        fn(*args)  # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(fn(*args))
        return (time.perf_counter() - t0) / reps * 1e6

    from repro.kernels.gaussian import ops as g
    img = rng.standard_normal((512, 512)).astype(np.float32)
    ip, w = g.prepare(img)
    ipj, wj = jnp.asarray(ip), jnp.asarray(w)
    us = timeit(lambda: g.run_range(ipj, wj, 0, g.total_work(img)))
    from repro.kernels.gaussian import kernel as gk
    pal = gk.blur_rows(ipj[:g.LWS + g.KSIZE - 1], wj)
    ref = g.run_range(ipj, wj, 0, 1)
    ok = bool(jnp.allclose(pal, ref, atol=1e-4))
    rows.append(("kernel_gaussian", us, f"pallas_allclose={ok}"))

    from repro.kernels.binomial import ops as b
    s0, k0, ty = map(jnp.asarray, b.make_inputs(16384))
    us = timeit(lambda: b.run_range(s0, k0, ty, 0, b.total_work(16384)))
    from repro.kernels.binomial import kernel as bk
    pal = bk.price_options(s0[:b.LWS], k0[:b.LWS], ty[:b.LWS], steps=b.STEPS)
    ref = b.run_range(s0, k0, ty, 0, 1)
    ok = bool(jnp.allclose(pal, ref, atol=1e-3))
    rows.append(("kernel_binomial", us, f"pallas_allclose={ok}"))

    from repro.kernels.mandelbrot import ops as m
    us = timeit(lambda: m.run_range(0, m.total_work(256), width=256,
                                    height=256, max_iter=256))
    from repro.kernels.mandelbrot import kernel as mk
    pal = mk.escape_counts(0, m.LWS, 256, 256, 64)
    ref, = m.run_range(0, 1, width=256, height=256, max_iter=64)
    ok = bool((pal == ref).all())
    rows.append(("kernel_mandelbrot", us, f"pallas_exact={ok}"))

    from repro.kernels.nbody import ops as n
    pm, vel = map(jnp.asarray, n.make_inputs(4096))
    us = timeit(lambda: n.run_range(pm, vel, 0, n.total_work(4096)))
    from repro.kernels.nbody import kernel as nk, ref as nr
    pal = nk.accelerations(pm[:2 * n.LWS], pm)
    ref = nr.accelerations(pm, 0, 2 * n.LWS)
    ok = bool(jnp.allclose(pal, ref, rtol=2e-4, atol=2e-4))
    rows.append(("kernel_nbody", us, f"pallas_allclose={ok}"))

    from repro.kernels.ray import ops as r, ref as rr
    sc = rr.make_scene(1)
    us = timeit(lambda: r.run_range(sc, 0, r.total_work(128), width=128,
                                    height=128))
    rows.append(("kernel_ray", us, "jnp_only=see_ref.py"))

    from repro.kernels.flash_attention import kernel as fk, ref as fr
    q = jnp.asarray(rng.standard_normal((1, 256, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 256, 4, 64)), jnp.float32)
    ref = fr.attention_ref(q, k, v)
    pal = fk.flash_attention(q, k, v, interpret=True)
    ok = bool(jnp.allclose(ref, pal, atol=2e-5))
    us = timeit(lambda: fr.attention_ref(q, k, v))
    rows.append(("kernel_flash_attention", us, f"pallas_allclose={ok}"))

    from repro.kernels.mamba_scan import kernel as sk, ref as sr
    a = jnp.asarray(rng.uniform(0.6, 0.95, (2, 128, 64, 16)), jnp.float32)
    bb = jnp.asarray(rng.standard_normal((2, 128, 64, 16)) * 0.1, jnp.float32)
    Cc = jnp.asarray(rng.standard_normal((2, 128, 16)), jnp.float32)
    yr, hr = sr.selective_scan_ref(a, bb, Cc)
    yp, hp = sk.selective_scan(a, bb, Cc, chunk=32, tile_d=32, interpret=True)
    ok = bool(jnp.allclose(yr, yp, atol=2e-5))
    us = timeit(lambda: sr.selective_scan_ref(a, bb, Cc))
    rows.append(("kernel_mamba_scan", us, f"pallas_allclose={ok}"))

    from repro.kernels.flash_decode import kernel as dk, ref as dr
    q = jnp.asarray(rng.standard_normal((2, 8, 64)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((2, 256, 4, 64)), jnp.bfloat16)
    ref = dr.decode_attention_ref(q, kc, vc, jnp.int32(200))
    pal = dk.flash_decode(q, kc, vc, jnp.int32(200), bk=64, interpret=True)
    ok = bool(jnp.allclose(np.asarray(ref, np.float32),
                           np.asarray(pal, np.float32), atol=2e-2))
    us = timeit(lambda: dr.decode_attention_ref(q, kc, vc, jnp.int32(200)))
    rows.append(("kernel_flash_decode", us, f"pallas_allclose={ok}"))

    bad = 0
    for name, us, derived in rows:
        print(common.csv_line(name, us, derived))
        if "False" in derived:
            bad += 1
    return bad


def main() -> None:
    t_start = time.time()
    failures = 0
    sections = []

    print("==== kernels ====")
    failures += _bench_kernels()

    for mod_name in ("fig3_speedup_efficiency", "fig4_balance",
                     "fig5_param_sweep", "fig6_inflection",
                     "real_engine", "session_reuse", "offload_modes",
                     "transfer_overlap", "sched_overhead", "dag_pipeline",
                     "fleet_slo", "energy_pareto", "autotune_gain",
                     "scale1000", "roofline"):
        print(f"\n==== {mod_name} ====", flush=True)
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["main"])
        try:
            rc = mod.main()
        except SystemExit as e:
            rc = int(e.code or 0)
        except Exception as e:  # pragma: no cover
            print(f"ERROR in {mod_name}: {e}")
            rc = 1
        failures += 1 if rc else 0
        sections.append((mod_name, rc))

    print("\n==== summary ====")
    for name, rc in sections:
        print(f"{name:28s} {'ok' if rc == 0 else 'FAIL'}")
    print(f"total wall: {time.time()-t_start:.1f}s; failures: {failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
