"""Serving launcher: thin CLI over the deadline-aware serving subsystem.

All mechanism lives in repro.serve (workload generation, admission,
co-execution dispatch, accounting); this module only parses flags, builds
replicas and prints the outcome.  For scheduler comparisons at fleet
scale use the simulator twin: benchmarks/serve_slo.py.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
      --requests 16 --rate 50 --slo 10 --replicas r0:1,r1:2
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, get_smoke
from repro.core.scheduler import available_schedulers
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import (ARRIVALS, CoexecServer, Replica, RequestQueue,
                         ServerConfig, make_requests, trace_arrivals)


def build_replicas(spec: str, cfg, params) -> list:
    """Replicas from a ``name:throttle,...`` list, the i-th pinned to
    ``jax.devices()[i % n]`` with its own copy of ``params``."""
    devices = jax.devices()
    replicas = []
    for i, part in enumerate(spec.split(",")):
        name, thr = part.split(":")
        replicas.append(Replica(name, cfg, params, throttle=float(thr),
                                device=devices[i % len(devices)]))
    return replicas


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--replicas", default="r0:1",
                    help="name:throttle list, e.g. r0:1,r1:2")
    ap.add_argument("--lws", type=int, default=4,
                    help="requests per packet alignment")
    ap.add_argument("--scheduler", default="hguided_deadline",
                    choices=available_schedulers())
    ap.add_argument("--arrival", default="poisson",
                    choices=sorted(ARRIVALS) + ["trace"])
    ap.add_argument("--trace", default=None,
                    help="file with one arrival timestamp per line")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="offered load, requests/s")
    ap.add_argument("--slo", type=float, default=10.0,
                    help="per-request deadline, seconds after arrival")
    ap.add_argument("--policy", default="shed",
                    choices=["shed", "degrade", "none"])
    ap.add_argument("--batch-window", type=float, default=0.0)
    ap.add_argument("--quantum", type=float, default=float("inf"),
                    help="round quantum, seconds of fleet work")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check-invariance", action="store_true",
                    help="re-serve a few requests on a reference replica "
                         "and require identical tokens")
    args = ap.parse_args(argv)
    if args.arrival == "trace" and not args.trace:
        ap.error("--arrival trace requires --trace FILE")
    if args.smoke:
        args.requests = min(args.requests, 16)
        args.gen = min(args.gen, 8)

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    from repro.models import transformer as T
    params, _ = T.init_params(cfg, jax.random.PRNGKey(0))
    replicas = build_replicas(args.replicas, cfg, params)

    rng = np.random.default_rng(args.seed)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.requests, args.prompt_len)).astype(np.int32)
    if args.arrival == "trace":
        with open(args.trace) as f:
            arrivals = trace_arrivals([float(x) for x in f if x.strip()])
        arrivals = arrivals[:args.requests]
    else:
        arrivals = ARRIVALS[args.arrival](args.requests, args.rate, rng)
    reqs = make_requests(arrivals, args.slo, prompt_fn=lambda i: prompts[i])

    server = CoexecServer(replicas, ServerConfig(
        scheduler=args.scheduler, lws=args.lws, gen=args.gen,
        policy=args.policy, batch_window_s=args.batch_window,
        round_quantum_s=args.quantum))
    try:
        out = server.run(RequestQueue(reqs))
    finally:
        server.close()
    st = out.stats
    print(f"{len(reqs)} requests @ {args.rate:.0f}/s ({args.arrival}), "
          f"SLO {args.slo:.2f}s, scheduler={args.scheduler}")
    print(st.row())
    print(f"dispatch={st.dispatch} degraded={st.degraded} "
          f"duration={st.duration:.2f}s")

    if args.check_invariance:
        # replica assignment / packing must not change outputs: re-serve a
        # few full-generation requests on a fresh reference replica
        full = [r for r in out.requests
                if not r.shed and r.finish is not None
                and not r.degraded][:4]
        if not full:
            # a check that compared nothing has not passed
            print("outputs replica-invariant: not checked "
                  "(no full requests)")
            return 1
        ref = Replica("ref", cfg, params)
        batch = np.stack([r.prompt for r in full])
        want = ref.serve(batch, args.gen)
        got = np.stack([out.results[r.rid] for r in full])
        ok = np.array_equal(got, want)
        print(f"outputs replica-invariant: {ok} ({len(full)} requests)")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
