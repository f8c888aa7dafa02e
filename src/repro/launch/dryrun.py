import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax import: jax locks the device
# count on first init.  This process is the ONLY place that sees 512
# placeholder devices, all on the host CPU (it never takes a chip);
# smoke tests and benches see the real devices.

import argparse          # noqa: E402
import gzip              # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import (ARCH_IDS, SHAPES, get_config,  # noqa: E402
                           shapes_for)
from repro.launch import hlo_analysis, hlo_cost  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch import specs as SP  # noqa: E402
from repro.optim.adamw import OptConfig, TrainState  # noqa: E402
from repro.parallel.sharding import ShardingResolver  # noqa: E402
from repro.training import step as STEP  # noqa: E402

SDS = jax.ShapeDtypeStruct


def _sh_tree(resolver, abstract, axes, *, param):

    def is_ax(x):
        return isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x)

    return jax.tree.map(
        lambda ax, leaf: resolver.sharding(ax, leaf.shape, param=param),
        axes, abstract, is_leaf=is_ax)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               save_hlo: bool = False, opt_overrides=None):
    """Lower + compile one (arch x shape x mesh) cell; return the record."""
    cfg = get_config(arch)
    if opt_overrides:
        cfg = apply_overrides(cfg, opt_overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    resolver = ShardingResolver(mesh, fsdp=(shape.kind == "train"))
    t0 = time.time()

    if shape.kind == "prefill" and cfg.serve_2d_weights:
        # weights spread over data for prefill (batch amortizes the gathers);
        # decode keeps TP-resident weights (gathering per token is 15x the
        # memory floor) — dbrx decode capacity requires int8 weights or
        # TP-32 in production (see EXPERIMENTS.md)
        resolver = ShardingResolver(mesh, fsdp=True)
    if shape.kind == "train":
        opt = OptConfig()
        state_abs, state_axes = SP.abstract_train_state(cfg, opt)
        batch_abs = SP.input_specs(cfg, shape)
        batch_axes = SP.batch_logical_axes(cfg, shape)
        st_sh = _sh_tree(resolver, state_abs, state_axes, param=True)
        b_sh = _sh_tree(resolver, batch_abs, batch_axes, param=False)
        fn = STEP.make_train_step(cfg, opt, res=resolver,
                                  accum_steps=cfg.accum_override
                                  or shape.accum_steps)
        jfn = jax.jit(fn, in_shardings=(st_sh, b_sh),
                      out_shardings=(st_sh, None), donate_argnums=(0,))
        with mesh:
            lowered = jfn.lower(state_abs, batch_abs)
    elif shape.kind == "prefill":
        params_abs, p_axes = SP.abstract_params(cfg)
        cache_abs, c_axes = SP.abstract_cache(cfg, shape.global_batch,
                                              shape.seq_len)
        batch_abs = SP.input_specs(cfg, shape)
        batch_axes = SP.batch_logical_axes(cfg, shape)
        p_sh = _sh_tree(resolver, params_abs, p_axes, param=True)
        c_sh = _sh_tree(resolver, cache_abs, c_axes, param=False)
        b_sh = _sh_tree(resolver, batch_abs, batch_axes, param=False)
        fn = STEP.make_prefill_step(cfg, res=resolver)
        jfn = jax.jit(fn, in_shardings=(p_sh, b_sh, c_sh),
                      out_shardings=(None, c_sh), donate_argnums=(2,))
        with mesh:
            lowered = jfn.lower(params_abs, batch_abs, cache_abs)
    elif shape.kind == "decode":
        if cfg.decode_unroll:
            params_abs, p_axes = SP.abstract_params_unstacked(cfg)
        else:
            params_abs, p_axes = SP.abstract_params(cfg)
        cache_abs, c_axes = SP.abstract_cache(cfg, shape.global_batch,
                                              shape.seq_len)
        ins = SP.input_specs(cfg, shape)
        p_sh = _sh_tree(resolver, params_abs, p_axes, param=True)
        c_sh = _sh_tree(resolver, cache_abs, c_axes, param=False)
        t_sh = NamedSharding(mesh, P())
        fn = STEP.make_decode_step(cfg, res=resolver)
        jfn = jax.jit(fn, in_shardings=(p_sh, t_sh, c_sh, t_sh),
                      out_shardings=(None, c_sh), donate_argnums=(2,))
        with mesh:
            lowered = jfn.lower(params_abs, ins["token"], cache_abs,
                                ins["pos"])
    else:
        raise ValueError(shape.kind)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    hlo = compiled.as_text()
    t0 = time.time()
    corrected = hlo_cost.analyze(hlo)   # loop-corrected per-device totals
    t_cost = time.time() - t0
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(mesh.devices.size),
        "kind": shape.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "cost_pass_s": round(t_cost, 2),
        # raw XLA numbers (uncorrected: while bodies counted once)
        "xla_flops_per_device": float(cost.get("flops", -1)),
        "xla_bytes_per_device": float(cost.get("bytes accessed", -1)),
        # loop-corrected per-device totals (see launch/hlo_cost.py)
        "flops_per_device": corrected["flops"],
        "transcendentals_per_device": corrected["transcendentals"],
        "traffic_bytes_per_device": corrected["traffic_bytes"],
        "collectives": corrected["collectives"],
        "collective_wire_bytes_per_device": corrected["collective_wire_bytes"],
        "unknown_trip_loops": corrected["unknown_trip_loops"],
        "memory": {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        },
        "op_histogram": hlo_analysis.op_histogram(hlo),
    }
    if save_hlo:
        os.makedirs("artifacts/hlo", exist_ok=True)
        fp = f"artifacts/hlo/{arch}__{shape_name}__{record['mesh']}.txt.gz"
        with gzip.open(fp, "wt") as f:
            f.write(hlo)
        record["hlo_path"] = fp
    return record


def cell_list():
    cells = []
    for arch in ARCH_IDS:
        for shape in shapes_for(get_config(arch)):
            cells.append((arch, shape.name))
    return cells


def _parse_overrides(pairs):
    """--set key=value config overrides (ints/floats/bools/strings; nested
    moe.* / ssm.* fields supported)."""
    import dataclasses
    out = {}
    for pair in pairs or []:
        key, val = pair.split("=", 1)
        for cast in (int, float):
            try:
                val = cast(val)
                break
            except ValueError:
                continue
        if val in ("true", "True"):
            val = True
        if val in ("false", "False"):
            val = False
        out[key] = val
    return out


def apply_overrides(cfg, overrides):
    import dataclasses
    top = {}
    for key, val in overrides.items():
        if "." in key:
            sub, field_name = key.split(".", 1)
            subcfg = dataclasses.replace(getattr(cfg, sub),
                                         **{field_name: val})
            top[sub] = subcfg
        else:
            top[key] = val
    return dataclasses.replace(cfg, **top)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--set", action="append", dest="overrides",
                    help="cfg override key=value (repeatable); e.g. "
                         "--set remat_policy=dots "
                         "--set moe.capacity_factor=1.0")
    ap.add_argument("--tag", default="",
                    help="artifact suffix for perf iterations")
    args = ap.parse_args()

    enable_compile_cache()
    cells = cell_list() if args.all else [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)
    overrides = _parse_overrides(args.overrides)
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            mesh_tag = "2x16x16" if mp else "16x16"
            suffix = f"__{args.tag}" if args.tag else ""
            fp = os.path.join(
                args.out, f"{arch}__{shape}__{mesh_tag}{suffix}.json")
            if os.path.exists(fp) and not args.force:
                print(f"[skip] {fp}")
                continue
            print(f"[dryrun] {arch} x {shape} x {mesh_tag} {overrides} ...",
                  flush=True)
            try:
                rec = lower_cell(arch, shape, multi_pod=mp,
                                 save_hlo=args.save_hlo,
                                 opt_overrides=overrides or None)
                rec["overrides"] = overrides
                rec["tag"] = args.tag
                with open(fp, "w") as f:
                    json.dump(rec, f, indent=1)
                wire = rec["collective_wire_bytes_per_device"]
                temp = rec["memory"].get("temp_size_in_bytes", 0) / 2**30
                print(f"  ok: compile={rec['compile_s']}s "
                      f"flops/dev={rec['flops_per_device']:.3e} "
                      f"wire/dev={wire:.3e} temp={temp:.2f}GiB",
                      flush=True)
            except Exception:
                failures += 1
                print(f"  FAILED:\n{traceback.format_exc()}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
