import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape) combination
on the production meshes and extract memory / cost / collective evidence.

    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k [--multi-pod] [--out experiments/dryrun]
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes

The XLA_FLAGS line above MUST stay the first statement: jax locks the device
count on first initialization (this is the only entry point that forces 512
host devices — tests and benchmarks see the real device count).
"""

import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ALL_ARCHS, ASSIGNED_ARCHS, get_config
from repro.configs.shapes import INPUT_SHAPES, input_specs
from jax import set_mesh
from repro.launch.mesh import make_production_mesh, num_workers
from repro.launch.roofline import (
    roofline_terms, parse_collectives, model_flops_per_step)
from repro.models import build_model
from repro.optim.adamw import AdamWConfig, init_adamw
from repro.distributed.train_step import make_fsdp_norm_step
from repro.distributed.serve_step import make_decode_step, make_prefill


def dryrun_config(arch: str, remat: str = "full"):
    """Full config tuned for lowering: bf16, remat, chunked xent."""
    cfg = get_config(arch)
    return cfg.replace(dtype="bfloat16", param_dtype="bfloat16",
                       remat=remat, xent_chunk=512)


def _compile_one(cfg, shape, mesh, step_impl: str, accum: int = 1,
                 variance_impl: str = "scalar", seqpar: bool = False):
    """Build + lower + compile the step for one config; returns compiled."""
    with set_mesh(mesh):
        return _compile_one_inner(cfg, shape, mesh, step_impl, accum,
                                  variance_impl, seqpar)


def _compile_one_inner(cfg, shape, mesh, step_impl: str, accum: int = 1,
                       variance_impl: str = "scalar", seqpar: bool = False):
    model = build_model(cfg)
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if shape.kind == "train":
        specs = input_specs(cfg, shape.name, accum=accum)
        opt_like = jax.eval_shape(init_adamw, params_like)
        if step_impl == "accum_norm":
            from repro.distributed.train_step import make_accum_norm_step
            wrap, _, _ = make_accum_norm_step(
                model, AdamWConfig(), mesh, params_like=params_like)
        else:
            wrap, _, _ = make_fsdp_norm_step(
                model, AdamWConfig(), mesh, params_like=params_like,
                variance_impl=variance_impl, sequence_parallel=seqpar)
        fn = wrap(specs)
        lowered = fn.lower(params_like, opt_like, specs,
                           jax.ShapeDtypeStruct((), jnp.float32))
    elif shape.kind == "prefill":
        specs = input_specs(cfg, shape.name)
        wrap, _ = make_prefill(model, mesh, batch=shape.global_batch,
                               params_like=params_like)
        fn = wrap(specs)
        lowered = fn.lower(params_like, specs)
    else:  # decode
        specs = input_specs(cfg, shape.name)
        wrap, _ = make_decode_step(model, mesh, batch=shape.global_batch,
                                   ring=specs["ring"], params_like=params_like)
        fn = wrap(specs["cache"])
        lowered = fn.lower(params_like, specs["cache"], specs["tokens"],
                           specs["pos"])
    return lowered.compile()


def _cost_and_collectives(compiled):
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):   # older jaxlib: one dict per device
        cost = cost[0] if cost else {}
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)), coll)


def _depth_cfg(cfg, repeats: int):
    """Reduced-depth unrolled variant of cfg with `repeats` pattern repeats
    (full width/batch) — used to calibrate true per-layer cost, since XLA's
    cost analysis counts a while-loop body once regardless of trip count."""
    layers = len(cfg.prefix_pattern) + repeats * len(cfg.block_pattern)
    return cfg.replace(num_layers=layers, scan_layers=False)


def lower_combo(arch: str, shape_name: str, multi_pod: bool,
                step_impl: str = "fsdp_norm", calibrate: bool = True,
                accum: int = 1, remat: str = "full",
                variance_impl: str = "scalar", seqpar: bool = False,
                bucket_ladder: str = ""):
    """Lower + compile one combination; returns (compiled, record).

    Three compiles: (A) the full-depth scanned model — THE deliverable proof
    that the sharding lowers and fits, and the memory_analysis source;
    (B)+(C) depth-1 / depth-2 unrolled variants whose cost difference is the
    exact per-layer cost, extrapolated to full depth for §Roofline."""
    cfg = dryrun_config(arch, remat=remat)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size

    t0 = time.time()
    compiled = _compile_one(cfg, shape, mesh, step_impl, accum=accum,
                            variance_impl=variance_impl, seqpar=seqpar)
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        mem_rec[attr] = getattr(mem, attr, None)

    if calibrate:
        c1 = _compile_one(_depth_cfg(cfg, 1), shape, mesh, step_impl,
                          accum=accum, variance_impl=variance_impl,
                          seqpar=seqpar)
        f1, b1, coll1 = _cost_and_collectives(c1)
        del c1
        c2 = _compile_one(_depth_cfg(cfg, 2), shape, mesh, step_impl,
                          accum=accum, variance_impl=variance_impl,
                          seqpar=seqpar)
        f2, b2, coll2 = _cost_and_collectives(c2)
        del c2
        R = cfg.num_repeats
        flops = f1 + (R - 1) * (f2 - f1)
        hbm = b1 + (R - 1) * (b2 - b1)
        coll = {}
        for op in coll1:
            coll[op] = {
                k: coll1[op][k] + (R - 1) * (coll2[op][k] - coll1[op][k])
                for k in coll1[op]
            }
        cost = {"flops": flops, "bytes accessed": hbm,
                "calibration": {"f1": f1, "f2": f2, "repeats": R}}
        hlo_for_terms = ""   # collectives already extrapolated
        mflops = model_flops_per_step(cfg, shape, n_dev)
        rl = roofline_terms(cost, hlo_for_terms, mflops)
        from repro.launch.roofline import wire_bytes, PEAK_FLOPS, HBM_BW, ICI_BW
        wb = wire_bytes(coll)
        rl.wire_bytes = wb
        rl.collective_s = wb / ICI_BW
        terms = {"compute": rl.compute_s, "memory": rl.memory_s,
                 "collective": rl.collective_s}
        rl.bottleneck = max(terms, key=terms.get)
    else:
        fl, hb, coll = _cost_and_collectives(compiled)
        cost = {"flops": fl, "bytes accessed": hb}
        mflops = model_flops_per_step(cfg, shape, n_dev)
        rl = roofline_terms(cost, compiled.as_text(), mflops)

    ladder_rec = {}
    if bucket_ladder and shape.kind == "train":
        # ahead-of-time compile every accumulation rung of the bucket ladder
        # (the engine's warmup cost if the whole ladder is prebuilt)
        for m in (int(v) for v in bucket_ladder.split(",")):
            if m == accum or shape.global_batch % m != 0:
                continue
            t0m = time.time()
            cm = _compile_one(cfg, shape, mesh, step_impl, accum=m,
                              variance_impl=variance_impl, seqpar=seqpar)
            ladder_rec[f"M{m}"] = round(time.time() - t0m, 1)
            del cm

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "step_impl": step_impl if shape.kind == "train" else shape.kind,
        "devices": n_dev,
        "workers_J": num_workers(mesh),
        "compile_s": round(t_compile, 1),
        "memory": mem_rec,
        "cost": cost,
        "collectives": coll,
        "roofline": rl.as_dict(),
        "params_total": cfg.param_count(),
        "params_active": cfg.param_count(active_only=True),
    }
    if ladder_rec:
        record["bucket_ladder_compile_s"] = ladder_rec
    return compiled, record


def applicable(arch: str, shape_name: str) -> bool:
    """All 40 pairs lower: long_500k uses the native sub-quadratic path for
    SSM/hybrid archs and the sliding-window serving mode for the rest
    (DESIGN §4)."""
    return True


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--assigned-only", action="store_true")
    p.add_argument("--multi-pod", action="store_true")
    p.add_argument("--both-meshes", action="store_true")
    p.add_argument("--step-impl", default="fsdp_norm")
    p.add_argument("--accum", type=int, default=1)
    p.add_argument("--remat", default="full")
    p.add_argument("--variance-impl", default="scalar")
    p.add_argument("--bucket-ladder", default="",
                   help="comma list of accumulation rungs to AOT-compile, "
                        "e.g. '1,2,4,8' (train shapes only)")
    p.add_argument("--seqpar", action="store_true")
    p.add_argument("--tag", default="")
    p.add_argument("--out", default="experiments/dryrun")
    p.add_argument("--skip-existing", action="store_true")
    args = p.parse_args(argv)

    archs = [args.arch] if args.arch else (
        list(ASSIGNED_ARCHS) if (args.all or args.assigned_only) else [])
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape_name in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape_name}__{'2x16x16' if mp else '16x16'}"
                if args.step_impl != "fsdp_norm":
                    tag += f"__{args.step_impl}"
                if args.tag:
                    tag += f"__{args.tag}"
                path = os.path.join(args.out, tag + ".json")
                if args.skip_existing and os.path.exists(path):
                    print(f"[skip] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    compiled, rec = lower_combo(
                        arch, shape_name, mp, step_impl=args.step_impl,
                        accum=args.accum, remat=args.remat,
                        variance_impl=args.variance_impl, seqpar=args.seqpar,
                        bucket_ladder=args.bucket_ladder)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=2, default=str)
                    rl = rec["roofline"]
                    print(f"  ok: compile={rec['compile_s']}s "
                          f"flops/dev={rl['flops']:.3g} "
                          f"bottleneck={rl['bottleneck']}", flush=True)
                    del compiled
                except Exception as e:
                    failures.append((tag, repr(e)))
                    with open(path + ".fail", "w") as f:
                        f.write(traceback.format_exc())
                    print(f"  FAIL: {e!r}", flush=True)
    if failures:
        print(f"\n{len(failures)} failures:")
        for tag, err in failures:
            print(" ", tag, err[:200])
        raise SystemExit(1)
    print("\nall combinations lowered + compiled OK")


if __name__ == "__main__":
    main()
