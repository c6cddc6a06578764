"""Chip smoke test: the adaptive-batch trainer's main path on a TPU.

    python chip_smoke.py             # one chip: phases a-d
    python chip_smoke.py --chips 4   # four chips: the FSDP-Norm J=4 path only

One chip:
  a. device check — JAX must report a TPU (exit 1 otherwise, e.g. on CPU)
  b. the compiled flat-tail kernels (`fused_stats`, `fused_adamw_stats`) on
     one 4 MiB bucket, and on one with a ragged last block, against
     `repro.kernels.ref`
  c. one full-width MicroLlama-300M ACCUM-NORM step, flat/flat residency
     (compiled Pallas tail) against tree/tree (plain XLA) on the same
     weights and batch; the flat step's HLO must hold `tpu_custom_call`
  d. `repro.launch.train.main` at full width: adaptive schedule with AOT
     warmup of the next rung, a few steps, per-step wall time fenced with
     `block_until_ready`, compile count and peak device memory

Four chips (`--chips 4`):
  i.  one FSDP-Norm J=4 step of MicroLlama-300M, flat/flat against
      tree/tree on the same global batch (non-zero `var_l1` must agree)
  ii. TinyLlama-1.1B at full width, FSDP-Norm over J=4 workers, flat/flat,
      through `repro.launch.train.main`; peak memory of every device (run
      first, so that its peaks are its own)

Every phase runs in this one process (a TPU belongs to one process at a
time).  Weights and data come from fixed seeds.  The last line of standard
output is the JSON verdict; any failure exits non-zero before printing it.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SEED = 0
LR = 1e-3
BUCKET = (4 << 20) // 4          # one 4 MiB f32 flat bucket
RAGGED = 3000                    # elements short of whole kernel blocks
SEQ = 2048

# tolerances, compiled Pallas / oracle against reference / tree oracle
KERNEL_SUM_RTOL = 1e-5           # f32 sums over one bucket, any order
KERNEL_ELEM_ATOL = 1e-6          # elementwise AdamW outputs
LOSS_RTOL = 1e-5
GSQ_RTOL = 1e-4                  # Σg², reduction order differs
VAR_RTOL = 1e-3                  # var_l1 is a difference of near-equal sums
MOMENT_RTOL = 1e-4               # first moment, relative to its max
# Adam moves a weight by lr * g/(|g| + eps): compared where |g| is at least
# this share of max|g| (near eps, rounding of g is amplified), to within
# this share of lr
LIVE_GRAD_SHARE = 1e-3
PARAM_LR_SHARE = 1e-2
# four chips: largest over smallest per-device peak (state built sharded)
PEAK_SPREAD = 1.05


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str):
    if not ok:
        raise SmokeFailure(msg)


def log(msg: str):
    print(msg, flush=True)


def _rel(a, b) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def memory(devices) -> list[dict]:
    """Per-device allocator counters (empty where the backend has none)."""
    keys = ("peak_bytes_in_use", "bytes_in_use", "bytes_limit")
    return [{k: (d.memory_stats() or {}).get(k, -1) for k in keys}
            for d in devices]


# ------------------------------------------------------------- phase a ----

def device_check(chips: int):
    interp = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip().lower()
    check(interp in ("", "0", "false", "no"),
          f"REPRO_PALLAS_INTERPRET={interp!r} forces interpret-mode Pallas; "
          f"unset it")
    import jax
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX reports platform {devices[0].platform!r}; this "
          f"script runs only on a TPU")
    check(len(devices) == chips,
          f"expected {chips} chip(s), JAX reports {len(devices)}")
    log(f"[a] device: platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")
    return devices


# ------------------------------------------------------------- phase b ----

def kernel_check():
    """Compiled fused_stats / fused_adamw_stats vs ref.py on one bucket, and
    on one whose last kernel block is ragged (masked in the sums)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    k = jax.random.split(jax.random.PRNGKey(SEED), 5)
    hp = dict(lr=LR, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1,
              c1=0.19, c2=0.0975, clip_scale=0.5)
    stats = jax.jit(lambda a, b: ops.fused_stats(a, b, interpret=False))
    adamw = jax.jit(lambda *a: ops.fused_adamw_stats(*a, **hp,
                                                     interpret=False))
    for n in (BUCKET, BUCKET - RAGGED):
        x = jax.random.normal(k[0], (n,))
        y = jax.random.normal(k[1], (n,))
        p = 0.02 * jax.random.normal(k[2], (n,))
        m = 0.01 * jax.random.normal(k[3], (n,))
        v = jnp.abs(1e-4 * jax.random.normal(k[4], (n,)))
        tag = f"[b] n={n}"

        text = stats.lower(x, y).compile().as_text()
        check("tpu_custom_call" in text, "fused_stats compiled without Pallas")
        got, want = stats(x, y), ref.fused_stats_ref(x, y)
        for name, a, b in zip(("sum_sqdiff", "sum_sq"), got, want):
            r = _rel(a, b)
            log(f"{tag} fused_stats {name}: pallas={float(a)!r} "
                f"ref={float(b)!r} rel={r:.3e} (tol {KERNEL_SUM_RTOL})")
            check(r <= KERNEL_SUM_RTOL, f"fused_stats {name} off by {r:.3e}")

        text = adamw.lower(p, x, m, v).compile().as_text()
        check("tpu_custom_call" in text,
              "fused_adamw_stats compiled without Pallas")
        got = adamw(p, x, m, v)
        want = ref.adamw_stats_ref(p, x, m, v, **hp)
        for name, a, b in zip(("p", "m", "v"), got[:3], want[:3]):
            d = float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            log(f"{tag} fused_adamw_stats {name}: max|pallas-ref|={d:.3e} "
                f"(tol {KERNEL_ELEM_ATOL})")
            check(d <= KERNEL_ELEM_ATOL,
                  f"fused_adamw_stats {name} off by {d}")
        r = _rel(got[3], want[3])
        log(f"{tag} fused_adamw_stats sum_g2: pallas={float(got[3])!r} "
            f"ref={float(want[3])!r} rel={r:.3e} (tol {KERNEL_SUM_RTOL})")
        check(r <= KERNEL_SUM_RTOL, f"fused_adamw_stats Σg² off by {r:.3e}")


# ------------------------------------------------------------- phase c ----

def run_one_step(arch: str, step_impl: str, impl: str, *, workers: int,
                 accum: int, micro: int, seq: int = SEQ,
                 precision: str | None = None):
    """One step of `arch` (remat full) with `impl` residency for both the
    statistics tail and the params, on a `workers`-chip data mesh, its
    matmuls at `precision` (None: JAX's default); returns host copies of
    the metrics, updated weights and first moment (leaf order), the compile
    seconds, the compiler's memory analysis and whether the HLO holds a
    Pallas call."""
    import contextlib
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core.schedule import BatchPlan
    from repro.data.pipeline import MarkovTokens, make_batch
    from repro.distributed.train_step import (make_accum_norm_step,
                                              make_fsdp_norm_step)
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import init_train_state
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig

    cfg = get_config(arch)
    model = build_model(cfg.replace(remat="full"))
    mesh = make_host_mesh(data=workers)
    key = jax.random.PRNGKey(SEED)
    params_like = jax.eval_shape(model.init, key)
    build = (make_accum_norm_step if step_impl == "accum_norm"
             else make_fsdp_norm_step)
    wrap, p_specs, o_specs = build(model, AdamWConfig(), mesh,
                                   stats_impl=impl, params_impl=impl,
                                   params_like=params_like)
    params, opt = init_train_state(model, key, mesh, wrap, p_specs, o_specs,
                                   stats_impl=impl, params_impl=impl)
    plan = BatchPlan(global_batch=workers * micro * accum, micro_batch=micro,
                     accum_steps=accum, workers=workers)
    batch = {k: jnp.asarray(v) for k, v in make_batch(
        MarkovTokens(vocab_size=cfg.vocab_size, seed=SEED), 0, plan,
        seq).items()}
    lr = jnp.float32(LR)
    prec = (jax.default_matmul_precision(precision) if precision
            else contextlib.nullcontext())
    with jax.set_mesh(mesh), prec:
        t0 = time.perf_counter()
        step = wrap(jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), batch))
        compiled = step.lower(params, opt, batch, lr).compile()
        compile_s = time.perf_counter() - t0
        ma = compiled.memory_analysis()
        new_p, new_o, metrics = compiled(params, opt, batch, lr)
        jax.block_until_ready((new_p, new_o))
        layout = wrap.flat_layout
        if impl == "flat":
            to_tree = jax.jit(lambda b: layout.unflatten(list(b)))
            new_p, new_m = to_tree(new_p), to_tree(new_o["m"])
        else:
            new_m = new_o["m"]
        host = {"metrics": {k: float(v) for k, v in metrics.items()},
                "params": [np.asarray(a) for a in jax.tree.leaves(new_p)],
                "m": [np.asarray(a) for a in jax.tree.leaves(new_m)],
                "compile_s": compile_s,
                "temp_gb": ma.temp_size_in_bytes / 1e9,
                "args_gb": ma.argument_size_in_bytes / 1e9,
                "pallas": "tpu_custom_call" in compiled.as_text()}
    return host


def compare_steps(tag: str, flat: dict, tree: dict, *, need_var: bool):
    """Flat/flat against the tree/tree oracle, within the stated
    tolerances."""
    import numpy as np
    fm, tm = flat["metrics"], tree["metrics"]
    for name, tol in (("loss", LOSS_RTOL), ("grad_sqnorm", GSQ_RTOL),
                      ("var_l1", VAR_RTOL)):
        r = _rel(fm[name], tm[name])
        log(f"[{tag}] {name}: flat={fm[name]!r} tree={tm[name]!r} "
            f"rel={r:.3e} (tol {tol})")
        check(math.isfinite(fm[name]) and r <= tol,
              f"{tag}: {name} flat {fm[name]} vs tree {tm[name]}")
    if need_var:
        check(fm["var_l1"] > 0, f"{tag}: var_l1 is {fm['var_l1']}, not > 0")
    m_max = max(float(np.max(np.abs(m))) for m in tree["m"])
    diffs = [float(np.max(np.abs(a - b))) if a.size else 0.0
             for a, b in zip(flat["m"], tree["m"])]
    worst = int(np.argmax(diffs))
    m_diff = diffs[worst]
    log(f"[{tag}] first moment: max|flat-tree|={m_diff:.3e} "
        f"max|m|={m_max:.3e} (tol {MOMENT_RTOL} x max|m|; worst in leaf "
        f"{worst} of shape {tree['m'][worst].shape})")
    check(m_diff <= MOMENT_RTOL * m_max, f"{tag}: gradients disagree")
    p_diff = 0.0
    for a, b, m in zip(flat["params"], tree["params"], tree["m"]):
        live = np.abs(m) >= LIVE_GRAD_SHARE * m_max
        if live.any():
            p_diff = max(p_diff, float(np.max(np.abs(a[live] - b[live]))))
    log(f"[{tag}] updated weights: max|flat-tree|={p_diff:.3e} where "
        f"|g| >= {LIVE_GRAD_SHARE} max|g| (tol {PARAM_LR_SHARE} x lr)")
    check(p_diff <= PARAM_LR_SHARE * LR, f"{tag}: updated weights disagree")


def oracle_check(tag, arch, step_impl, *, workers, accum, micro, **kw):
    """Flat/flat then tree/tree, built and run one after the other (the
    first run's device state is freed before the second is built)."""
    import gc
    runs = {}
    for impl in ("flat", "tree"):
        runs[impl] = run_one_step(arch, step_impl, impl, workers=workers,
                                  accum=accum, micro=micro, **kw)
        gc.collect()
        r = runs[impl]
        log(f"[{tag}] {impl}/{impl} step: compile_s={r['compile_s']:.1f} "
            f"memory_analysis temp={r['temp_gb']:.3f} GB "
            f"args={r['args_gb']:.3f} GB tpu_custom_call={r['pallas']} "
            f"matmul precision={kw.get('precision') or 'default'}")
    check(runs["flat"]["pallas"],
          f"{tag}: the flat-tail step holds no tpu_custom_call")
    compare_steps(tag, runs["flat"], runs["tree"],
                  need_var=step_impl == "fsdp_norm" or accum > 1)


# ------------------------------------------------------------- phase d ----

def main_run(tag: str, argv: list[str], devices):
    """The trainer's CLI entry point, in process; checks the run's health
    and prints its per-step times, compile count and peak memory."""
    from repro.launch.train import main as train_main
    log(f"[{tag}] python -m repro.launch.train {' '.join(argv)}")
    hist = train_main(argv)
    eng = hist["engine"]
    log(f"[{tag}] losses: {hist['loss']}")
    log(f"[{tag}] global batch per step: {hist['global_batch']}")
    log(f"[{tag}] step wall s (block_until_ready-fenced; the first of "
        f"each rung includes its compile): "
        f"{[round(s, 4) for s in hist['step_s']]}")
    log(f"[{tag}] engine: compiles={eng['compiles']} "
        f"warmups={eng['warmups']} warmup_failures={eng['warmup_failures']} "
        f"buckets_used={eng['buckets_used']} "
        f"disk_cache_hits={eng['disk_cache_hits']}")
    for i, mem in enumerate(memory(devices)):
        log(f"[{tag}] device {i} " + " ".join(f"{k}={v}"
                                             for k, v in mem.items()))
    check(hist["loss"] and all(math.isfinite(x) for x in hist["loss"]),
          f"{tag}: non-finite loss {hist['loss']}")
    check(eng["warmup_failures"] == 0,
          f"{tag}: {eng['warmup_failures']} AOT warmup compile(s) failed")
    return hist


def one_chip(devices):
    kernel_check()
    oracle_check("c", "microllama-300m", "accum_norm", workers=1, accum=2,
                 micro=4)
    hist = main_run("d", [
        "--arch", "microllama-300m", "--no-smoke", "--remat", "full",
        "--seq-len", str(SEQ), "--step-impl", "accum_norm",
        "--stats-impl", "flat", "--params-impl", "flat",
        "--schedule", "adaptive", "--aot-warmup", "--steps", "8",
        "--base-global-batch", "8", "--max-global-batch", "16",
        "--base-micro-batch", "4", "--max-micro-batch", "4",
        "--base-accum", "2", "--eval-every", "0"], devices)
    check(hist["engine"]["warmups"] >= 1,
          "d: the AOT warmup never compiled the second rung")


def four_chips(devices):
    # the paper's TinyLlama FSDP-Norm setup: micro-batch 2 per worker
    main_run("ii", [
        "--arch", "tinyllama-1.1b", "--no-smoke", "--remat", "full",
        "--seq-len", str(SEQ), "--step-impl", "fsdp_norm",
        "--stats-impl", "flat", "--params-impl", "flat", "--mesh-data", "4",
        "--schedule", "constant", "--steps", "4",
        "--base-global-batch", "8", "--max-global-batch", "8",
        "--base-micro-batch", "2", "--max-micro-batch", "2",
        "--base-accum", "1", "--eval-every", "0"], devices)
    # state built sharded: no device holds much more than the others
    peaks = [m["peak_bytes_in_use"] for m in memory(devices)]
    log(f"[ii] peak spread: max/min={max(peaks) / min(peaks):.4f} "
        f"(tol {PEAK_SPREAD})")
    check(max(peaks) <= PEAK_SPREAD * min(peaks),
          f"ii: peak memory uneven across devices: {peaks}")
    # f32 matmuls at full precision: at the default (one bf16 pass) the two
    # sharded programs' backward passes round differently, which moves
    # single gradient entries by up to ~0.5% of max|g| whatever the tail
    oracle_check("i", "microllama-300m", "fsdp_norm", workers=4, accum=1,
                 micro=2, precision="highest")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-d; 4: the four-chip FSDP-Norm path")
    args = ap.parse_args(argv)
    try:
        try:
            import repro  # noqa: F401
        except ImportError as e:
            raise SmokeFailure(f"the repro package is not beside this "
                               f"script (src/repro): {e}")
        devices = device_check(args.chips)
        from repro.distributed.coordination import enable_persistent_cache
        log(f"compile cache: {enable_persistent_cache()}")
        t0 = time.perf_counter()
        (one_chip if args.chips == 1 else four_chips)(devices)
        log(f"total wall s: {time.perf_counter() - t0:.1f}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
