"""Benchmark harness — one benchmark per paper table/figure + system benches.

    PYTHONPATH=src python -m benchmarks.run [--only NAME] [--steps N]

Output: ``name,us_per_call,derived`` CSV rows (harness contract), where
`derived` carries the table-specific payload (loss/val-loss/avg-batch/...).

Paper tables (CPU-scale analogs of Tables 1-3 / Figure 2 — same schemes,
reduced models; the full-scale reproduction path is launch/train.py on real
hardware):
  table1_microllama   adaptive(eta sweep) vs constant vs stagewise, DDP-Norm
  table2_tinyllama    same schemes under FSDP-Norm on a 4-worker mesh
                      (CPU subprocess with 4 host devices, like the paper's
                      4 GPUs)
  table3_openllama    adaptive vs constant vs stagewise, ACCUM-NORM variant
System benches:
  serve               continuous-batching serving tier under bursty
                      open-loop load (req/s, p99, warmed-rung transitions)
                      -> BENCH_serve.json
  norm_test_overhead  us/call of the eq.(5) statistic vs param count;
                      step-time overhead of testing every step
  kernel_micro        Pallas kernels (interpret) vs jnp reference oracles
  roofline_table      re-emits §Roofline terms from experiments/dryrun JSONs
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax
import jax.numpy as jnp


def _row(name, us_per_call, **derived):
    payload = ";".join(f"{k}={v}" for k, v in derived.items())
    print(f"{name},{us_per_call:.1f},{payload}", flush=True)


# names of the benches whose child process failed: main() exits non-zero
FAILED: list = []


def _cpu_child_env() -> dict:
    """Environment of a bench's child process.  The children are CPU checks
    (coordination, the 4-worker schedule): JAX_PLATFORMS=cpu keeps them off
    an accelerator this process may already hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    return env


# Per-step perf trajectory, written to --json-out (BENCH_step.json) so the
# numbers are tracked PR-over-PR: stats-path tail timings (tree vs flat,
# DESIGN §9) and per-step wall clock per engine bucket.
BENCH_JSON: dict = {}


# ------------------------------------------------------------ tables ----

def _train_scheme(arch, scheme, steps, *, eta=0.2, step_impl="accum_norm",
                  max_gb=64, base_gb=4, stages=None, seed=0):
    # the paper's comparison criterion: FIXED TOTAL SAMPLES for every scheme
    # (Tables 1-3 train each scheme on the same 2M sequences); steps differ.
    from repro.launch.train import TrainJob, run_training, summarize
    total_samples = steps * max_gb
    kw = dict(arch=arch, steps=10**9, total_samples=total_samples, seq_len=64,
              base_global_batch=base_gb,
              max_global_batch=max_gb, base_micro_batch=2, max_micro_batch=4,
              base_accum=2, eval_every=max(steps // 2, 1), eval_batches=2,
              data_seed=seed, step_impl=step_impl)
    if scheme == "adaptive":
        job = TrainJob(schedule="adaptive", eta=eta, **kw)
    elif scheme == "stagewise":
        job = TrainJob(schedule="stagewise",
                       stages=stages or ((0.025, base_gb), (0.025, base_gb * 4),
                                         (0.95, max_gb)), **kw)
    else:  # constant:<batch>
        b = int(scheme.split(":")[1])
        kw.update(base_global_batch=b, max_global_batch=b)
        job = TrainJob(schedule="constant", **kw)
    # repro: allow(unfenced-timing) — whole-run span; run_training/serving materializes host floats every step, so the wall clock cannot run ahead of device work
    t0 = time.time()
    hist = run_training(job)
    s = summarize(hist)
    us = (time.time() - t0) / max(s["steps"], 1) * 1e6
    return us, s


def _engine_payload(s):
    """engine_stats columns (compiles / cache hit rate / padding waste) for
    the benchmark rows — the tentpole's measurable recompile savings."""
    eng = s.get("engine")
    if not eng:
        return {}
    return {"compiles": eng["compiles"], "hit_rate": eng["hit_rate"],
            "pad_waste": eng["padding_waste"]}


def bench_table1_microllama(steps):
    """Paper Table 1: MicroLlama schemes under the norm test (CPU-scale)."""
    for scheme, eta in (("adaptive", 0.1), ("adaptive", 0.2),
                        ("constant:4", None), ("constant:64", None),
                        ("stagewise", None)):
        name = f"table1_microllama/{scheme}" + (f"_eta{eta}" if eta else "")
        us, s = _train_scheme("microllama-300m", scheme, steps, eta=eta or 0.2)
        _row(name, us, steps=s["steps"], avg_bsz=round(s["avg_batch"], 1),
             loss=round(s["best_loss"], 3), val_loss=round(s["best_val_loss"], 3),
             time_s=round(s["wall_s"], 1), **_engine_payload(s))


def bench_table2_tinyllama(steps):
    """Paper Table 2: TinyLlama under FSDP-Norm, J=4 workers (subprocess with
    4 forced host devices, mirroring the paper's 4-GPU setup)."""
    import subprocess
    code = f"""
import json, time
from repro.launch.train import TrainJob, run_training, summarize
for scheme, eta in (("adaptive", 0.08), ("constant", None), ("stagewise", None)):
    job = TrainJob(arch="tinyllama-1.1b", steps=10**9,
                   total_samples={steps} * 64, seq_len=64,
                   schedule=scheme, eta=eta or 0.2,
                   base_global_batch=8, max_global_batch=64,
                   stages=((0.025, 8), (0.025, 16), (0.95, 64)),
                   base_micro_batch=2, max_micro_batch=4, base_accum=1,
                   step_impl="fsdp_norm", mesh_data=4,
                   eval_every=10, eval_batches=2)
    t0 = time.time(); h = run_training(job); s = summarize(h)
    s["us"] = (time.time()-t0)/max(s["steps"],1)*1e6
    print("ROW", scheme, eta, json.dumps(s))
"""
    env = _cpu_child_env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=1800)
    if res.returncode != 0:
        _row("table2_tinyllama/FAILED", 0, err=res.stderr[-200:].replace("\n", " "))
        FAILED.append("table2_tinyllama")
        return
    for line in res.stdout.splitlines():
        if line.startswith("ROW"):
            _, scheme, eta, payload = line.split(" ", 3)
            s = json.loads(payload)
            name = f"table2_tinyllama/{scheme}" + (
                f"_eta{eta}" if eta != "None" else "")
            _row(name, s["us"], steps=s["steps"],
                 avg_bsz=round(s["avg_batch"], 1),
                 loss=round(s["best_loss"], 3),
                 val_loss=round(s["best_val_loss"], 3))


def bench_table3_openllama(steps):
    """Paper Table 3: OpenLlama schemes (ACCUM-NORM variant, short sequences
    mirroring the paper's 512-token OpenLlama runs)."""
    for scheme, eta in (("adaptive", 0.15), ("constant:8", None),
                        ("constant:64", None), ("stagewise", None)):
        name = f"table3_openllama/{scheme}" + (f"_eta{eta}" if eta else "")
        us, s = _train_scheme("openllama-3b", scheme, steps, eta=eta or 0.15,
                              base_gb=8)
        _row(name, us, steps=s["steps"], avg_bsz=round(s["avg_batch"], 1),
             loss=round(s["best_loss"], 3), val_loss=round(s["best_val_loss"], 3),
             time_s=round(s["wall_s"], 1), **_engine_payload(s))


def bench_engine_cache(steps):
    """Recompile savings of the bucketed engine (DESIGN §8): the same
    adaptive 4→64 schedule with the bucket ladder on vs off, plus the
    AOT-warmup variant.  Derived columns: traces compiled, cache hit rate,
    padding waste, wall seconds.  The ladder-on walls also land in
    BENCH_step.json['warmup_overlap'] — what overlapping the next rung's
    compile with training saves end-to-end — and a 2-process
    file-coordinated run emits the per-rank barrier-wait timings
    (BENCH_step.json['coordination'], DESIGN §8.1)."""
    from repro.launch.train import TrainJob, run_training, summarize
    walls = {}
    for tag, ladder, warm in (("ladder_auto", "auto", False),
                              ("ladder_auto_aot", "auto", True),
                              ("ladder_off", "off", False)):
        job = TrainJob(arch="llama3.2-1b", steps=min(steps, 25), seq_len=64,
                       base_global_batch=4, max_global_batch=64,
                       base_micro_batch=2, max_micro_batch=4, base_accum=2,
                       eta=0.12, step_impl="accum_norm", eval_every=0,
                       bucket_ladder=ladder, aot_warmup=warm)
        # repro: allow(unfenced-timing) — whole-run span; run_training/serving materializes host floats every step, so the wall clock cannot run ahead of device work
        t0 = time.time()
        h = run_training(job)
        s = summarize(h)
        walls[tag] = round(time.time() - t0, 3)
        payload = _engine_payload(s) or {"compiles": "n/a"}
        _row(f"engine_cache/{tag}",
             (time.time() - t0) / max(s["steps"], 1) * 1e6,
             steps=s["steps"], avg_bsz=round(s["avg_batch"], 1),
             wall_s=round(s["wall_s"], 1), **payload)
    BENCH_JSON["warmup_overlap"] = {
        "sync_wall_s": walls["ladder_auto"],
        "aot_wall_s": walls["ladder_auto_aot"],
        "no_ladder_wall_s": walls["ladder_off"],
        "saved_s": round(walls["ladder_auto"] - walls["ladder_auto_aot"], 3)}
    _bench_coordination()


_COORD_RANK_CODE = """
import json, sys
from repro.launch.train import TrainJob, run_training
rank, coord_dir = int(sys.argv[1]), sys.argv[2]
job = TrainJob(arch="llama3.2-1b", schedule="stagewise",
               stages=((0.5, 4), (0.5, 8)), steps=12, total_samples=48,
               seq_len=16, base_global_batch=4, max_global_batch=8,
               base_micro_batch=2, max_micro_batch=2, base_accum=2,
               step_impl="accum_norm", eval_every=0, aot_warmup=True,
               coord="file", coord_dir=coord_dir, coord_rank=rank,
               coord_world=2, coord_timeout=120.0)
h = run_training(job)
print("ENG", json.dumps(h["engine"]))
"""


def _bench_coordination():
    """Two file-coordinated processes over a stagewise 4→8 increase: the
    multi-host half of the engine story.  Reports per-rank barrier crossings
    and wait time (the coordination overhead a fleet pays per rung
    transition) plus warmups/hit-rate proving the post-increase step was a
    cache hit on both hosts.  Both ranks share the one persistent compile
    cache (`coordination.compile_cache_dir`)."""
    import subprocess
    import tempfile
    with tempfile.TemporaryDirectory() as tmp:
        env = _cpu_child_env()
        coord = os.path.join(tmp, "coord")
        procs = [subprocess.Popen(
            [sys.executable, "-c", _COORD_RANK_CODE, str(r), coord],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env) for r in range(2)]
        out = {}
        try:
            for r, p in enumerate(procs):
                stdout, stderr = p.communicate(timeout=600)
                if p.returncode != 0:
                    _row("engine_coord/FAILED", 0,
                         err=stderr[-200:].replace("\n", " "))
                    FAILED.append("engine_coord")
                    return
                eng = json.loads(next(l for l in stdout.splitlines()
                                      if l.startswith("ENG")).split(" ", 1)[1])
                out[f"rank{r}"] = {k: eng[k] for k in
                                   ("barriers", "barrier_wait_s", "desyncs",
                                    "warmups", "compiles", "hits", "hit_rate",
                                    "disk_cache_hits")}
                _row(f"engine_coord/rank{r}", eng["barrier_wait_s"] * 1e6,
                     barriers=eng["barriers"], warmups=eng["warmups"],
                     hit_rate=eng["hit_rate"], desyncs=eng["desyncs"])
        finally:
            # a failed (or timed-out) rank must not leave its peer orphaned
            # inside the tmp dir the with-block is about to delete
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.communicate()
        BENCH_JSON["coordination"] = out


# ----------------------------------------------------- system benches ----

def _flat_bench_tree(d: int, layers: int):
    """Transformer-like gradient pytree (deep-narrow shapes hit the
    leaf-count regime the flat path targets)."""
    t = {"embed": jnp.zeros((1024, d))}
    for i in range(layers):
        t[f"layer{i}"] = {
            "qkv": jnp.zeros((d, 3 * d)), "o": jnp.zeros((d, d)),
            "mlp_in": jnp.zeros((d, 4 * d)), "mlp_out": jnp.zeros((4 * d, d)),
            "ln1": jnp.zeros((d,)), "ln2": jnp.zeros((d,)),
        }
    return t


def _bench_pair(fa, aa, fb, ab, reps=6):
    """Interleaved timing (this box is noisy): returns (us_a, us_b)."""
    jax.block_until_ready(fa(*aa))
    jax.block_until_ready(fb(*ab))
    ta = tb = 0.0
    for _ in range(reps):
        t0 = time.time(); jax.block_until_ready(fa(*aa)); ta += time.time() - t0
        t0 = time.time(); jax.block_until_ready(fb(*ab)); tb += time.time() - t0
    return ta / reps * 1e6, tb / reps * 1e6


def bench_flat_stats(steps):
    """DESIGN §9 microbenchmark: the per-step statistics+update tail on its
    native layout — leaf-by-leaf pytree walk (tree) vs bucketed flat buffers
    + fused single-pass kernels (flat).  Rows land in the CSV and in
    BENCH_step.json['stats_path']; the grad-packing overhead (what a step
    pays to enter the flat layout when gradients arrive as a pytree) is
    measured separately and never hidden inside the tail numbers."""
    from repro.core.norm_test import tree_sqdiff, tree_sqnorm
    from repro.distributed.flatbuf import FlatLayout
    from repro.kernels import ops
    from repro.optim.adamw import (
        AdamWConfig, init_adamw, adamw_update, adamw_update_buffers,
        flat_opt_state)

    tiny = bool(os.environ.get("BENCH_TINY"))
    shapes = ((("tiny_0.2M", 64, 4),) if tiny else
              (("deep_19M", 128, 96), ("wide_13M", 512, 4)))
    cfg = AdamWConfig()
    reps = 3 if tiny else 6

    def randlike(seed, tree):
        leaves, td = jax.tree.flatten(tree)
        keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
        return td.unflatten([jax.random.normal(k, l.shape)
                             for k, l in zip(keys, leaves)])

    for tag, d, layers in shapes:
        like = _flat_bench_tree(d, layers)
        n = sum(x.size for x in jax.tree.leaves(like))
        params = randlike(0, like)
        gj, g = randlike(1, like), randlike(2, like)
        state = init_adamw(params)
        state["m"] = randlike(3, like)
        state["v"] = jax.tree.map(jnp.abs, randlike(4, like))
        layout = FlatLayout.from_tree(params)
        pb, gjb, gb = (layout.flatten(t) for t in (params, gj, g))
        fstate = flat_opt_state(params, state)
        mb, vb = list(fstate["m"]), list(fstate["v"])
        lr, count = jnp.float32(1e-3), state["count"]

        def tree_tail(params, gj, g, m, v, lr):
            var = tree_sqdiff(gj, g)
            gsq = tree_sqnorm(g)
            st = {"m": m, "v": v, "count": count}
            p2, st2, gn = adamw_update(params, g, st, cfg, lr)
            return var, gsq, gn, p2, st2

        def flat_tail(pb, gjb, gb, mb, vb, lr):
            var = gsq = jnp.zeros((), jnp.float32)
            for a, b in zip(gjb, gb):
                dd, qq = ops.stats_flat(a, b)
                var += dd
                gsq += qq
            out = adamw_update_buffers(pb, gb, mb, vb, cfg, lr, count,
                                       grad_sqnorm=gsq)
            return (var, gsq) + tuple(out)

        tree_us, flat_us = _bench_pair(
            jax.jit(tree_tail), (params, gj, g, state["m"], state["v"], lr),
            jax.jit(flat_tail), (pb, gjb, gb, mb, vb, lr), reps=reps)
        pack = jax.jit(layout.flatten)
        jax.block_until_ready(pack(g))
        t0 = time.time()
        for _ in range(reps):
            out = pack(g)
        jax.block_until_ready(out)
        pack_us = (time.time() - t0) / reps * 1e6

        entry = {"params": n, "leaves": layout.num_leaves,
                 "buckets": layout.num_buffers,
                 "tree_us": round(tree_us, 1), "flat_us": round(flat_us, 1),
                 "speedup": round(tree_us / max(flat_us, 1e-9), 3),
                 "pack_grads_us": round(pack_us, 1)}
        BENCH_JSON.setdefault("stats_path", {})[tag] = entry
        _row(f"flat_stats/{tag}/tree", tree_us, params=n,
             leaves=layout.num_leaves)
        _row(f"flat_stats/{tag}/flat", flat_us, params=n,
             buckets=layout.num_buffers, speedup=entry["speedup"],
             pack_us=round(pack_us, 1))

        # unflatten-under-grad adjoint characterization (ROADMAP: the
        # slice-transpose cost that gates flat-resident params, DESIGN §10).
        # Three ways to obtain the flat gradient of the same loss:
        #   pad_add    — autodiff straight through `unflatten` (XLA's native
        #                slice adjoint: per-slot zero-pad + N-way add)
        #   pack_vjp   — `unflatten_for_grad`'s explicit adjoint (one
        #                ravel+concat per bucket)
        #   grad_pack  — the OLD dataflow: materialize the gradient pytree,
        #                then flatten it (what flat residency deletes)
        def adjoint_loss(t):
            return tree_sqdiff(t, params)        # nonlinear enough, 1 read

        pad_add = jax.jit(jax.grad(
            lambda bufs: adjoint_loss(layout.unflatten(list(bufs)))))
        pack_vjp = jax.jit(jax.grad(
            lambda bufs: adjoint_loss(layout.unflatten_for_grad(bufs))))
        grad_pack = jax.jit(
            lambda t: layout.flatten(jax.grad(adjoint_loss)(t)))
        bufs = tuple(pb)
        pad_us, vjp_us = _bench_pair(pad_add, (bufs,), pack_vjp, (bufs,),
                                     reps=reps)
        _, gp_us = _bench_pair(pack_vjp, (bufs,), grad_pack, (g,), reps=reps)
        adj = {"pad_add_us": round(pad_us, 1),
               "pack_vjp_us": round(vjp_us, 1),
               "tree_grad_pack_us": round(gp_us, 1)}
        BENCH_JSON.setdefault("unflatten_adjoint", {})[tag] = adj
        _row(f"flat_stats/{tag}/unflatten_adjoint", vjp_us, **adj)

    _bench_step_per_bucket(4 if tiny else min(steps, 12))


def _bench_step_per_bucket(nsteps):
    """Per-step wall clock at EVERY ladder rung, across the three residency
    paths — the engine/bucket half of BENCH_step.json:

      tree          — stats_impl=tree,  params_impl=tree (the oracle)
      flat          — stats_impl=flat,  params_impl=tree (DESIGN §9: fused
                      tail, mean gradient packed once per step)
      flat_resident — stats_impl=flat,  params_impl=flat (DESIGN §10:
                      gradients born flat, ZERO packs per step — its
                      pack_us is structurally 0, guarded by the tier-1
                      `count_layout_ops` marker-eqn test)

    Each rung gets its own constant-batch FSDP-Norm step (the paper's
    primary distributed step, and the one where flat residency deletes the
    most per-step layout movement: both gradient packs, the params pack,
    and the new-params unflatten) pinned to that rung's capacity (the old
    adaptive run only ever produced steady-state timings for the top rung
    it settled into), the compile step is excluded (warmup call), and the
    flat path's per-step gradient PACK time is measured separately against
    the model's own parameter tree — never hidden inside the step means."""
    from repro.core.schedule import bucket_ladder
    from repro.distributed.flatbuf import FlatLayout

    from jax import set_mesh
    from repro.configs import get_smoke_config
    from repro.data.pipeline import MarkovTokens, make_batch
    from repro.distributed.train_step import make_fsdp_norm_step
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.optim.adamw import AdamWConfig, init_adamw, init_adamw_flat

    IMPLS = (("tree", "tree", "tree"), ("flat", "flat", "tree"),
             ("flat_resident", "flat", "flat"))
    base_gb, max_gb = 4, 16
    ladder = bucket_ladder(workers=1, micro_batch=2, max_micro_batch=2,
                           base_accum=2, base_global=base_gb,
                           max_global=max_gb)
    out = {tag: {} for tag, _, _ in IMPLS}

    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    mesh = make_host_mesh(data=1, model=1)
    src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
    opt_cfg = AdamWConfig()
    lr = jnp.float32(1e-3)
    params_like = model.init(jax.random.PRNGKey(0))
    # the deltas at stake (one gradient pack, ~hundreds of µs) sit far below
    # this shared 2-core box's run-to-run drift (the agent harness shares
    # the cores), so the three impls are timed STEP-BY-STEP round-robin —
    # rotating the cycle order every iteration — instead of run-by-run:
    # drift at the seconds scale hits all three equally
    reps = 2 if os.environ.get("BENCH_TINY") else 5
    with set_mesh(mesh):
        for rung in ladder:
            batch = jax.tree.map(jnp.asarray,
                                 make_batch(src, 0, rung, 32))
            sds_b = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
            runners = {}
            for tag, stats_impl, params_impl in IMPLS:
                params = model.init(jax.random.PRNGKey(0))
                wrap, _, _ = make_fsdp_norm_step(
                    model, opt_cfg, mesh, stats_impl=stats_impl,
                    params_impl=params_impl, params_like=params)
                layout = wrap.flat_layout
                opt = (init_adamw_flat(params, layout=layout)
                       if stats_impl == "flat" else init_adamw(params))
                if params_impl == "flat":
                    params = tuple(layout.flatten(params))
                fn = wrap(sds_b)
                # warmup = compile (the steps donate: thread the state)
                params, opt, _ = fn(params, opt, batch, lr)
                jax.block_until_ready(params)
                runners[tag] = [fn, params, opt]
            dts = {tag: [] for tag in runners}
            for i in range(nsteps * reps):
                rot = i % len(IMPLS)
                for tag, _, _ in IMPLS[rot:] + IMPLS[:rot]:
                    r = runners[tag]
                    t0 = time.time()
                    p, o, _ = r[0](r[1], r[2], batch, lr)
                    jax.block_until_ready(p)
                    dts[tag].append(time.time() - t0)
                    r[1], r[2] = p, o
            for tag, samples in dts.items():
                # the box shares its 2 cores with other processes, so
                # samples are bimodal (quiet vs contended) with a heavy
                # straggler tail; the headline `mean_us` is trimmed at 2x
                # the median, with `median_us` and `min_us` (noise floor —
                # contention is strictly additive) alongside so the
                # flat-resident-vs-flat delta can be read against the
                # noise: at 3-bucket smoke scale the two are within a few
                # percent either way (the structural difference — zero
                # packs — is pinned by the tier-1 op-count test, and the
                # deep-tree stats_path/unflatten_adjoint shapes above are
                # where it is measurable)
                med = sorted(samples)[len(samples) // 2]
                kept = [d for d in samples if d <= 2 * med] or samples
                out[tag][str(rung.global_batch)] = {
                    "steps": len(kept),
                    "outliers_dropped": len(samples) - len(kept),
                    "mean_us": round(sum(kept) / len(kept) * 1e6, 1),
                    "median_us": round(med * 1e6, 1),
                    "min_us": round(min(samples) * 1e6, 1)}
    for impl, rungs in out.items():
        out[impl] = dict(sorted(rungs.items(), key=lambda kv: int(kv[0])))

    # pack overhead, reported separately (param-SHAPED tree, same layout
    # the flat steps use — pack time is shape-only, the values don't
    # matter): what one flatten of the gradient-shaped tree costs.  The
    # flat-resident path never performs it — its steady-state pack count is
    # 0 (tier-1 op-count guarded), so its pack_us is identically 0.
    layout = FlatLayout.from_tree(params_like)
    pack = jax.jit(layout.flatten)
    jax.block_until_ready(pack(params_like))
    t0 = time.time()
    reps = 5
    for _ in range(reps):
        packed = pack(params_like)
    jax.block_until_ready(packed)
    pack_us = round((time.time() - t0) / reps * 1e6, 1)
    for e in out["flat"].values():
        e["pack_us"] = pack_us
    for e in out["flat_resident"].values():
        e["pack_us"] = 0.0
        e["packs_per_step"] = 0

    for tag, rungs in out.items():
        for k, e in rungs.items():
            _row(f"flat_stats/step_bucket{k}/{tag}", e["mean_us"],
                 steps=e["steps"], **({"pack_us": e["pack_us"]}
                                      if "pack_us" in e else {}))
    BENCH_JSON["step_per_bucket"] = out


def bench_serve(steps):
    """Continuous-batching serving tier (DESIGN §11) under bursty open-loop
    load: sustained req/s, p50/p99 request latency, decode tok/s, engine
    cache counters, and the steady-state probe (a request-batch-size change
    served from a warmed rung: transition cache hits, ZERO new compiles).
    Lands in BENCH_serve.json — its own trajectory file, separate from the
    training-side BENCH_step.json."""
    from repro.launch.serve import run_continuous_serving
    tiny = bool(os.environ.get("BENCH_TINY"))
    load = dict(max_slots=8, prompt_len=4, gen_len=8,
                load_steps=30 if tiny else max(steps, 60),
                arrival_rate=0.5, burst_every=10 if tiny else 20,
                burst_size=5, aot_warmup=True)
    # repro: allow(unfenced-timing) — whole-run span; run_training/serving materializes host floats every step, so the wall clock cannot run ahead of device work
    t0 = time.time()
    res = run_continuous_serving("llama3.2-1b", smoke=True, **load)
    us = (time.time() - t0) / max(res["engine"]["steps"], 1) * 1e6
    _row("serve/bursty", us,
         req_per_s=round(res["sustained_req_per_s"], 2),
         p99_s=round(res["p99_latency_s"], 3),
         tok_per_s=round(res["decode_tok_per_s"], 1),
         hit_rate=res["engine"]["hit_rate"],
         steady_hit=res["probe"]["steady_state_transition_hit"])
    out = {"load": load, **{k: v for k, v in res.items() if k != "rung_trace"},
           "rung_trace": res["rung_trace"][:64]}
    path = os.path.join(os.getcwd(), "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")


def bench_norm_test_overhead(steps):
    """us/call of the eq.(5) reduction at increasing gradient sizes, plus
    step-time overhead of test_interval=1 vs no testing."""
    from repro.core.norm_test import tree_sqdiff, tree_sqnorm
    key = jax.random.PRNGKey(0)
    for n in (1 << 16, 1 << 20, 1 << 23):
        g1 = {"w": jax.random.normal(key, (n,))}
        g2 = {"w": jax.random.normal(jax.random.PRNGKey(1), (n,))}
        f = jax.jit(lambda a, b: (tree_sqdiff(a, b), tree_sqnorm(b)))
        jax.block_until_ready(f(g1, g2)[0])
        t0 = time.time()
        reps = 20
        for _ in range(reps):
            r, _ = f(g1, g2)
        r.block_until_ready()
        us = (time.time() - t0) / reps * 1e6
        _row(f"norm_test_stat/{n}", us, params=n,
             gb_per_s=round(2 * 4 * n / (us / 1e6) / 1e9, 2))

    from repro.launch.train import TrainJob, run_training
    for tag, interval in (("test_every_step", 1), ("test_off", 10**9)):
        job = TrainJob(arch="llama3.2-1b", steps=min(steps, 12), seq_len=64,
                       base_global_batch=8, max_global_batch=8,
                       base_micro_batch=2, max_micro_batch=2, base_accum=2,
                       step_impl="accum_norm", test_interval=interval,
                       eval_every=0)
        t0 = time.time()
        hist = run_training(job)
        us = (time.time() - t0) / len(hist["step"]) * 1e6
        _row(f"norm_test_overhead/{tag}", us, steps=len(hist["step"]))


def bench_kernel_micro(steps):
    """Pallas kernels (interpret mode on CPU — correctness path) vs oracles."""
    from repro.kernels import ops, ref

    key = jax.random.PRNGKey(0)

    def timeit(f, *args, reps=5):
        jax.block_until_ready(f(*args))
        t0 = time.time()
        for _ in range(reps):
            out = f(*args)
        jax.block_until_ready(out)
        return (time.time() - t0) / reps * 1e6

    n = 1 << 20
    x = jax.random.normal(key, (n,))
    y = x + 0.01
    _row("kernel/sqdiff_norm_pallas", timeit(lambda a, b: ops.sqdiff_norm(a, b), x, y))
    _row("kernel/sqdiff_norm_ref", timeit(jax.jit(ref.sqdiff_norm_ref), x, y))

    b, t, h, d = 1, 512, 4, 64
    q = jax.random.normal(key, (b, t, h, d))
    k = jax.random.normal(key, (b, t, h, d))
    v = jax.random.normal(key, (b, t, h, d))
    _row("kernel/flash_attention_pallas",
         timeit(lambda a, c, e: ops.flash_attention(a, c, e, block_q=256,
                                                    block_kv=256), q, k, v))
    _row("kernel/attention_ref",
         timeit(jax.jit(lambda a, c, e: ref.attention_ref(a, c, e)), q, k, v))

    xw = jax.random.normal(key, (4096, 1024))
    sc = jnp.ones((1024,))
    _row("kernel/rmsnorm_pallas", timeit(lambda a, s: ops.rmsnorm(a, s), xw, sc))
    _row("kernel/rmsnorm_ref", timeit(jax.jit(ref.rmsnorm_ref), xw, sc))


def bench_roofline_table(steps):
    """Emit §Roofline rows from the dry-run artifacts (single-pod)."""
    base = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "experiments", "dryrun")
    for path in sorted(glob.glob(os.path.join(base, "*__16x16.json"))):
        d = json.load(open(path))
        rl = d["roofline"]
        _row(f"roofline/{d['arch']}/{d['shape']}", d["compile_s"] * 1e6,
             compute_s=f"{rl['compute_s']:.3g}",
             memory_s=f"{rl['memory_s']:.3g}",
             collective_s=f"{rl['collective_s']:.3g}",
             bottleneck=rl["bottleneck"],
             useful=f"{rl['useful_ratio']:.2f}")


def bench_norm_test_knobs(steps):
    """Beyond-paper knobs (DESIGN §7.4): test interval and EMA smoothing of
    T_k — overhead amortization vs schedule fidelity."""
    from repro.launch.train import TrainJob, run_training, summarize
    for tag, interval, ema in (("interval1", 1, 0.0), ("interval5", 5, 0.0),
                               ("interval1_ema0.7", 1, 0.7)):
        job = TrainJob(arch="llama3.2-1b", steps=10**9,
                       total_samples=steps * 32, seq_len=64,
                       base_global_batch=4, max_global_batch=64,
                       base_micro_batch=2, max_micro_batch=4, base_accum=2,
                       eta=0.15, step_impl="accum_norm",
                       test_interval=interval, ema=ema, eval_every=0)
        import time as _t
        t0 = _t.time()
        h = run_training(job)
        ss = summarize(h)
        _row(f"norm_test_knobs/{tag}", (_t.time() - t0) / max(ss["steps"], 1) * 1e6,
             steps=ss["steps"], avg_bsz=round(ss["avg_batch"], 1),
             loss=round(ss["best_loss"], 3),
             final_bsz=h["global_batch"][-1])


def bench_gns_predict(steps):
    """Predictive GNS controller (DESIGN §14): the same adaptive schedule
    with the predictor on vs off, AOT warmup enabled in both.  Emits the
    prediction trajectory into BENCH_step.json['gns_prediction'] — the
    predicted vs actual rung-crossing step and whether warmup turned each
    measured rung transition into a cache hit (the acceptance claim: under
    prediction, transition_hits == transitions with zero foreground compiles
    at a transition)."""
    from repro.launch.train import TrainJob, run_training, summarize
    out = {}
    for tag, predict in (("predict", True), ("baseline", False)):
        job = TrainJob(arch="llama3.2-1b", steps=min(steps, 25), seq_len=64,
                       base_global_batch=32, max_global_batch=64,
                       base_micro_batch=2, max_micro_batch=2, base_accum=2,
                       eta=0.12, step_impl="accum_norm", eval_every=0,
                       aot_warmup=True, predict=predict)
        # repro: allow(unfenced-timing) — whole-run span; run_training/serving materializes host floats every step, so the wall clock cannot run ahead of device work
        t0 = time.time()
        h = run_training(job)
        s = summarize(h)
        wall = round(time.time() - t0, 3)
        eng = h["engine"]
        # actual crossing: first step whose executed batch left the base rung
        base_gb = h["global_batch"][0]
        actual = next((st for st, gb in zip(h["step"], h["global_batch"])
                       if gb > base_gb), -1)
        # predicted crossing: first step that forecast a rung above base
        predicted = next((st for st, r in zip(h["step"], h["pred_rung"])
                          if r > base_gb), -1)
        out[tag] = {
            "wall_s": wall,
            "transitions": eng["transitions"],
            "transition_hits": eng["transition_hits"],
            "compiles": eng["compiles"],
            "warmups": eng["warmups"],
            "actual_crossing_step": actual,
            "predicted_crossing_step": predicted,
            "pred_rung_trace": h["pred_rung"],
            "pred_eta_trace": [round(e, 3) for e in h["pred_eta"]],
            "batch_trace": h["global_batch"],
        }
        _row(f"gns_predict/{tag}", wall / max(s["steps"], 1) * 1e6,
             steps=s["steps"], transitions=eng["transitions"],
             transition_hits=eng["transition_hits"],
             compiles=eng["compiles"], actual_cross=actual,
             predicted_cross=predicted)
    BENCH_JSON["gns_prediction"] = out


BENCHES = {
    "table1_microllama": bench_table1_microllama,
    "table2_tinyllama": bench_table2_tinyllama,
    "table3_openllama": bench_table3_openllama,
    "engine_cache": bench_engine_cache,
    "gns_predict": bench_gns_predict,
    "serve": bench_serve,
    "flat_stats": bench_flat_stats,
    "norm_test_overhead": bench_norm_test_overhead,
    "norm_test_knobs": bench_norm_test_knobs,
    "kernel_micro": bench_kernel_micro,
    "roofline_table": bench_roofline_table,
}


def main(argv=None) -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None,
                   help="comma-separated bench names (default: all)")
    p.add_argument("--steps", type=int, default=40)
    p.add_argument("--json-out", default="BENCH_step.json",
                   help="where the per-step perf trajectory JSON lands; "
                        "existing top-level keys from other benches are "
                        "preserved (merge-update, so --only runs don't "
                        "clobber the rest of the trajectory)")
    p.add_argument("--baseline", default=None,
                   help="committed BENCH_step.json to gate the fresh "
                        "step_per_bucket times against (perf_gate; exits 1 "
                        "on a measured regression)")
    p.add_argument("--gate-mult", type=float, default=None,
                   help="gate multiplier (default $BENCH_GATE_MULT or 8.0)")
    args = p.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None
    if only and (unknown := only - set(BENCHES)):
        p.error(f"unknown bench(es): {sorted(unknown)}")
    from repro.distributed.coordination import enable_persistent_cache
    enable_persistent_cache()
    print("name,us_per_call,derived")
    for name, fn in BENCHES.items():
        if only and name not in only:
            continue
        fn(args.steps)
    if BENCH_JSON and args.json_out:
        merged = {}
        if os.path.exists(args.json_out):
            try:
                with open(args.json_out) as f:
                    merged = json.load(f)
            except (OSError, json.JSONDecodeError):
                merged = {}
        merged.update(BENCH_JSON)
        with open(args.json_out, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
            f.write("\n")
    if args.baseline:
        # gate AFTER the merge so the comparison sees the full trajectory
        from benchmarks.perf_gate import run_gate
        if run_gate(args.json_out, args.baseline, args.gate_mult):
            raise SystemExit(1)
    if FAILED:
        raise SystemExit(f"child process failed in: {', '.join(FAILED)}")


if __name__ == "__main__":
    main()
