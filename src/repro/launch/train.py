"""Training driver: adaptive / constant / stagewise batch-size pretraining.

Usable as a library (`run_training(TrainJob(...))` — benchmarks and examples
call this) and as a CLI:

    PYTHONPATH=src python -m repro.launch.train \
        --arch microllama-300m --schedule adaptive --eta 0.2 \
        --steps 200 --seq-len 128 --max-global-batch 256

runs the 2-layer smoke preset; `--no-smoke --remat full` runs the published
widths with activation recomputation (how a full-width model fits one chip).

The loop is Algorithm 1: for each step the controller's BatchPlan determines
the (M, J*micro, seq) stacked batch; the fused distributed step accumulates
over M, runs the norm test collectives and the AdamW update; the host
controller consumes (var_l1, grad_sqnorm) and emits the next plan.  A new
(M, micro) pair compiles once and is cached.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import time
import zlib
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.core.controller import (
    ControllerConfig, controller_state_as_dict, controller_state_from_dict,
    init_controller, controller_update)
from repro.core.schedule import (
    BatchPlan, ConstantSchedule, StagewiseSchedule, accum_free_plan,
    bucket_ladder, parse_ladder, round_plan)
from repro.data.pipeline import (
    MarkovTokens, UniformTokens, make_batch, pad_to_bucket)
from repro.distributed.coordination import (
    CoordinationError, enable_persistent_cache, make_coordinator)
from repro.distributed.engine import BucketedEngine
from repro.distributed.params import param_pspecs
from repro.distributed.train_step import make_fsdp_norm_step, make_accum_norm_step
from jax import set_mesh
from repro.launch.mesh import make_host_mesh, num_workers
from repro.models import build_model
from repro.optim.adamw import (
    AdamWConfig, init_adamw, init_adamw_flat, warmup_cosine)
from repro.checkpoint.store import (
    FLAT_PARAMS_META, flat_params_metadata, latest_step, restore_checkpoint,
    save_checkpoint)
from repro.testing.faults import fault_point


@dataclass
class TrainJob:
    arch: str = "microllama-300m"
    smoke: bool = True                    # --no-smoke: published widths
    remat: str = "none"                   # none | full (ModelConfig.remat)
    schedule: str = "adaptive"            # adaptive | constant | stagewise
    step_impl: str = "fsdp_norm"          # fsdp_norm | accum_norm
    variance_impl: str = "scalar"         # scalar | paper
    stats_impl: str = "tree"              # tree | flat (DESIGN §9 buffers)
    params_impl: str = "tree"             # tree | flat (DESIGN §10 resident)
    eta: float = 0.2
    steps: int = 200
    total_samples: int | None = None      # stop criterion (paper trains by samples)
    seq_len: int = 128
    base_global_batch: int = 16
    max_global_batch: int = 256
    base_micro_batch: int = 2
    max_micro_batch: int = 4
    base_accum: int = 2
    test_interval: int = 1
    ema: float = 0.0
    # predictive GNS companion (DESIGN §14): fit the smoothed B_simple
    # trajectory and AOT-warm the PREDICTED target rung instead of blindly
    # the next one.  Pure observer — the batch trajectory is identical with
    # predict on or off.
    predict: bool = False
    gns_alpha: float = 0.9
    slope_alpha: float = 0.5
    predict_horizon: int = 5
    # accumulation-free low rungs (DESIGN §14; Marek et al.): re-plan rungs
    # with global batch <= accum_free_below as M=1 plans run `M` times —
    # same samples per scheduled step, proportionally more optimizer steps.
    # accum_free_below=0 means auto (workers * max_micro_batch).
    accum_free: bool = False
    accum_free_below: int = 0
    stages: tuple = ((0.025, 16), (0.025, 64), (0.95, 256))
    peak_lr: float = 4e-4
    min_lr: float = 4e-5
    warmup_frac: float = 0.01
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    data: str = "markov"                  # markov | uniform
    data_seed: int = 0
    seed: int = 0
    mesh_data: int = 0                    # 0 => all devices on data axis
    mesh_model: int = 1
    # sequence-length warmup (paper §2; GrowLength/Llama-3 style): stages of
    # (fraction_of_samples, seq_len); empty = constant job.seq_len
    seq_stages: tuple = ()
    # bucketed step-compilation engine (DESIGN §8): 'auto' builds the
    # powers-of-two ladder from the batch knobs; 'off' recompiles per plan
    # (the pre-engine behavior); or an explicit 'micro:accum,micro:accum,...'
    bucket_ladder: str = "auto"
    aot_warmup: bool = False              # compile the next rung in background
    # multi-host warmup coordination (DESIGN §8.1): 'none' = uncoordinated
    # single-host engine (bit-identical to no coordination); 'file' = shared
    # directory (subprocess tests, NFS fleets); 'distributed' = jax.distributed
    coord: str = "none"                   # none | file | distributed
    coord_dir: str = ""                   # shared dir for --coord=file
    coord_rank: int = -1                  # -1: resolve from REPRO_COORD_RANK
    coord_world: int = 0                  # 0: resolve from REPRO_COORD_WORLD
    coord_timeout: float = 120.0          # barrier/agreement timeout seconds
                                          # (file coord; 'distributed' uses
                                          # the jax.distributed runtime's own
                                          # collective timeouts)
    # persistent XLA compile cache dir; $JAX_COMPILATION_CACHE_DIR wins, and
    # empty means <checkout>/.jax_cache (coordination.compile_cache_dir):
    # restarted / late-joining workers deserialize executables from disk
    compile_cache: str = ""
    eval_every: int = 25
    eval_batches: int = 4
    checkpoint_dir: str = ""
    # crash-safe training (DESIGN §12): checkpoint_every > 0 writes a
    # crash-atomic checkpoint (params/opt + controller state + samples
    # cursor) every N steps; --resume restarts from the newest complete
    # checkpoint in checkpoint_dir and reproduces the uninterrupted run's
    # losses BIT-identically (data/eval/LR are pure functions of the
    # restored step/samples cursors)
    checkpoint_every: int = 0
    resume: bool = False
    log_path: str = ""


def _make_source(job: TrainJob, vocab: int):
    if job.data == "markov":
        return MarkovTokens(vocab_size=vocab, seed=job.data_seed)
    return UniformTokens(vocab_size=vocab, seed=job.data_seed)


def _sds(batch):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)


def _shardings(mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))


def init_train_state(model, key, mesh, wrap, p_specs, o_specs, *,
                     stats_impl: str, params_impl: str):
    """(params, opt_state) born in the step's own layout (`wrap`, `p_specs`,
    `o_specs` from a step builder): each device materializes only its shard,
    so no full copy of the state ever lands on one device.  The weight tree
    is drawn first and flattened after, so every residency starts from
    bit-identical weights — and the same weights as an eager `model.init`."""
    layout = wrap.flat_layout
    params_like = jax.eval_shape(model.init, key)
    draw_specs = (p_specs if params_impl == "tree" else
                  param_pspecs(params_like, mesh, fsdp=True))
    params = jax.jit(model.init,
                     out_shardings=_shardings(mesh, draw_specs))(key)
    # flat moment buckets are padded to J-divisible sizes and SHARDED over
    # the data axes (DESIGN §9) — the state layout must match the step's
    opt_state = jax.jit(
        lambda: (init_adamw_flat(params_like, layout=layout)
                 if stats_impl == "flat" else init_adamw(params_like)),
        out_shardings=_shardings(mesh, o_specs))()
    if params_impl == "flat":
        # flat residency (DESIGN §10): the ONLY pack of the whole run —
        # from here on gradients are born flat and params stay buffers
        params = jax.jit(lambda p: tuple(layout.flatten(p)),
                         out_shardings=_shardings(mesh, tuple(p_specs)))(params)
    return params, opt_state


def model_config(job: TrainJob):
    """The job's ModelConfig: smoke preset or published widths, with the
    job's activation-recomputation policy."""
    cfg = get_smoke_config(job.arch) if job.smoke else get_config(job.arch)
    return cfg.replace(remat=job.remat)


def run_training(job: TrainJob) -> dict:
    # before any compile: every executable this job builds lands in (or
    # comes from) the persistent cache
    enable_persistent_cache(job.compile_cache)
    # run identity for the file coordinator: a digest of the job config
    # minus per-host fields, so every rank of THIS job (including restarts)
    # shares one coordination namespace while a different job pointed at a
    # reused --coord-dir can never replay this run's barrier/agreement state.
    # `resume` is excluded too: a crashed worker restarted with --resume is
    # the SAME run and must land in the same namespace — barrier files it
    # re-crosses while replaying its deterministic prefix already exist
    # there (the FileCoordinator restart contract)
    per_host = {"coord_rank", "log_path", "checkpoint_dir", "resume"}
    run_id = "job-%08x" % zlib.crc32(repr(sorted(
        (k, v) for k, v in dataclasses.asdict(job).items()
        if k not in per_host)).encode())
    coordinator = make_coordinator(job.coord, root=job.coord_dir,
                                   rank=job.coord_rank, world=job.coord_world,
                                   timeout=job.coord_timeout, run_id=run_id)
    cfg = model_config(job)
    model = build_model(cfg)
    key = jax.random.PRNGKey(job.seed)
    params_like = jax.eval_shape(model.init, key)

    n_dev = len(jax.devices())
    d = job.mesh_data or max(1, n_dev // job.mesh_model)
    mesh = make_host_mesh(data=d, model=job.mesh_model)
    workers = num_workers(mesh)

    opt_cfg = AdamWConfig(lr=job.peak_lr, weight_decay=job.weight_decay,
                          grad_clip=job.grad_clip)
    if job.step_impl == "fsdp_norm":
        wrap, p_specs, o_specs = make_fsdp_norm_step(
            model, opt_cfg, mesh, variance_impl=job.variance_impl,
            stats_impl=job.stats_impl, params_impl=job.params_impl,
            params_like=params_like)
    else:
        wrap, p_specs, o_specs = make_accum_norm_step(
            model, opt_cfg, mesh, stats_impl=job.stats_impl,
            params_impl=job.params_impl, params_like=params_like)
    # the ONE per-step-signature layout the builder compiled against —
    # shared with the optimizer state, the residency conversion, and the
    # checkpoint metadata (None on the pure tree path)
    layout = wrap.flat_layout
    params, opt_state = init_train_state(
        model, key, mesh, wrap, p_specs, o_specs,
        stats_impl=job.stats_impl, params_impl=job.params_impl)

    if job.bucket_ladder == "off":
        ladder = None
    elif job.bucket_ladder == "auto":
        # the ladder must cover every plan any schedule can emit, including
        # stagewise stages configured above max_global_batch
        top = max(job.max_global_batch, job.base_global_batch,
                  *([b for _, b in job.stages] if job.schedule == "stagewise"
                    else [0]))
        ladder = bucket_ladder(workers, job.base_micro_batch,
                               job.max_micro_batch, job.base_accum,
                               min(job.base_global_batch, top), top)
    else:
        ladder = parse_ladder(job.bucket_ladder, workers)

    # accum-free low rungs need their (M=1, J·mb) shapes ON the ladder or
    # the engine rejects them with LadderShapeError.  APPEND the extra rungs:
    # quantize_to_ladder's sort is stable, so on a capacity tie the original
    # accumulated rung still wins for normal plan quantization and the
    # accum-free branch selects its M=1 rung explicitly.
    accum_free_below = job.accum_free_below or workers * job.max_micro_batch
    if job.accum_free and ladder is not None:
        have = {(p.accum_steps, p.micro_batch) for p in ladder}
        extra = []
        for mb in sorted({p.micro_batch for p in ladder}):
            if (1, mb) not in have:
                extra.append(BatchPlan(global_batch=workers * mb,
                                       micro_batch=mb, accum_steps=1,
                                       workers=workers))
                have.add((1, mb))
        ladder = ladder + tuple(extra)

    ctrl_cfg = ControllerConfig(
        eta=job.eta, workers=workers,
        base_micro_batch=job.base_micro_batch,
        max_micro_batch=job.max_micro_batch, base_accum=job.base_accum,
        base_global_batch=job.base_global_batch,
        max_global_batch=job.max_global_batch,
        test_interval=job.test_interval, ema=job.ema, ladder=ladder,
        predict=job.predict, gns_alpha=job.gns_alpha,
        gns_groups="accum" if job.step_impl == "accum_norm" else "workers",
        slope_alpha=job.slope_alpha, predict_horizon=job.predict_horizon)
    ctrl = init_controller(ctrl_cfg)

    if job.schedule == "constant":
        schedule = ConstantSchedule(round_plan(
            job.base_global_batch, workers, job.base_micro_batch,
            job.max_micro_batch, job.base_accum, job.base_global_batch))
    elif job.schedule == "stagewise":
        schedule = StagewiseSchedule(tuple(job.stages), workers,
                                     job.base_micro_batch, job.max_micro_batch,
                                     job.base_accum, ladder=ladder)
    else:
        schedule = None

    total_samples = job.total_samples or job.steps * job.max_global_batch
    # the paper schedules the lr in SAMPLES (Table 5: warmup 1% of training
    # samples) — the only fair basis when batch sizes differ across schemes
    warmup_samples = max(1, int(job.warmup_frac * total_samples))

    source = _make_source(job, cfg.vocab_size)
    # held-out evaluation: same distribution (same Markov chain), disjoint
    # step-id stream => unseen sequences
    val_source = source
    VAL_STEP_BASE = 1_000_000_000

    extra_specs = {}
    if cfg.frontend.kind == "vision_stub":
        extra_specs["patch_embeds"] = (cfg.frontend.num_prefix_tokens, cfg.d_model)
    elif cfg.frontend.kind == "audio_stub":
        extra_specs["frames"] = (cfg.encoder.num_frames, cfg.d_model)

    compiled = {}
    eval_fn = {}

    engine = None
    if ladder is not None:
        engine = BucketedEngine(wrap, ladder, mesh=mesh,
                                params_like=_sds(params),
                                opt_like=_sds(opt_state),
                                aot_warmup=job.aot_warmup,
                                coordinator=coordinator)

    def get_step(plan: BatchPlan, batch):
        # legacy path (bucket_ladder='off'): one compile per (M, micro, seq)
        key_ = (plan.accum_steps, plan.micro_batch,
                batch["tokens"].shape[-1])
        if key_ not in compiled:
            compiled[key_] = wrap(_sds(batch))
        return compiled[key_]

    def eval_loss(params, step):
        bplan = BatchPlan(global_batch=workers * 2, micro_batch=2,
                          accum_steps=1, workers=workers)
        losses = []
        for i in range(job.eval_batches):
            vb = make_batch(val_source, VAL_STEP_BASE + i, bplan,
                            job.seq_len, extra_specs)
            vb = {k: jnp.asarray(v[0]) for k, v in vb.items()}
            if "eval" not in eval_fn:
                if job.params_impl == "flat":
                    # unflatten INSIDE the jit: the tree view is sliced out
                    # of the resident buffers, never materialized on host
                    eval_fn["eval"] = jax.jit(
                        lambda pb, b: model.loss(layout.unflatten(list(pb)),
                                                 b)[0])
                else:
                    eval_fn["eval"] = jax.jit(lambda p, b: model.loss(p, b)[0])
            losses.append(float(eval_fn["eval"](params, vb)))
        return float(np.mean(losses))

    history = {"step": [], "loss": [], "val_loss": [], "global_batch": [],
               "T": [], "var_l1": [], "grad_sqnorm": [], "samples": [],
               "time": [], "step_s": [], "accum_steps": [], "opt_steps": [],
               "pred_rung": [], "pred_eta": []}
    history["workers"] = workers
    samples = 0
    step = 0

    # ------------------------------------------------- crash-safe resume --
    # Restore the FULL loop state: params/opt (in this job's residency —
    # the like-tree was just built in it), the controller state machine,
    # and the step/samples cursors.  Everything else the loop consumes —
    # batches, eval batches, the LR — is a pure function of those cursors,
    # so the resumed trajectory is bit-identical to the uninterrupted one.
    resumed_from = None
    if job.resume:
        if not job.checkpoint_dir:
            raise ValueError("--resume requires --checkpoint-dir")
        ck = latest_step(job.checkpoint_dir)
        if ck is not None:
            state, meta = restore_checkpoint(
                job.checkpoint_dir, ck, {"params": params, "opt": opt_state})
            saved_job = meta.get("job", {})
            for f in ("arch", "step_impl", "stats_impl", "params_impl",
                      "schedule", "seed", "data_seed"):
                want, got = str(getattr(job, f)), str(saved_job.get(
                    f, getattr(job, f)))
                if got != want:
                    raise ValueError(
                        f"--resume config mismatch on {f!r}: checkpoint was "
                        f"saved with {got}, this job has {want}")
            params = jax.tree.map(jnp.asarray, state["params"])
            opt_state = jax.tree.map(jnp.asarray, state["opt"])
            step = ck
            samples = int(meta.get("samples", 0))
            if "controller" in meta:
                ctrl = controller_state_from_dict(meta["controller"])
            resumed_from = ck
    history["resumed_from"] = resumed_from

    last_saved = [-1]

    def save_state():
        """Crash-atomic full-state checkpoint at the CURRENT step (no-op
        without a checkpoint_dir, or when this step is already on disk)."""
        if not job.checkpoint_dir or last_saved[0] == step:
            return
        meta = {"job": dataclasses.asdict(job), "samples": samples,
                "controller": controller_state_as_dict(ctrl)}
        if job.stats_impl == "flat":
            # flat moments are raw bucketed buffers: record the STEP'S OWN
            # layout recipe (bucket size + worker count) — a reader on a
            # different backend/mesh must rebuild the SAME FlatLayout to
            # unflatten them
            meta["flat_layout"] = flat_params_metadata(layout)
        if job.params_impl == "flat":
            # flat-RESIDENT params save as raw buffers (params/0..N); the
            # recipe lets any reader — tree-resident, or flat on another
            # backend's bucket size — rebuild this exact layout and restore
            # bit-exactly (checkpoint.store.restore_params[_flat])
            meta[FLAT_PARAMS_META] = flat_params_metadata(layout)
        save_checkpoint(job.checkpoint_dir, step,
                        {"params": params, "opt": opt_state}, metadata=meta)
        last_saved[0] = step

    t0 = time.time()
    log_f = (open(job.log_path, "a" if resumed_from is not None else "w")
             if job.log_path else None)
    if log_f and resumed_from is None:
        log_f.write("step,samples,global_batch,accum,micro,loss,val_loss,T,var_l1,grad_sqnorm,wall_s\n")

    def seq_len_for(samples_done: int) -> int:
        if not job.seq_stages:
            return job.seq_len
        frac = samples_done / max(total_samples, 1)
        acc = 0.0
        for f, sl in job.seq_stages:
            acc += f
            if frac < acc:
                return sl
        return job.seq_stages[-1][1]

    try:
        with set_mesh(mesh):
            while samples < total_samples and step < job.steps:
                # injection site: the Nth call is the Nth step of the RUN,
                # not of this process — chaos tests key kill rules on it
                fault_point("train.step", step=step + 1)
                t_step = time.time()
                if schedule is not None:
                    plan = schedule.plan_for(samples, total_samples)
                else:
                    plan = ctrl.plan
                seq_len = seq_len_for(samples)
                batch_np = make_batch(source, step, plan, seq_len, extra_specs)
                bucket = None
                if engine is not None:
                    # no max_global clamp here: the ladder top is built to
                    # cover every schedule plan, including stagewise stages
                    # configured above max_global_batch (the controller
                    # clamps its own plans)
                    bucket = engine.bucket_for(plan.global_batch)

                # accum-free low rungs (DESIGN §14): re-plan this scheduled
                # step as M optimizer steps of the same (J·mb) microbatch.
                # Guards: the plan must BE its rung (a padded bucket could
                # leave an all-padding sub-step whose zero gradient still
                # weight-decays — not equivalent), and on a TESTED adaptive
                # step the M=1 sub-plan must still carry live variance
                # signal (FSDP-Norm with J>1 compares worker gradients;
                # ACCUM-NORM's M=1 variance is identically zero and would
                # kill the controller) — otherwise keep the accumulated
                # path for that step.
                tested = (job.schedule == "adaptive" and not ctrl.at_max
                          and (ctrl_cfg.test_interval <= 1
                               or (ctrl.step + 1) % ctrl_cfg.test_interval == 0))
                signal_alive = job.step_impl == "fsdp_norm" and workers > 1
                use_af = (job.accum_free and plan.accum_steps > 1
                          and plan.global_batch <= accum_free_below
                          and (bucket is None or bucket == plan)
                          and (job.schedule != "adaptive" or not tested
                               or signal_alive))

                if use_af:
                    sub_plan, repeats = accum_free_plan(plan)
                    sub_losses = []
                    for m in range(repeats):
                        sub_np = {k: v[m:m + 1] for k, v in batch_np.items()}
                        if engine is not None:
                            # (1, J·mb) is on the ladder by construction
                            # (the accum-free rungs appended above)
                            step_fn = engine.get_step(sub_np)
                            engine.observe(sub_plan, sub_plan)
                        sub_b = jax.tree.map(jnp.asarray, sub_np)
                        lr = warmup_cosine(samples, peak_lr=job.peak_lr,
                                           min_lr=job.min_lr,
                                           warmup_steps=warmup_samples,
                                           total_steps=total_samples)
                        if engine is None:
                            step_fn = get_step(sub_plan, sub_b)
                        params, opt_state, metrics = step_fn(
                            params, opt_state, sub_b, lr)
                        samples += sub_plan.global_batch
                        sub_losses.append(float(metrics["loss"]))
                    loss = float(np.mean(sub_losses))
                    # the last sub-step's var_l1 sits on the sub-batch scale
                    # (E[var_l1] ≈ trΣ·J/b): rescale to the scheduled plan's
                    # batch so the controller sees the accumulated-path scale
                    var_l1 = (float(metrics["var_l1"])
                              * sub_plan.global_batch / plan.global_batch)
                    gsq = float(metrics["grad_sqnorm"])
                    exec_plan, opt_steps = sub_plan, repeats
                else:
                    if engine is not None:
                        batch_np = pad_to_bucket(batch_np, plan, bucket)
                        step_fn = engine.get_step(batch_np)
                        engine.observe(plan, bucket)
                    batch = jax.tree.map(jnp.asarray, batch_np)
                    lr = warmup_cosine(samples, peak_lr=job.peak_lr,
                                       min_lr=job.min_lr,
                                       warmup_steps=warmup_samples,
                                       total_steps=total_samples)
                    if engine is None:
                        step_fn = get_step(plan, batch)
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch, lr)
                    var_l1 = float(metrics["var_l1"])
                    gsq = float(metrics["grad_sqnorm"])
                    loss = float(metrics["loss"])
                    samples += plan.global_batch
                    exec_plan, opt_steps = plan, 1
                step += 1
                # this step's wall time, fenced on the updated state (input
                # assembly and dispatch included; controller and eval not)
                jax.block_until_ready((params, opt_state))
                history["step_s"].append(time.time() - t_step)
                if job.schedule == "adaptive":
                    ctrl = controller_update(ctrl_cfg, ctrl, var_l1, gsq)
                if engine is not None:
                    # warmup AFTER the controller decision (DESIGN §14): warm
                    # the rung the fleet is actually headed to — the
                    # decided-growth rung when the controller just grew past
                    # this bucket, else the predicted target rung, else the
                    # next rung up.  The proposal is a pure function of
                    # globally-reduced stats, so every host proposes the same
                    # rung and PR 5's leader-decided agreement stays aligned.
                    proposal = None
                    if job.schedule == "adaptive":
                        if ctrl.plan.global_batch > bucket.global_batch:
                            proposal = engine.bucket_for(
                                ctrl.plan.global_batch)
                        elif job.predict and ctrl.pred_rung > bucket.global_batch:
                            proposal = engine.bucket_for(ctrl.pred_rung)
                    engine.warmup_agreed(bucket, batch_np, proposal=proposal)

                val = math.nan
                if job.eval_every and (step % job.eval_every == 0
                                       or step == job.steps):
                    val = eval_loss(params, step)

                t_stat = var_l1 / (job.eta**2 * gsq + 1e-30)
                history["step"].append(step)
                history["loss"].append(loss)
                history["val_loss"].append(val)
                history["global_batch"].append(plan.global_batch)
                history["T"].append(t_stat)
                history["var_l1"].append(var_l1)
                history["grad_sqnorm"].append(gsq)
                history["samples"].append(samples)
                history["time"].append(time.time() - t0)
                history["accum_steps"].append(exec_plan.accum_steps)
                history["opt_steps"].append(opt_steps)
                history["pred_rung"].append(
                    ctrl.pred_rung if job.schedule == "adaptive" else 0)
                history["pred_eta"].append(
                    ctrl.pred_eta_steps if job.schedule == "adaptive" else -1.0)
                if log_f:
                    log_f.write(
                        f"{step},{samples},{plan.global_batch},"
                        f"{exec_plan.accum_steps},{exec_plan.micro_batch},"
                        f"{loss:.4f},"
                        f"{val:.4f},{t_stat:.1f},{var_l1:.4g},{gsq:.4g},"
                        f"{time.time()-t0:.1f}\n")
                    log_f.flush()
                # save AFTER the step's metrics land (log line k precedes
                # checkpoint k: a resumed log never skips a line)
                if job.checkpoint_every and step % job.checkpoint_every == 0:
                    save_state()
    except CoordinationError as e:
        # a peer rank is dead or never arrived: the fleet cannot make
        # progress, but THIS rank's state is intact — checkpoint it and
        # exit cleanly (DESIGN §12) so a restarted fleet resumes from here
        # instead of from the last periodic save (or from scratch)
        save_state()
        history["coordination_failure"] = str(e)
        if log_f:
            log_f.close()
        if engine is not None:
            engine.drain(raise_errors=False)
        if coordinator is not None:
            coordinator.close()
        raise

    save_state()
    if log_f:
        log_f.close()
    if engine is not None:
        # failures were already recovered by get_step's sync fallback; they
        # surface as stats.warmup_failures rather than aborting the run
        engine.drain(raise_errors=False)
        history["engine"] = engine.stats.as_dict()
    if coordinator is not None:
        coordinator.close()
    # callers (benchmarks, examples) consume the pytree view
    history["final_params"] = (layout.unflatten(list(params))
                               if job.params_impl == "flat" else params)
    return history


def summarize(history: dict) -> dict:
    losses = [l for l in history["loss"] if math.isfinite(l)]
    vals = [v for v in history["val_loss"] if math.isfinite(v)]
    out = {
        "steps": history["step"][-1] if history["step"] else 0,
        "avg_batch": float(np.mean(history["global_batch"])) if history["global_batch"] else 0,
        "best_loss": min(losses) if losses else math.nan,
        "best_val_loss": min(vals) if vals else math.nan,
        "wall_s": history["time"][-1] if history["time"] else 0.0,
    }
    eng = history.get("engine")
    if eng:
        out["engine"] = {k: eng[k] for k in
                         ("compiles", "hit_rate", "padding_waste", "warmups",
                          "barrier_wait_s", "desyncs", "disk_cache_hits",
                          "transitions", "transition_hits")}
    return out


def parse_job(argv=None) -> TrainJob:
    """The CLI's TrainJob: one flag per field (`--no-x` for booleans)."""
    p = argparse.ArgumentParser()
    for f in dataclasses.fields(TrainJob):
        name = "--" + f.name.replace("_", "-")
        if f.type == "bool" or isinstance(f.default, bool):
            # --x / --no-x: a default-True switch can be turned off
            p.add_argument(name, action=argparse.BooleanOptionalAction,
                           default=f.default)
        elif f.name == "stages":
            p.add_argument(name, type=str, default=None,
                           help="e.g. '0.025:16,0.025:64,0.95:256'")
        else:
            typ = type(f.default) if f.default is not None else str
            if f.default is None:
                typ = int
            p.add_argument(name, type=typ, default=f.default)
    args = p.parse_args(argv)
    kw = vars(args)
    if isinstance(kw.get("stages"), str) and kw["stages"]:
        kw["stages"] = tuple((float(a), int(b)) for a, b in
                             (s.split(":") for s in kw["stages"].split(",")))
    elif kw.get("stages") is None:
        kw["stages"] = TrainJob.stages
    return TrainJob(**kw)


def main(argv=None) -> dict:
    """CLI entry point: prints the JSON summary and returns the full run
    history (engine stats included) to in-process callers."""
    hist = run_training(parse_job(argv))
    print(json.dumps(summarize(hist), indent=2))
    return hist


if __name__ == "__main__":
    main()
