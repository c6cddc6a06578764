"""Bucketed step-compilation engine (DESIGN §8): compile-count regression,
padding exactness, ladder-quantization properties, end-to-end stats."""
import numpy as np
import pytest
from proptest import given, settings, st

import jax
import jax.numpy as jnp

from repro.core.controller import (
    ControllerConfig, init_controller, controller_update)
from repro.core.schedule import (
    BatchPlan, bucket_ladder, parse_ladder, quantize_to_ladder, round_plan)
from repro.data.pipeline import MarkovTokens, make_batch, pad_to_bucket
from repro.distributed.engine import BucketedEngine


# ------------------------------------------------------------- ladder ----

def test_ladder_covers_range_and_is_sorted():
    ladder = bucket_ladder(workers=8, micro_batch=4, max_micro_batch=8,
                           base_accum=16, base_global=256, max_global=8192)
    caps = [p.global_batch for p in ladder]
    assert caps == sorted(caps)
    assert caps[0] <= 256 * 2          # base rung near the base batch
    assert caps[-1] == round_plan(8192, 8, 4, 8, 16, 8192).global_batch
    for p in ladder:
        assert p.global_batch == p.workers * p.accum_steps * p.micro_batch
        assert p.micro_batch <= 8


def test_parse_ladder_and_rejects_nonincreasing():
    ladder = parse_ladder("2:1,2:2,4:2,4:4", workers=2)
    assert [p.global_batch for p in ladder] == [4, 8, 16, 32]
    with pytest.raises(ValueError):
        parse_ladder("4:4,2:2", workers=2)


@given(desired=st.integers(1, 10_000_000),
       workers=st.sampled_from([1, 2, 8]),
       micro=st.sampled_from([1, 2, 4]), max_micro=st.sampled_from([8, 16]),
       accum=st.sampled_from([1, 2, 16]),
       max_global=st.sampled_from([512, 8192]))
@settings(max_examples=200, deadline=None)
def test_quantize_never_shrinks_and_respects_max(desired, workers, micro,
                                                 max_micro, accum, max_global):
    base = workers * micro
    ladder = bucket_ladder(workers, micro, max_micro, accum, base, max_global)
    rung = quantize_to_ladder(desired, ladder, max_global)
    assert rung in ladder
    top = ladder[-1].global_batch
    # never shrinks: any request a rung can cover gets a covering rung
    assert rung.global_batch >= min(desired, max_global, top)
    # respects the cap: no rung exceeds max_global
    assert rung.global_batch <= max_global


# ------------------------------------------------------------ padding ----

def _plan(gb, micro, accum, workers=1):
    return BatchPlan(global_batch=gb, micro_batch=micro, accum_steps=accum,
                     workers=workers)


def test_pad_to_bucket_layout_and_mask():
    src = MarkovTokens(vocab_size=64, seed=0)
    plan = _plan(5, 1, 5)
    bucket = _plan(16, 2, 8)
    batch = make_batch(src, 0, plan, seq_len=8)
    padded = pad_to_bucket(batch, plan, bucket)
    assert padded["tokens"].shape == (8, 2, 8)
    flat_lab = padded["labels"].reshape(16, 8)
    flat_ref = batch["labels"].reshape(5, 8)
    np.testing.assert_array_equal(flat_lab[:5], flat_ref)
    assert (flat_lab[5:] == -1).all()          # padded slots fully masked
    # identical bucket shape -> no-op
    same = pad_to_bucket(batch, plan, _plan(5, 1, 5))
    assert same is batch


def test_padded_batch_identical_loss_and_grads():
    """The acceptance bar: padded vs unpadded batch produce the same loss and
    the same updated parameters to 1e-5 (accum_norm, 1-worker mesh)."""
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.launch.mesh import make_host_mesh
    from repro.distributed.train_step import make_accum_norm_step
    from repro.optim.adamw import AdamWConfig, init_adamw
    from jax import set_mesh

    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    mesh = make_host_mesh(data=1, model=1)
    src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
    plan = _plan(6, 2, 3)                      # 6 real samples in 3 microbatches
    bucket = _plan(16, 2, 8)                   # 10 padded slots, 5 empty rows
    batch = make_batch(src, 0, plan, seq_len=16)
    padded = pad_to_bucket(batch, plan, bucket)

    outs = {}
    for tag, b in (("plain", batch), ("padded", padded)):
        params = model.init(jax.random.PRNGKey(0))   # fresh: steps donate args
        opt = init_adamw(params)
        wrap, _, _ = make_accum_norm_step(model, AdamWConfig(), mesh,
                                          params_like=params)
        fn = wrap(jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.tree.map(jnp.asarray, b)))
        with set_mesh(mesh):
            p2, _, m = fn(params, opt, jax.tree.map(jnp.asarray, b),
                          jnp.float32(1e-3))
        outs[tag] = (p2, m)

    lp, lm = outs["plain"][1], outs["padded"][1]
    assert abs(float(lp["loss"]) - float(lm["loss"])) < 1e-5
    assert abs(float(lp["grad_sqnorm"]) - float(lm["grad_sqnorm"])) < 1e-4 * \
        max(float(lp["grad_sqnorm"]), 1.0)
    for a, b in zip(jax.tree.leaves(outs["plain"][0]),
                    jax.tree.leaves(outs["padded"][0])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-5, atol=1e-5)


# ----------------------------------------------- compile-count caching ----

def test_one_trace_per_bucket_256_to_8192():
    """Regression for the tentpole claim: a simulated adaptive schedule that
    grows 256→8192 builds EXACTLY one step per ladder rung it visits; every
    other step is a cache hit."""
    cfg = ControllerConfig(eta=0.2, workers=8, base_micro_batch=4,
                           max_micro_batch=8, base_accum=8,
                           base_global_batch=256, max_global_batch=8192)
    ladder = bucket_ladder(cfg.workers, cfg.base_micro_batch,
                           cfg.max_micro_batch, cfg.base_accum,
                           cfg.base_global_batch, cfg.max_global_batch)
    cfg = ControllerConfig(**{**cfg.__dict__, "ladder": ladder})

    traces = []                      # one append per engine build == trace

    def counting_wrap(batch_like):
        key = tuple(sorted((k, tuple(v.shape)) for k, v in batch_like.items()))
        traces.append(key)
        return lambda *a: None

    engine = BucketedEngine(counting_wrap, ladder)
    src = MarkovTokens(vocab_size=32, seed=0)
    state = init_controller(cfg)
    # T_k ramp: forces progressive growth through every intermediate rung
    for step in range(60):
        plan = state.plan
        bucket = engine.bucket_for(plan.global_batch, cfg.max_global_batch)
        batch = pad_to_bucket(make_batch(src, step, plan, seq_len=4),
                              plan, bucket)
        engine.get_step(batch)
        engine.observe(plan, bucket)
        t_target = min(256 * 2 ** (step // 4), 8192) * 1.5
        state = controller_update(cfg, state, var_l1=t_target * cfg.eta**2,
                                  grad_sqnorm=1.0)

    assert state.plan.global_batch == 8192 and state.at_max
    visited = set(engine.stats.buckets_used)
    assert len(traces) == len(set(traces)) == len(visited), (
        traces, visited)
    assert engine.stats.compiles == len(visited)
    assert engine.stats.hits == engine.stats.steps - len(visited)
    # adaptive plans are ladder-quantized -> zero padding waste
    assert engine.stats.padding_waste == 0.0
    # the run climbed through multiple rungs, not just base+top
    assert len(visited) >= 3


def test_engine_warmup_precompiles_next_bucket():
    """AOT warmup lands the next rung in the cache: stepping into it later is
    a hit, not a fresh build."""
    ladder = parse_ladder("2:1,2:2,2:4", workers=1)
    builds = []

    def counting_wrap(batch_like):
        builds.append(tuple(v.shape for v in batch_like.values()))
        return lambda *a: None

    # fake jit object protocol for the AOT path: lower().compile()
    class FakeJitted:
        def lower(self, *a):
            return self

        def compile(self):
            return lambda *a: None

    def aot_wrap(batch_like):
        builds.append(tuple(v.shape for v in batch_like.values()))
        return FakeJitted()

    engine = BucketedEngine(aot_wrap, ladder, params_like={}, opt_like={},
                            aot_warmup=True)
    src = MarkovTokens(vocab_size=32, seed=0)
    plan = ladder[0]
    batch = make_batch(src, 0, plan, seq_len=4)
    engine.get_step(batch)
    engine.warmup(engine.next_bucket(plan), batch)
    engine.drain()
    assert engine.stats.warmups == 1 and len(builds) == 2
    # stepping into the warmed rung: served from cache, no third build
    plan2 = ladder[1]
    batch2 = pad_to_bucket(make_batch(src, 1, plan2, seq_len=4), plan2, plan2)
    before = engine.stats.hits
    engine.get_step(batch2)
    assert len(builds) == 2 and engine.stats.hits == before + 1


def test_engine_warmup_failure_counts_and_surfaces():
    """Warmup stats are counted on COMPLETION: a background compile that
    raises contributes to warmup_failures (never warmups/compiles),
    `get_step` falls back to a synchronous build, and `drain()` re-raises
    instead of swallowing the exception into a cache entry."""
    ladder = parse_ladder("2:1,2:2,2:4", workers=1)

    class ExplodingJitted:
        def lower(self, *a):
            raise RuntimeError("boom: AOT lowering failed")

    builds = []

    def wrap(batch_like):
        builds.append(1)
        return ExplodingJitted()

    engine = BucketedEngine(wrap, ladder, params_like={}, opt_like={},
                            aot_warmup=True)
    src = MarkovTokens(vocab_size=32, seed=0)
    plan = ladder[0]
    batch = make_batch(src, 0, plan, seq_len=4)
    engine.warmup(ladder[1], batch)
    with pytest.raises(RuntimeError, match="warmup compile"):
        engine.drain()
    assert engine.stats.warmups == 0 and engine.stats.compiles == 0
    assert engine.stats.warmup_failures == 1
    assert engine.stats.as_dict()["warmup_failures"] == 1

    # a failed warmup consumed by get_step: sync fallback, error kept for
    # drain, training itself not interrupted
    engine2 = BucketedEngine(wrap, ladder, params_like={}, opt_like={},
                             aot_warmup=True)
    engine2.warmup(ladder[1], batch)
    plan2 = ladder[1]
    batch2 = pad_to_bucket(make_batch(src, 1, plan2, seq_len=4), plan2, plan2)
    # get_step blocks on the pending future, swallows its failure into
    # warmup_failures, and falls back to a fresh sync build
    step = engine2.get_step(batch2)
    assert isinstance(step, ExplodingJitted)
    assert engine2.stats.warmup_failures == 1
    assert engine2.stats.compiles == 1          # the sync fallback build
    with pytest.raises(RuntimeError, match="warmup compile"):
        engine2.drain()
    engine2.drain()                    # errors were flushed by the raise
    assert engine2.stats.warmup_failures == 1


def test_warmup_failure_accounted_exactly_once_under_race():
    """Satellite bugfix: `drain` used to iterate a STALE snapshot of
    `_pending` while `get_step` popped and recorded the same future's
    failure — one background exception inflated `warmup_failures` to 2 and
    re-raised a handled error.  Accounting is now claim-based (whoever pops
    the key under the lock owns the outcome), so a drain racing a get_step
    against one deliberately failing warmup records EXACTLY one failure."""
    import threading
    import time as _time

    ladder = parse_ladder("2:1,2:2", workers=1)
    release = threading.Event()

    class BlockingExploder:
        def lower(self, *a):
            release.wait(timeout=30)
            raise RuntimeError("boom: deferred AOT failure")

    engine = BucketedEngine(lambda bl: BlockingExploder(), ladder,
                            params_like={}, opt_like={}, aot_warmup=True)
    src = MarkovTokens(vocab_size=32, seed=0)
    batch = make_batch(src, 0, ladder[0], seq_len=4)
    engine.warmup(ladder[1], batch)
    drainer = threading.Thread(target=lambda: engine.drain(raise_errors=False))
    drainer.start()
    # wait until drain CLAIMED the (still-running) warmup future
    deadline = _time.monotonic() + 10
    while engine._pending:
        assert _time.monotonic() < deadline, "drain never claimed the warmup"
        _time.sleep(0.005)
    # the racing get_step finds nothing pending -> synchronous fallback
    # build; it must NOT account the same future a second time
    plan2 = ladder[1]
    batch2 = pad_to_bucket(make_batch(src, 1, plan2, seq_len=4), plan2, plan2)
    step = engine.get_step(batch2)
    assert isinstance(step, BlockingExploder)
    release.set()                      # let the background failure surface
    drainer.join(timeout=30)
    assert not drainer.is_alive()
    assert engine.stats.warmup_failures == 1   # was 2 with the stale copy
    assert engine.stats.compiles == 1          # only the sync fallback
    engine.drain(raise_errors=False)           # idempotent: nothing pending
    assert engine.stats.warmup_failures == 1


def test_run_training_engine_stats_end_to_end():
    """The engine threads through launch/train.py: an adaptive run reports
    compiles == buckets used, and a new seq_len bucket is a new compile."""
    from repro.launch.train import TrainJob, run_training
    job = TrainJob(arch="llama3.2-1b", steps=8, seq_len=32,
                   base_global_batch=4, max_global_batch=16,
                   base_micro_batch=2, max_micro_batch=2, base_accum=2,
                   eta=0.12, step_impl="accum_norm", eval_every=0)
    h = run_training(job)
    eng = h["engine"]
    assert eng["steps"] == 8
    assert eng["compiles"] == len(eng["buckets_used"])
    assert eng["hits"] == eng["steps"] - eng["compiles"]
    assert all(np.isfinite(l) for l in h["loss"])


def test_warmup_agreed_proposal_targets_requested_rung():
    """`warmup_agreed` warms the CALLER's proposal (the predicted target
    rung, DESIGN §14) when one is given — not blindly the next rung up —
    and still defaults to next_bucket without one."""
    ladder = parse_ladder("2:1,2:2,2:4,2:8", workers=1)
    builds = []

    class FakeJitted:
        def lower(self, *a):
            return self

        def compile(self):
            return lambda *a: None

    def aot_wrap(batch_like):
        builds.append(batch_like["tokens"].shape[:2])
        return FakeJitted()

    engine = BucketedEngine(aot_wrap, ladder, params_like={}, opt_like={},
                            aot_warmup=True)
    src = MarkovTokens(vocab_size=32, seed=0)
    batch = make_batch(src, 0, ladder[0], seq_len=4)
    # predicted rung two levels up: warm THAT one, skipping ladder[1]
    queued = engine.warmup_agreed(ladder[0], batch, proposal=ladder[2])
    engine.drain()
    assert queued == ladder[2]
    assert builds == [(ladder[2].accum_steps, ladder[2].micro_batch)]
    # no proposal: the pre-predictor default (next rung up)
    queued = engine.warmup_agreed(ladder[0], batch)
    engine.drain()
    assert queued == ladder[1]
    assert builds[-1] == (ladder[1].accum_steps, ladder[1].micro_batch)
    # stepping into the predicted rung later is a transition HIT
    plan2 = ladder[2]
    b0 = pad_to_bucket(make_batch(src, 0, ladder[0], seq_len=4),
                       ladder[0], ladder[0])
    b2 = pad_to_bucket(make_batch(src, 1, plan2, seq_len=4), plan2, plan2)
    engine.get_step(b0)
    engine.get_step(b2)
    assert engine.stats.transitions == 1
    assert engine.stats.transition_hits == 1


def test_predictive_run_rung_transitions_are_cache_hits():
    """Acceptance: predictive mode at smoke scale warms the rung the
    controller actually transitions to — every measured rung transition is
    a cache hit (the foreground never traces it), with per-rung compiles
    unchanged.  Base 32 of a 64-ladder so the two-scale GNS estimate is
    valid (M·J large) and the predictor populates mid-run."""
    from repro.launch.train import TrainJob, run_training
    job = TrainJob(arch="llama3.2-1b", steps=8, seq_len=32,
                   base_global_batch=32, max_global_batch=64,
                   base_micro_batch=2, max_micro_batch=2, base_accum=2,
                   eta=0.12, step_impl="accum_norm", eval_every=0,
                   predict=True, aot_warmup=True)
    h = run_training(job)
    eng = h["engine"]
    assert eng["transitions"] >= 1
    assert eng["transition_hits"] == eng["transitions"]
    # one compile per rung visited, none of them foreground at a transition
    assert eng["compiles"] == len(eng["buckets_used"])
    # the predictor populated and targeted the rung the run sits on
    assert any(r == 64 for r in h["pred_rung"])
    assert all(np.isfinite(l) for l in h["loss"])


def test_padded_batch_identical_grads_fsdp_multiworker(subproc):
    """Padding that lands unevenly across the J workers still yields the
    unpadded loss/params: the per-worker means are valid-token weighted
    before the cross-worker reduction (DESIGN §8)."""
    out = subproc("""
import jax, jax.numpy as jnp
import numpy as np
from jax import set_mesh
from repro.configs import get_smoke_config
from repro.models import build_model
from repro.launch.mesh import make_host_mesh
from repro.distributed.train_step import make_fsdp_norm_step
from repro.optim.adamw import AdamWConfig, init_adamw
from repro.data.pipeline import MarkovTokens, make_batch, pad_to_bucket
from repro.core.schedule import BatchPlan

cfg = get_smoke_config("llama3.2-1b")
model = build_model(cfg)
mesh = make_host_mesh(data=2, model=1)
src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
plan = BatchPlan(global_batch=6, micro_batch=3, accum_steps=1, workers=2)
bucket = BatchPlan(global_batch=16, micro_batch=4, accum_steps=2, workers=2)
batch = make_batch(src, 0, plan, 16)
padded = pad_to_bucket(batch, plan, bucket)
# row-major fill of 6 reals into (2, 8): row0 = 6 real + 2 pad, so worker 0
# holds 4 real and worker 1 holds 2 real + 2 pad -> uneven by construction
outs = {}
for tag, b in (("plain", batch), ("padded", padded)):
    params = model.init(jax.random.PRNGKey(0))
    opt = init_adamw(params)
    wrap, _, _ = make_fsdp_norm_step(model, AdamWConfig(), mesh,
                                     params_like=params)
    jb = jax.tree.map(jnp.asarray, b)
    fn = wrap(jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), jb))
    with set_mesh(mesh):
        p2, o2, m = fn(params, opt, jb, jnp.float32(1e-3))
    outs[tag] = (p2, float(m["loss"]), o2["m"])
assert abs(outs["plain"][1] - outs["padded"][1]) < 1e-5, outs
# the first AdamW moment is (1 - beta1) x the clipped mean gradient: the
# gradients themselves must agree (a padding leak would move them by the
# order of the gradient, not by reduction-order rounding)
for a, b in zip(jax.tree.leaves(outs["plain"][2]),
                jax.tree.leaves(outs["padded"][2])):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-5, atol=1e-8)
# the first AdamW step moves each parameter by lr * g / (|g| + eps): where
# |g| is within a few hundred eps of zero, that ratio turns a 1e-9 rounding
# difference of the (differently ordered) padded reduction into a 1e-5-scale
# parameter difference.  Compare parameters where |g| >> eps.
for a, b, g in zip(jax.tree.leaves(outs["plain"][0]),
                   jax.tree.leaves(outs["padded"][0]),
                   jax.tree.leaves(outs["plain"][2])):
    live = np.abs(np.asarray(g)) / 0.1 > 1e-6
    np.testing.assert_allclose(np.asarray(a, np.float32)[live],
                               np.asarray(b, np.float32)[live],
                               rtol=1e-5, atol=1e-5)
print("FSDP_PAD_OK")
""", devices=2)
    assert "FSDP_PAD_OK" in out


def test_get_step_concurrent_callers_compile_once():
    """Regression for the unlocked-cache race: `get_step` used to read and
    write `self._cache` outside `self._lock`, so a foreground build racing
    another caller (e.g. a finishing AOT warmup) could trace the same
    signature twice and double-count `stats.compiles`.  N threads asking
    for the same batch must produce exactly ONE compile; everyone else is
    a hit."""
    import threading
    import time as _time

    ladder = parse_ladder("2:1,2:2", workers=1)
    builds = []
    entered = threading.Barrier(4 + 1, timeout=10)

    def slow_wrap(batch_like):
        builds.append(tuple(v.shape for v in batch_like.values()))
        _time.sleep(0.05)          # widen the race window
        return lambda *a: ("step", len(builds))

    engine = BucketedEngine(slow_wrap, ladder)
    src = MarkovTokens(vocab_size=32, seed=0)
    batch = make_batch(src, 0, ladder[0], seq_len=4)

    results, errors = [], []

    def worker():
        try:
            entered.wait()
            results.append(engine.get_step(batch))
        except Exception as e:     # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    entered.wait()                 # release all workers at once
    for t in threads:
        t.join(timeout=10)
    assert not errors
    assert len(builds) == 1, f"double-compiled: {len(builds)} traces"
    assert engine.stats.compiles == 1
    assert engine.stats.hits == 3
    assert len({id(fn) for fn in results}) == 1   # everyone got THE step


def test_flat_resident_layout_reused_across_rungs_zero_packs():
    """DESIGN §10 engine invariant: a flat-resident step builder exposes ONE
    `FlatLayout` (`wrap.flat_layout`), every ladder rung the engine compiles
    reuses it (the engine asserts identity at build time), and the step
    TRACED at each rung contains zero pack eqns — buffers from one rung
    feed the step compiled for the next with no residency conversion.

    Pack counting is jaxpr-level (`engine.trace_step` +
    `repro.analysis.count_layout_ops`), not the deprecated Python-call
    proxy: the marker eqns are visible regardless of jit caching, so the
    zero-pack claim is about the compiled graph itself."""
    from jax import set_mesh
    from repro.configs import get_smoke_config
    from repro.models import build_model
    from repro.launch.mesh import make_host_mesh
    from repro.distributed.train_step import make_accum_norm_step
    from repro.analysis.jaxpr_check import LAYOUT_MARKER, iter_eqns
    from repro.optim.adamw import AdamWConfig, init_adamw_flat

    cfg = get_smoke_config("llama3.2-1b")
    model = build_model(cfg)
    mesh = make_host_mesh(data=1, model=1)
    params = model.init(jax.random.PRNGKey(0))
    wrap, _, _ = make_accum_norm_step(model, AdamWConfig(), mesh,
                                      stats_impl="flat", params_impl="flat",
                                      params_like=params)
    layout = wrap.flat_layout
    assert layout is not None
    opt = init_adamw_flat(params, layout=layout)
    pb = tuple(layout.flatten(params))
    abstract = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)

    ladder = parse_ladder("2:1,2:2", workers=1)
    engine = BucketedEngine(wrap, ladder, mesh=mesh,
                            params_like=abstract(pb), opt_like=abstract(opt))
    src = MarkovTokens(vocab_size=cfg.vocab_size, seed=0)
    with set_mesh(mesh):
        for rung in ladder:
            batch = jax.tree.map(jnp.asarray,
                                 make_batch(src, 0, rung, seq_len=16))
            jaxpr = engine.trace_step(batch)
            packs = [e for e in iter_eqns(jaxpr.jaxpr)
                     if e.primitive.name == LAYOUT_MARKER
                     and e.params["kind"] == "pack"]
            assert not packs, (
                f"rung {rung.global_batch}: {len(packs)} pack eqns in a "
                "flat-resident steady-state step")
            fn = engine.get_step(batch)
            assert wrap.flat_layout is layout      # one layout, every rung
            pb, opt, m = fn(pb, opt, batch, jnp.float32(1e-3))
            assert np.isfinite(float(m["loss"]))
    assert engine.stats.compiles == len(ladder)


def test_stagewise_stage_above_max_global_trains():
    """Regression: a stagewise stage configured above max_global_batch must
    ride the auto ladder's extended top rung, not crash in pad_to_bucket."""
    from repro.launch.train import TrainJob, run_training
    job = TrainJob(arch="llama3.2-1b", schedule="stagewise",
                   stages=((0.25, 8), (0.75, 32)), steps=8, total_samples=64,
                   seq_len=16, base_global_batch=4, max_global_batch=16,
                   base_micro_batch=2, max_micro_batch=2, base_accum=2,
                   step_impl="accum_norm", eval_every=0)
    h = run_training(job)
    assert max(h["global_batch"]) == 32       # the above-cap stage executed
    assert all(np.isfinite(l) for l in h["loss"])


def test_explicit_ladder_rungs_above_cap_are_ineligible():
    """Regression: quantization never hands the controller a rung above
    max_global_batch, even from an explicit over-provisioned ladder."""
    ladder = parse_ladder("2:1,2:24,2:48", workers=1)   # rungs 2, 48, 96
    rung = quantize_to_ladder(10_000, ladder, max_global=64)
    assert rung.global_batch == 48             # largest eligible, not 96

    cfg = ControllerConfig(eta=0.5, workers=1, base_micro_batch=2,
                           max_micro_batch=2, base_accum=1,
                           base_global_batch=2, max_global_batch=64,
                           ladder=ladder)
    s = init_controller(cfg)
    s = controller_update(cfg, s, var_l1=1e12, grad_sqnorm=1.0)
    assert s.plan.global_batch == 48 and s.at_max   # latched at the ceiling
    s2 = controller_update(cfg, s, var_l1=1e15, grad_sqnorm=1.0)
    assert s2.plan.global_batch == 48
