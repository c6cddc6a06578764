"""The training loop's profiler spans, the engine's wait counter and the
model's named scopes (DESIGN §16): each loop phase once per step, in loop
order, with its step; the wait counted per step; the names the benchmark
reads; and nothing of it moves a loss."""

import glob
import os
import re
import sys

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import spans
from repro.launch.train import TrainJob, run_training

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(arch="llama3.2-1b", step_impl="accum_norm", seq_len=32,
            base_micro_batch=2, max_micro_batch=2, base_accum=2,
            eval_every=0)


def traced_run(job: TrainJob, log_dir: str):
    with jax.profiler.trace(log_dir):
        hist = run_training(job)
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("train."):
                    events.append((plane.name, i, e.start_ns, e.name,
                                   {k: v for k, v in e.stats}))
    return hist, sorted(events, key=lambda e: e[2])


def loop_spans_by_step(events) -> dict:
    threads = {(p, i) for p, i, _, n, _ in events if n == spans.DISPATCH}
    assert len(threads) == 1, threads
    out: dict = {}
    for p, i, _, name, stats in events:
        if (p, i) in threads and name.startswith("train."):
            out.setdefault(stats["step"], []).append(name)
    return out


def test_loop_spans_tile_each_step_in_order(tmp_path):
    """Adaptive, bucketed, AOT warm-up: every phase once per step."""
    job = TrainJob(**TINY, schedule="adaptive", eta=0.12, steps=3,
                   base_global_batch=4, max_global_batch=16,
                   aot_warmup=True)
    hist, events = traced_run(job, str(tmp_path))
    by_step = loop_spans_by_step(events)
    assert sorted(by_step) == [1, 2, 3]
    for step, names in by_step.items():
        assert tuple(names) == spans.LOOP, (step, names)


def test_accum_free_sub_steps_carry_the_step(tmp_path):
    """Accumulation-free: the step's batch, each sub-step's phases (its
    slice of the batch first), then the step's fence and warm-up; constant
    schedule, so no controller span."""
    job = TrainJob(**TINY, schedule="constant", steps=2,
                   base_global_batch=8, max_global_batch=8,
                   accum_free=True, accum_free_below=64)
    hist, events = traced_run(job, str(tmp_path))
    assert set(hist["opt_steps"]) == {4}
    sub = [spans.BATCH, spans.LOOKUP, spans.PUT, spans.LR, spans.DISPATCH,
           spans.READBACK]
    want = [spans.BATCH] + sub * 4 + [spans.FENCE, spans.WARMUP]
    by_step = loop_spans_by_step(events)
    assert sorted(by_step) == [1, 2]
    for step, names in by_step.items():
        assert names == want, (step, names)


def test_lookup_wait_counted_per_step():
    job = TrainJob(**TINY, schedule="adaptive", eta=0.12, steps=4,
                   base_global_batch=4, max_global_batch=16,
                   aot_warmup=True)
    hist = run_training(job)
    waits = hist["lookup_wait_s"]
    assert len(waits) == len(hist["step"]) == 4
    assert waits[0] > 0          # the first step's foreground build
    assert all(w >= 0 for w in waits)
    assert sum(waits) == pytest.approx(hist["engine"]["lookup_wait_s"],
                                       rel=1e-9, abs=1e-12)


def test_lookup_wait_zero_without_engine():
    job = TrainJob(**TINY, schedule="constant", steps=2,
                   base_global_batch=4, max_global_batch=4,
                   bucket_ladder="off")
    hist = run_training(job)
    assert hist["lookup_wait_s"] == [0.0, 0.0]


def test_names_match_the_benchmark():
    sys.path.insert(0, os.path.join(REPO, "benchmarks", "chip"))
    try:
        from chipbench import spans as bench
    finally:
        sys.path.pop(0)
    assert bench.LOOP == spans.LOOP
    assert bench.DISPATCH == spans.DISPATCH
    assert bench.READBACK == spans.READBACK
    assert set(bench.INPUT) == {spans.BATCH, spans.PUT}
    assert bench.SCOPES == spans.SCOPES


def test_losses_identical_with_and_without_trace(tmp_path):
    kw = dict(TINY, schedule="adaptive", eta=0.12, steps=3,
              base_global_batch=4, max_global_batch=16)
    plain = run_training(TrainJob(**kw))
    traced, _ = traced_run(TrainJob(**kw), str(tmp_path))
    assert traced["loss"] == plain["loss"]
    assert traced["global_batch"] == plain["global_batch"]


def test_model_scopes_name_the_step_program():
    """The scopes reach the compiled step's op names (forward and
    backward), the metadata a device trace's operations carry."""
    from repro.launch.train import model_config
    from repro.models import build_model
    model = build_model(model_config(TrainJob(arch="llama3.2-1b")))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 32), np.int32)
    text = jax.jit(jax.grad(lambda p, b: model.loss(p, b)[0])).lower(
        params, {"tokens": tok, "labels": tok}).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    for scope in spans.SCOPES:
        held = [n for n in names
                if re.search(rf"(?:^|[/(]){scope}(?:[)/]|$)", n)]
        assert any("transpose(" in n for n in held), scope
        assert any("transpose(" not in n for n in held), scope


def test_expert_counters_per_step():
    """A model with expert layers keeps, per step, its pairs on held
    experts and the dispatch buffer rows that carried them, each summed
    over layers and microbatches.  The smoke DeepSeek-V2-Lite block (4 of 8
    experts held, top 3) gives one chunk: n·min(k, held) rows a layer."""
    from repro.launch.train import model_config
    from repro.models.moe import buffer_chunks
    job = TrainJob(**dict(TINY, arch="deepseek-v2-lite-16b", steps=2,
                          schedule="constant", base_global_batch=4,
                          max_global_batch=4))
    hist = run_training(job)
    cfg = model_config(job)
    m = cfg.moe
    n = job.base_micro_batch * job.seq_len
    rows, chunks = buffer_chunks(n, m.top_k, m.held, m.num_experts)
    assert (rows, chunks) == (n * 3, 1)
    calls = (cfg.num_layers - m.first_dense) * job.base_accum
    assert hist[spans.EXPERT_BUFFER_ROWS] == [float(rows * calls)] * 2
    assert all(0 < r <= b for r, b in zip(hist[spans.EXPERT_ROWS],
                                         hist[spans.EXPERT_BUFFER_ROWS]))
