"""One run of one cell: the trainer's own loop, timed, traced on request,
and checked against the plain reference.

The trainer is driven through `repro.launch.train.run_training` in this
process.  Two seams of the trainer are used and nothing else:
  * `repro.launch.train.get_config` hands the trainer the configuration's
    depth (its `TrainJob` has no field for it);
  * the bucketed engine's `get_step` is wrapped, so the benchmark sees
    each step's call: it copies what the comparison needs out of the
    first steps, and brackets the window when tracing;
and `repro.launch.train.fault_point`, the loop's per-step hook, marks the
end of set-up.

Set-up is the process start through the last of the `setup_steps` steps
(imports, the trainer's own jitted init, every executable from the
persistent cache, the first steps, a rung crossing that falls among them
with the warm-up it waits for, and any warm-up still queued).  The window
is every later step, on the trainer's own clock (`history["time"]`, each
step fenced).  Python's collector runs once at the end of set-up, so no
collection of set-up's garbage lands in the window.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import statistics
import sys
import time

from chipbench import compare, counts, peaks, spec, trace as tr

TRACE_DIR = os.path.join(spec.ROOT, ".chipbench_trace")


class NoChip(RuntimeError):
    pass


def device_check(chips: int, require_tpu: bool):
    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform "
                     f"{devices[0].platform!r}; the benchmark runs on a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s); JAX reports "
                     f"{len(devices)}")
    return devices[:chips]


def planned_rung(traffic: dict, step: int) -> int:
    """The batch of 1-based `step` as the traffic pins it: the base rung
    through the first norm test (at `test_interval`), the top rung after
    it under the adaptive schedule."""
    rungs = traffic["rungs"]
    if traffic["schedule"] == "adaptive" and step > traffic["test_interval"]:
        return rungs[-1]
    return rungs[0]


def plan_steps(cell: dict, traffic: dict, seconds: float) -> dict:
    """The run's fixed step count: set-up steps, then the steps whose
    recorded times (`cells/<cell>.json`, by rung) fill `seconds`, the last
    one counted where at least half of it falls inside (two at least)."""
    setup = traffic["setup_steps"]
    window, filled = 0, 0.0
    while True:
        t = cell["step_s"][str(planned_rung(traffic, setup + window + 1))]
        if window >= 2 and filled + t / 2 > seconds:
            break
        window, filled = window + 1, filled + t
    return {"setup": setup, "window": window, "total": setup + window}


def train_job(config: dict, traffic: dict, seed: int, steps: int, chips: int):
    from repro.launch.train import TrainJob
    rungs, mb = traffic["rungs"], traffic["micro_batch"]
    ladder = ",".join(f"{mb}:{r // (chips * mb)}" for r in rungs)
    fields = dict(config["train"])
    fields.update(
        arch=config["program"]["arch"], schedule=traffic["schedule"],
        steps=steps, seq_len=traffic["seq_len"], eta=traffic["eta"],
        test_interval=traffic["test_interval"],
        aot_warmup=traffic["aot_warmup"], bucket_ladder=ladder,
        base_global_batch=rungs[0], max_global_batch=rungs[-1],
        base_micro_batch=mb, max_micro_batch=mb,
        base_accum=rungs[0] // (chips * mb), mesh_data=chips,
        seed=seed, data_seed=seed, eval_every=0, checkpoint_every=0)
    return TrainJob(**fields)


class Hooks:
    """What the benchmark does at the trainer's step boundaries."""

    def __init__(self, setup_steps: int, total: int, trace_dir: str | None,
                 fault=None):
        self.setup_steps, self.total = setup_steps, total
        self.trace_dir = trace_dir
        self.fault = fault          # test-only: breaks the timed path
        self.calls = 0
        self.engine = None
        self.setup_end = None
        self.first_m_norms = None
        self.params_after = None
        self.trace_window_s = None
        self.t_step, self.t_call, self.t_back = {}, {}, {}
        self._mark = None
        self._t_trace = None

    # ---- the loop's own per-step hook: start of step `step`
    def at_step(self, step: int):
        # marks between steps the trainer's loop has already fenced
        self.t_step[step] = time.time()  # repro: allow(unfenced-timing) — the previous step is fenced by the loop
        if step == self.setup_steps + 1:
            # a warm-up still queued lands before the window, so nothing
            # compiles inside it
            self.engine.drain(raise_errors=False)
            gc.collect()
            self.setup_end = time.time()
            if self.trace_dir:
                self._start_trace()

    def slowest_window_step(self, step_s: list) -> str:
        """Where the window's slowest step spent its time: host work before
        the step's call (batch, padding, executable lookup), the call
        (dispatch), and the rest (device, readback, fence)."""
        k = max(range(self.setup_steps + 1, len(step_s) + 1),
                key=lambda i: step_s[i - 1], default=None)
        if k is None or k not in self.t_call or k not in self.t_step:
            return "no window step"
        pre = self.t_call[k] - self.t_step[k]
        call = self.t_back[k] - self.t_call[k]
        return (f"step {k} {step_s[k - 1]:.4f} s: before the call "
                f"{pre:.4f} s, call {call:.4f} s, after "
                f"{step_s[k - 1] - pre - call:.4f} s")

    def _start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._mark = jax.profiler.TraceAnnotation(tr.WINDOW_MARK)
        self._mark.__enter__()
        self._t_trace = time.time()

    def _stop_trace(self, out):
        import jax
        jax.block_until_ready(out)
        self.trace_window_s = time.time() - self._t_trace
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()

    # ---- the engine's step executable, wrapped
    def wrap(self, engine, fn):
        self.engine = engine

        def step(params, opt_state, batch, lr):
            self.calls += 1
            # the call's span is its dispatch, as `slowest_window_step` reports
            self.t_call[self.calls] = time.time()  # repro: allow(unfenced-timing) — times dispatch on purpose
            if self.fault is None:
                out = fn(params, opt_state, batch, lr)
            else:
                out = self.fault(self.calls, fn, params, opt_state, batch, lr)
            self.t_back[self.calls] = time.time()
            if self.calls == 1:
                self.first_m_norms = _leaf_norms(engine, out[1]["m"])
            if self.calls == self.setup_steps:
                self.params_after = _host_tree(engine, out[0])
            if self.trace_dir and self.calls == self.total:
                self._stop_trace(out)
            return out
        return step


def _as_tree(engine, x):
    """The model's pytree view of step state: flat-resident buffers are
    sliced out with the step's own layout (inside the jitted reader)."""
    layout = engine._flat_layout
    if layout is None or not isinstance(x, (tuple, list)):
        return x
    return layout.unflatten(list(x))


def _by_path(tree, fn) -> dict:
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(k): fn(v) for k, v in flat}


def _leaf_norms(engine, x) -> dict:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda b: jax.tree.map(
        lambda a: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))),
        _as_tree(engine, b)))(x)
    return _by_path(norms, float)


def _host_tree(engine, x) -> dict:
    import jax
    import numpy as np
    tree = jax.jit(lambda b: _as_tree(engine, b))(x)
    return _by_path(tree, lambda v: np.asarray(v, np.float32))


def install(hooks: Hooks, model_cfg):
    """Point the trainer's seams at the benchmark (see the module doc);
    returns the function that puts them back."""
    import repro.launch.train as train_mod
    from repro.distributed.engine import BucketedEngine

    class Engine(BucketedEngine):
        def get_step(self, batch):
            return hooks.wrap(self, super().get_step(batch))

    base_fault_point = train_mod.fault_point

    def fault_point(site, **info):
        if site == "train.step":
            hooks.at_step(info["step"])
        base_fault_point(site, **info)

    saved = (train_mod.BucketedEngine, train_mod.fault_point,
             train_mod.get_config)
    train_mod.BucketedEngine = Engine
    train_mod.fault_point = fault_point
    train_mod.get_config = lambda name: model_cfg

    def restore():
        (train_mod.BucketedEngine, train_mod.fault_point,
         train_mod.get_config) = saved
    return restore


def compiled_memory(engine, traffic: dict, chips: int) -> str:
    """The chip compiler's memory figures for the top-rung step (from the
    persistent cache), printed beside the allocator's peak."""
    import jax
    import jax.numpy as jnp
    mb, top = traffic["micro_batch"], traffic["rungs"][-1]
    tok = jax.ShapeDtypeStruct((top // (chips * mb), chips * mb,
                                traffic["seq_len"]), jnp.int32)
    ma = engine.lower_step({"tokens": tok, "labels": tok}).compile() \
        .memory_analysis()
    return (f"temp {ma.temp_size_in_bytes} args {ma.argument_size_in_bytes} "
            f"outputs {ma.output_size_in_bytes} "
            f"aliased {ma.alias_size_in_bytes}")


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, *, resolved: dict | None = None,
             require_tpu: bool = True, fault=None) -> dict:
    """One run started at `t_start` (host clock); returns the result
    object the command prints.  `fault` (tests only) replaces each step
    call: `fault(call_index, step_fn, params, opt_state, batch, lr)`."""
    r = resolved or spec.resolve(spec.benchmark(), name)
    w, config, traffic, cell = (r["workload"], r["config"], r["traffic"],
                                r["cell"])
    chips = w["chips"]
    devices = device_check(chips, require_tpu)
    from repro.configs import get_config
    from repro.launch.train import run_training

    ref = spec.reference_module(config["reference"])
    m = ref.sizes(config["model"])
    plan = plan_steps(cell, traffic, seconds)
    job = train_job(config, traffic, seed, plan["total"], chips)
    model_cfg = get_config(config["program"]["arch"]).replace(
        **config["program"].get("overrides", {}))
    ref.check_program(model_cfg, m)
    hooks = Hooks(plan["setup"], plan["total"], TRACE_DIR if trace else None,
                  fault)
    restore = install(hooks, model_cfg)
    try:
        hist = run_training(job)
    finally:
        restore()
    k = plan["setup"]
    setup_s = hooks.setup_end - t_start
    window_s = hist["time"][-1] - hist["time"][k - 1]
    seq = traffic["seq_len"]
    tokens = sum(hist["global_batch"][k:]) * seq
    peak_bytes = memory_peak(devices)
    log(f"reference {config['reference']}: {ref.param_count(m)} "
        f"parameters, {ref.flops_per_token(m, traffic['seq_len'])!r} "
        f"operations per token")
    log(f"steps {plan} global batch {hist['global_batch']}")
    log(f"step_s {[round(s, 4) for s in hist['step_s']]}")
    log(f"T {hist['T']}")
    log(f"engine {hist.get('engine')}")
    log(f"peak_bytes_in_use (allocator) {peak_bytes}")
    log(f"slowest window {hooks.slowest_window_step(hist['step_s'])}")
    log("top-rung step, compiler memory: "
        f"{compiled_memory(hooks.engine, traffic, chips)}")
    del hist["final_params"]
    gc.collect()

    # ---- the comparison, after the window and with the trainer's state gone
    rungs = traffic["rungs"]
    t_ref = time.time()  # repro: allow(unfenced-timing) — the reference returns host floats
    ref_run = reference(config, traffic, chips, seed, k, plan["total"],
                        prog_params=hooks.params_after)
    nums = compare.numbers(hist, hooks.first_m_norms, ref_run,
                           config["optimizer"]["beta1"], traffic["schedule"],
                           rungs, traffic["eta"], traffic["test_interval"])
    ok, lines = compare.verdict(nums, cell["limits"])
    log(f"reference s {time.time() - t_ref:.1f}")
    log(f"reference loss {ref_run.loss} trainer loss {hist['loss'][:k]}")
    log(f"reference var_l1 {ref_run.var_l1} trainer {hist['var_l1'][:k]}")
    log(f"reference grad_sqnorm {ref_run.grad_sqnorm} trainer "
        f"{hist['grad_sqnorm'][:k]}")

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devices), "memory_peak_bytes": peak_bytes}
    metrics = {}
    breakdown = None
    if not trace:
        e2e = {"train_tokens_per_s": tokens / window_s, "setup_s": setup_s}
        for mm in r["metrics"]["end_to_end"]:
            if mm["name"] in e2e:
                metrics[mm["name"]] = {"value": e2e[mm["name"]],
                                       "unit": mm["unit"]}
    else:
        ctx = layer_context(hist, hooks, plan, config, traffic, ref, m,
                            devices)
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        for mm in r["metrics"]["per_layer"]:
            v = spec.metric_reader(mm["name"])(ctx)
            if v is not None:
                metrics[mm["name"]] = {"value": v, "unit": mm["unit"]}
        breakdown = {"device_ops": ctx.top_ops, "idle_gaps": ctx.idle_gaps}
    for line in lines:
        log(line)
    failed = sum(1 for x in hist["loss"] if not math.isfinite(x))
    result = {"correct": ok, "attempted": plan["total"], "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": nums[k], "limit": cell["limits"][k]}
                          for k in sorted(cell["limits"])}
    return result


def optimizer(config: dict) -> dict:
    opt = dict(config["optimizer"])
    for k in ("warmup_frac", "peak_lr", "min_lr", "weight_decay",
              "grad_clip"):
        opt[k] = config["train"][k]
    return opt


def reference(config: dict, traffic: dict, chips: int, seed: int,
              steps: int, total_steps: int, **kw):
    """The configuration's plain reference over the run's first `steps`
    steps, at the rungs its own norm test prescribes, its state spread over
    the cell's chips; `kw` goes to `reference.train.run` (precision,
    faults, the trainer's weights)."""
    import jax
    from chipbench.reference import train as ref_train
    ref = spec.reference_module(config["reference"])
    rungs = traffic["rungs"]
    return ref_train.run(
        ref, ref.sizes(config["model"]), optimizer(config), seed=seed,
        data_seed=seed, steps=steps, rows=chips * traffic["micro_batch"],
        workers=chips, seq_len=traffic["seq_len"],
        total_samples=total_steps * rungs[-1], eta=traffic["eta"],
        rungs=rungs, schedule=traffic["schedule"],
        test_interval=traffic["test_interval"],
        step_impl=config["train"]["step_impl"],
        devices=jax.devices()[:chips], **kw)


@dataclasses.dataclass
class LayerContext:
    """What a per-layer metric's reader may read."""
    history: dict
    setup_steps: int
    seq_len: int
    chips: int
    model: dict
    peak: dict
    flops_per_token: float
    tail_bytes_per_step: dict
    events: list
    window_s: float          # the traced window, on the trace's clock
    window_ns: tuple
    device_ops: dict         # device id -> its ops inside the window
    busy_s: float            # mean over the chips
    traced_steps: int
    traced_tokens: int
    top_ops: list
    idle_gaps: list


def layer_context(hist, hooks, plan, config, traffic, ref, m, devices
                  ) -> LayerContext:
    events = tr.load_xplane(TRACE_DIR)
    lo, hi = tr.window(events)
    ids = tr.device_ids(events)[:len(devices)]
    ops = {i: tr.device_ops(events, i, lo, hi) for i in ids}
    busy = [tr.busy_ns(ops[i]) * 1e-9 for i in ids]
    k = plan["setup"]
    n = ref.param_count(m)
    chips = len(devices)
    ctx = LayerContext(
        history=hist, setup_steps=k, seq_len=traffic["seq_len"],
        chips=chips, model=m, peak=peaks.peaks_for(devices[0].device_kind),
        flops_per_token=ref.flops_per_token(m, traffic["seq_len"]),
        tail_bytes_per_step=counts.tail_bytes_per_step(
            n, chips, config["train"]["step_impl"]),
        events=events, window_s=(hi - lo) * 1e-9, window_ns=(lo, hi),
        device_ops=ops,
        busy_s=statistics.mean(busy) if busy else 0.0,
        traced_steps=plan["window"],
        traced_tokens=sum(hist["global_batch"][k:]) * traffic["seq_len"],
        top_ops=tr.top_ops(ops[ids[0]]) if ids else [],
        idle_gaps=tr.idle_gaps(events, ops[ids[0]], lo, hi) if ids else [])
    log(f"trace: window {ctx.window_s:.4f} s (host clock "
        f"{hooks.trace_window_s:.4f} s), busy {busy}, {len(events)} events")
    return ctx


def result_line(result: dict) -> str:
    import json
    return json.dumps(result, allow_nan=False)

