"""Serving driver: batched prefill + decode loop (smoke-scale on CPU).

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --batch 4 --prompt-len 32 --gen-len 32
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from jax import set_mesh
from repro.launch.mesh import make_host_mesh
from repro.distributed.serve_step import make_decode_step, make_prefill
from repro.models import build_model


def run_serving(arch: str, *, smoke=True, batch=4, prompt_len=32, gen_len=32,
                mesh_data=1, mesh_model=1, seed=0, greedy=True):
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    key = jax.random.PRNGKey(seed)
    params = model.init(key)
    mesh = make_host_mesh(data=mesh_data, model=mesh_model)

    cache_len = prompt_len + gen_len
    rng = np.random.default_rng(seed)
    text_len = prompt_len - (cfg.frontend.num_prefix_tokens
                             if cfg.frontend.kind == "vision_stub" else 0)
    if text_len <= 0:
        # vision_stub edge: the frontend's prefix tokens consume the whole
        # prompt budget, leaving no text token to seed `prompts[:, 0]`
        raise ValueError(
            f"prompt_len={prompt_len} leaves no text tokens after the "
            f"vision frontend's {cfg.frontend.num_prefix_tokens} prefix "
            f"tokens (text_len={text_len}); pass prompt_len > "
            f"{cfg.frontend.num_prefix_tokens}")
    prompts = rng.integers(0, cfg.vocab_size, (batch, text_len)).astype(np.int32)

    dec_wrap, _ = make_decode_step(model, mesh, batch=batch)
    cache = model.init_cache(batch, cache_len)
    step_fn = dec_wrap(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache))

    with set_mesh(mesh):
        # "prefill" by streaming the prompt through decode (cache stays
        # shape-stable; production prefill uses model.prefill)
        t0 = time.time()
        tok = jnp.asarray(prompts[:, 0])
        for i in range(text_len):
            logits, cache = step_fn(params, cache, tok, jnp.int32(i))
            tok = jnp.asarray(prompts[:, i + 1]) if i + 1 < text_len else (
                jnp.argmax(logits, -1).astype(jnp.int32))
        # fence the async dispatch: without this the prefill work is still
        # in flight when the clock is read, and its compute leaks into the
        # decode timing below (tok depends on the final logits; cache is
        # blocked too so no prefill writes straddle the phase boundary)
        jax.block_until_ready((tok, cache))
        t_prefill = time.time() - t0

        # the first generated token came out of the (already-timed) prefill
        # phase above: the timed decode loop emits gen_len - 1 tokens
        generated = [tok]
        t0 = time.time()
        for i in range(text_len, text_len + gen_len - 1):
            logits, cache = step_fn(params, cache, tok, jnp.int32(i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            generated.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0

    out = np.stack([np.asarray(t) for t in generated], axis=1)
    # throughput over the tokens the decode timer actually saw: gen_len - 1
    # per sequence (dividing batch * gen_len by this loop overstated tok/s)
    decode_tokens = batch * (gen_len - 1)
    toks_per_s = decode_tokens / max(t_decode, 1e-9) if decode_tokens else 0.0
    return {"tokens": out, "prefill_s": t_prefill, "decode_s": t_decode,
            "decode_tokens_timed": decode_tokens,
            "decode_tok_per_s": toks_per_s}


def run_continuous_serving(arch: str, *, smoke=True, max_slots=8,
                           prompt_len=4, gen_len=8, load_steps=60,
                           arrival_rate=0.5, burst_every=20, burst_size=5,
                           mesh_data=1, mesh_model=1, seed=0,
                           latency_slo_s=0.0, aot_warmup=True, max_queue=0):
    """Bursty open-loop load against the continuous-batching serve tier.

    An open-loop arrival process (Poisson at `arrival_rate` requests per
    engine step, plus a deterministic burst of `burst_size` every
    `burst_every` steps) drives `ServeEngine` for `load_steps` steps; the
    driver then drains the backlog.  Arrivals do NOT wait for completions,
    so queue pressure — and the controller's rung — genuinely moves.

    After the load phase, a steady-state probe: with every rung warm, a
    fresh burst forces a request-batch-size change, which must be served
    from the warmed rung — a transition cache hit with ZERO new compiles.

    Returns a metrics dict (sustained req/s, p50/p99 request latency,
    decode tok/s, engine counters, rung trace, probe verdict).
    """
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    mesh = make_host_mesh(data=mesh_data, model=mesh_model)
    from repro.core.serve_controller import ServeControllerConfig, serve_ladder
    from repro.distributed.serve_engine import QueueFullError, ServeEngine

    cache_len = prompt_len + gen_len
    engine = ServeEngine(
        model, params, mesh, max_slots=max_slots, cache_len=cache_len,
        controller=ServeControllerConfig(ladder=serve_ladder(max_slots),
                                         latency_slo_s=latency_slo_s),
        aot_warmup=aot_warmup, max_queue=max_queue)
    rng = np.random.default_rng(seed)

    def submit_one():
        prompt = rng.integers(0, cfg.vocab_size, (prompt_len,)).astype(np.int32)
        try:
            engine.submit(prompt, max_new_tokens=gen_len)
        except QueueFullError:
            pass    # open-loop load-shed: counted in stats.requests_rejected

    completed = []
    rung_trace = []
    t_start = time.time()
    for i in range(load_steps):
        n = rng.poisson(arrival_rate)
        if burst_every and i % burst_every == 0:
            n += burst_size
        for _ in range(n):
            submit_one()
        report = engine.step()
        if report is not None:
            completed.extend(report["completed"])
            rung_trace.append(report["rung"])
    completed.extend(engine.run_until_drained())
    wall_s = max(time.time() - t_start, 1e-9)

    # ---- steady-state probe: rung change must hit a warmed executable ----
    engine.warm(engine.ladder)
    engine.drain(raise_errors=False)        # all background compiles landed
    compiles0 = engine.stats.compiles
    trans0 = engine.stats.rung_transitions
    hits0 = engine.stats.transition_hits
    probe_burst = min(max_slots, engine.current_rung * 2)
    if engine.current_rung >= max_slots:    # already at top: force a shrink
        probe_burst = 1
    for _ in range(probe_burst):
        submit_one()
    completed.extend(engine.run_until_drained())
    probe = {
        "rung_transitions": engine.stats.rung_transitions - trans0,
        "transition_hits": engine.stats.transition_hits - hits0,
        "new_compiles": engine.stats.compiles - compiles0,
    }
    probe["steady_state_transition_hit"] = bool(
        probe["rung_transitions"] >= 1
        and probe["transition_hits"] == probe["rung_transitions"]
        and probe["new_compiles"] == 0)

    lat = sorted(r.latency_s for r in completed)

    def pct(p):
        return lat[min(len(lat) - 1, int(p / 100 * len(lat)))] if lat else 0.0

    stats = engine.stats
    return {
        "requests_completed": len(lat),
        "sustained_req_per_s": len(lat) / wall_s,
        "p50_latency_s": pct(50),
        "p99_latency_s": pct(99),
        "decode_tok_per_s": stats.tokens_generated / wall_s,
        "wall_s": wall_s,
        "rung_trace": rung_trace,
        "probe": probe,
        "engine": stats.as_dict(),
    }


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="llama3.2-1b")
    p.add_argument("--full", action="store_true")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--gen-len", type=int, default=32)
    p.add_argument("--continuous", action="store_true",
                   help="bursty open-loop load on the continuous-batching "
                        "tier instead of the fixed-batch driver")
    p.add_argument("--max-slots", type=int, default=8)
    p.add_argument("--load-steps", type=int, default=60)
    p.add_argument("--arrival-rate", type=float, default=0.5)
    p.add_argument("--burst-every", type=int, default=20)
    p.add_argument("--burst-size", type=int, default=5)
    p.add_argument("--max-queue", type=int, default=0,
                   help="reject submits once this many requests wait "
                        "(0 = unbounded)")
    args = p.parse_args(argv)
    if args.continuous:
        res = run_continuous_serving(
            args.arch, smoke=not args.full, max_slots=args.max_slots,
            prompt_len=args.prompt_len, gen_len=args.gen_len,
            load_steps=args.load_steps, arrival_rate=args.arrival_rate,
            burst_every=args.burst_every, burst_size=args.burst_size,
            max_queue=args.max_queue)
        print(f"served {res['requests_completed']} requests: "
              f"{res['sustained_req_per_s']:.2f} req/s, "
              f"p50 {res['p50_latency_s']:.3f}s p99 {res['p99_latency_s']:.3f}s, "
              f"{res['decode_tok_per_s']:.1f} tok/s")
        print("engine:", res["engine"])
        print("steady-state probe:", res["probe"])
        return
    res = run_serving(args.arch, smoke=not args.full, batch=args.batch,
                      prompt_len=args.prompt_len, gen_len=args.gen_len)
    print(f"prefill {res['prefill_s']:.2f}s decode {res['decode_s']:.2f}s "
          f"({res['decode_tok_per_s']:.1f} tok/s)")
    print("sample:", res["tokens"][0][:16])


if __name__ == "__main__":
    main()
