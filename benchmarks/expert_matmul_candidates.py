"""The two candidates for a dropless expert layer's grouped matmuls, timed
at a cell's shapes on the chip, run by hand:

    python3 benchmarks/expert_matmul_candidates.py [--rows 12288]

The three products of the held SwiGLU experts (gate and up, d -> f; down,
f -> d) over rows sorted into `--groups` groups of uneven size, forward
and backward, as `models/moe.py` runs them: the megablox Pallas kernels
(`repro.kernels.grouped_matmul`) and `jax.lax.ragged_dot` (XLA's ragged
dot).  Prints one JSON line per candidate with the median of `--iters`
fenced calls.  Needs a TPU; the shapes default to
deepseek-v2-lite-1chip.adaptive-cross-4k's (8,192 tokens a microbatch,
6 experts a token, 8 of 64 held: a buffer chunk of 12,288 rows,
`moe.buffer_chunks`, ~6,144 filled).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=12288)
    ap.add_argument("--filled", type=int, default=6144)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--f", type=int, default=1408)
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.grouped_matmul import grouped_matmul
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    rng = np.random.default_rng(0)
    sizes = rng.multinomial(args.filled, np.ones(args.groups) / args.groups)
    gs = jnp.asarray(sizes, jnp.int32)
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(k[0], (args.rows, args.d))
    wg = 0.02 * jax.random.normal(k[1], (args.groups, args.d, args.f))
    wu = 0.02 * jax.random.normal(k[2], (args.groups, args.d, args.f))
    wd = 0.02 * jax.random.normal(k[3], (args.groups, args.f, args.d))
    ct = jax.random.normal(k[4], (args.rows, args.d))
    valid = (jnp.arange(args.rows) < int(sizes.sum()))[:, None]

    def experts(mm):
        def f(x, wg, wu, wd):
            x = jnp.where(valid, x, 0)
            h = jax.nn.silu(mm(x, wg)) * mm(x, wu)
            return jnp.sum(jnp.where(valid, mm(h, wd), 0) * ct)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2, 3)))

    cands = {
        "megablox_gmm": lambda a, w: grouped_matmul(a, w, gs),
        "ragged_dot": lambda a, w: jax.lax.ragged_dot(a, w, gs),
    }
    for name, mm in cands.items():
        fn = experts(mm)
        jax.block_until_ready(fn(x, wg, wu, wd))
        times = []
        for _ in range(args.iters):
            t = time.perf_counter()
            jax.block_until_ready(fn(x, wg, wu, wd))
            times.append(time.perf_counter() - t)
        print(json.dumps({"candidate": name, "median_s": statistics.median(
            times), "times_s": times, "group_sizes": sizes.tolist(),
            "rows": args.rows, "device": jax.devices()[0].device_kind}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
