"""Plain reference of the first steps of an adaptive-batch training run.

Follows Algorithm 1 step by step from the seed: the corpus rows of each
step (`markov`), the model's loss and gradient (the configuration's
reference module, float32 at `highest` matmul precision), the norm test's
statistics over the step's variance groups, the controller's decision, and
global-norm-clipped AdamW with the trainer's warmup-cosine learning rate.
The variance groups follow the trainer's step: one per microbatch under
ACCUM-NORM, one per data worker (its rows of every microbatch, the g_j of
FSDP-Norm) under FSDP-Norm.

Each step runs at the rung the reference's own norm test prescribed, so
it follows an adaptive run across its rung crossing.  It runs in blocks
of rows (the gradient of one block at a time, summed), so that its
weights, moments and gradient sums fit one chip at the cell's own sizes;
where the cell holds several chips, its weights, moments and gradient sums
are spread over them (each leaf split along an axis, the computation left
to XLA's partitioner).  The initial weights are drawn again from the seed
at the end rather than kept.

`dtype=bfloat16` makes the control (the same step a precision lower), and
`fault` plants a fault the benchmark must catch in the reference's own
step: `half_batch`, the second half of each worker's rows of every
microbatch left out, the mean taken over the rest; `exchange`, the last
worker's rows replaced by the first worker's, so the last worker's
contribution is missing from the reduced gradient.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.markov import MarkovTokens, step_batch

STEP_IMPLS = ("accum_norm", "fsdp_norm")
FAULTS = ("half_batch", "exchange")


@dataclass
class RefRun:
    batch: list = field(default_factory=list)      # rung of each step
    loss: list = field(default_factory=list)
    var_l1: list = field(default_factory=list)
    grad_sqnorm: list = field(default_factory=list)
    decision: list = field(default_factory=list)   # rung after each step
    first_grad: dict = field(default_factory=dict)  # leaf -> clipped ‖g₁‖
    change: dict = field(default_factory=dict)      # leaf -> ‖p_n − p₀‖
    prog_change: dict = field(default_factory=dict)  # leaf -> ‖p_prog − p₀‖
    params: dict | None = None      # leaf -> weights after the steps (host)


def warmup_cosine(samples, *, peak_lr, min_lr, warmup, total):
    """The paper's schedule in samples: linear warmup, then cosine decay."""
    s = jnp.asarray(samples, jnp.float32)
    warm = peak_lr * s / jnp.maximum(warmup, 1)
    prog = jnp.clip((s - warmup) / jnp.maximum(total - warmup, 1), 0.0, 1.0)
    cos = min_lr + 0.5 * (peak_lr - min_lr) * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(s < warmup, warm, cos)


def decide(rung: int, var_l1: float, gsq: float, eta: float,
           rungs: list[int]) -> int:
    """Algorithm 1's test on a ladder: T = var/(η²‖g‖²); when T exceeds the
    batch, the smallest rung covering ⌈T⌉ (the top rung at most)."""
    t = var_l1 / (eta ** 2 * gsq + 1e-30)
    if t <= rung:
        return rung
    want = math.ceil(t)
    return next((r for r in sorted(rungs) if r >= want), max(rungs))


def variance_groups(step_impl: str, accum: int, rows: int, workers: int,
                    keep: int, block_rows: int) -> list[list[tuple]]:
    """The step's variance groups, each a list of (microbatch, first row,
    end row) blocks: one group per microbatch (ACCUM-NORM) or per worker
    (FSDP-Norm).  Worker j holds rows [j·rows/workers, (j+1)·rows/workers)
    of every microbatch, as the trainer's batch sharding lays them out; the
    first `keep` of them count."""
    per = rows // workers

    def blocks(m, j):
        lo = j * per
        return [(m, r0, min(r0 + block_rows, lo + keep))
                for r0 in range(lo, lo + keep, block_rows)]
    if step_impl == "fsdp_norm":
        return [[b for m in range(accum) for b in blocks(m, j)]
                for j in range(workers)]
    return [[b for j in range(workers) for b in blocks(m, j)]
            for m in range(accum)]


def group_variance(step_impl: str, group_sq: list[float], gsq: float,
                   workers: int) -> float:
    """The norm test's ‖Var‖₁ from each group's ‖g_k‖² and ‖g‖² (the
    groups are of equal size, so g is their mean).  FSDP-Norm:
    (1/J) Σ_j ‖g_j − g‖².  ACCUM-NORM: the microbatch variance
    (Σ_m ‖g_m‖² − M‖g‖²)/(M − 1), rescaled by J/M to the per-worker
    minibatch (0 for a single microbatch)."""
    k = len(group_sq)
    if step_impl == "fsdp_norm":
        return max((sum(group_sq) - k * gsq) / k, 0.0)
    v_m = max((sum(group_sq) - k * gsq) / max(k - 1, 1), 0.0)
    return v_m * workers / k if k > 1 else 0.0


def placement(like, devices):
    """Where the reference keeps a tree shaped like `like`: on one device,
    or over several, each leaf split along its largest axis that they
    divide (replicated where none does)."""
    if devices is None or len(devices) < 2:
        return None
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("ref",))
    n = len(devices)

    def one(a):
        axes = [i for i, d in enumerate(a.shape) if d % n == 0]
        if not axes:
            return NamedSharding(mesh, P())
        ax = max(axes, key=lambda i: a.shape[i])
        return NamedSharding(mesh, P(*[("ref" if i == ax else None)
                                       for i in range(a.ndim)]))
    return jax.tree.map(one, like)


def _jit(f, out_shardings=None):
    """`jax.jit`, with the output placement where one is given."""
    return (jax.jit(f) if out_shardings is None
            else jax.jit(f, out_shardings=out_shardings))


def run(ref, model: dict, opt: dict, *, seed: int, data_seed: int,
        steps: int, rows: int, workers: int, seq_len: int,
        total_samples: int, eta: float, rungs: list[int], schedule: str,
        test_interval: int, step_impl: str = "accum_norm",
        block_rows: int = 2, dtype=jnp.float32, fault: str | None = None,
        prog_params: dict | None = None, keep_params: bool = False,
        devices=None) -> RefRun:
    """The run's first `steps` steps of the reference module `ref` (see
    the package's doc) at the sizes `model`: each step's rung is
    microbatches of `rows` sequences (`workers` data workers), its
    variance groups as `step_impl` forms them; the first step is at the
    base rung, and under the `adaptive` schedule every `test_interval`-th
    step's decision sets the next.  `prog_params` (leaf path -> host
    array) is the trainer's weights after the same steps: their change
    from the reference's initial weights is measured too.  `keep_params`
    returns the weights after the steps on the host (the control's side).
    `devices` (several) spreads the reference's state over them."""
    if step_impl not in STEP_IMPLS:
        raise ValueError(f"the reference follows {STEP_IMPLS} steps, not "
                         f"{step_impl!r}")
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"no fault {fault!r}; known: {FAULTS}")
    if fault == "exchange" and workers < 2:
        raise ValueError("the exchange fault needs two workers or more")
    key = ref.seed_key(seed)
    like = jax.eval_shape(lambda k: ref.init(k, model), key)
    place = placement(like, devices)
    params = _jit(lambda k: jax.tree.map(lambda a: a.astype(dtype),
                                         ref.init(k, model)), place)(key)
    zeros = _jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, dtype), like), place)
    m_state, v_state = zeros(), zeros()
    src = MarkovTokens(model["vocab_size"], data_seed)
    grad = _jit(ref.block_grad(model),
                None if place is None else (None, None, place))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    scale_tree = jax.jit(lambda a, s: jax.tree.map(
        lambda x: (x * s).astype(x.dtype), a))
    sq = jax.jit(ref.sqnorm)
    b1, b2, eps, wd = opt["beta1"], opt["beta2"], opt["eps"], opt["weight_decay"]

    def adam_leaf(p, g, m, v, lr, c1, c2):
        g = g.astype(jnp.float32)
        m = (b1 * m + (1 - b1) * g).astype(dtype)
        v = (b2 * v + (1 - b2) * g * g).astype(dtype)
        upd = lr * (m / c1) / (jnp.sqrt(v / c2) + eps)
        return ((1.0 - lr * wd) * p - upd).astype(p.dtype), m, v

    adam = jax.jit(lambda p, g, m, v, lr, c1, c2: jax.tree.transpose(
        jax.tree.structure(p), jax.tree.structure((0, 0, 0)),
        jax.tree.map(lambda *a: adam_leaf(*a, lr, c1, c2), p, g, m, v)),
        donate_argnums=(0, 2, 3))

    out = RefRun()
    rung = rungs[0]
    samples = 0
    warmup = max(1, int(opt["warmup_frac"] * total_samples))
    per = rows // workers
    keep = per // 2 if fault == "half_batch" else per
    prec = (jax.default_matmul_precision("highest") if dtype == jnp.float32
            else contextlib.nullcontext())
    with prec:
        for s in range(steps):
            accum = rung // rows
            tokens, labels = step_batch(src, s, accum, rows, seq_len)
            if fault == "exchange":
                tokens[:, rows - per:] = tokens[:, :per]
                labels[:, rows - per:] = labels[:, :per]
            acc, total_n, loss_s, group_sq = None, 0.0, 0.0, []
            for group in variance_groups(step_impl, accum, rows, workers,
                                         keep, block_rows):
                gacc, n_g = None, 0.0
                for m, r0, r1 in group:
                    ls, n, g = grad(params, jnp.asarray(tokens[m, r0:r1]),
                                    jnp.asarray(labels[m, r0:r1]))
                    loss_s += float(ls)
                    n_g += float(n)
                    gacc = g if gacc is None else add(gacc, g)
                group_sq.append(float(sq(gacc)) / n_g ** 2)
                total_n += n_g
                acc = gacc if acc is None else add(acc, gacc)
            g = scale_tree(acc, jnp.float32(1.0 / total_n))
            del acc, gacc
            gsq = float(sq(g))
            var = group_variance(step_impl, group_sq, gsq, workers)
            decision = decide(rung, var, gsq, eta, rungs)
            out.batch.append(rung)
            out.loss.append(loss_s / total_n)
            out.var_l1.append(var)
            out.grad_sqnorm.append(gsq)
            out.decision.append(decision)
            clip = min(1.0, opt["grad_clip"] / (math.sqrt(gsq) + 1e-12))
            g = scale_tree(g, jnp.float32(clip))
            if s == 0:
                out.first_grad = ref.leaf_norms(g)
            lr = warmup_cosine(samples, peak_lr=opt["peak_lr"],
                               min_lr=opt["min_lr"], warmup=warmup,
                               total=total_samples)
            t = jnp.float32(s + 1)
            params, m_state, v_state = adam(params, g, m_state, v_state, lr,
                                            1.0 - b1 ** t, 1.0 - b2 ** t)
            del g
            samples += rung
            if schedule == "adaptive" and (s + 1) % test_interval == 0:
                rung = decision
    del m_state, v_state
    if keep_params:
        out.params = ref.host_leaves(params)
    p0 = initial_weights(ref, model, seed, devices)
    out.change = change_from(p0, _by_path(params))
    if prog_params is not None:
        out.prog_change = change_from(p0, prog_params)
    return out


def _by_path(tree) -> dict:
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def initial_weights(ref, model: dict, seed: int, devices=None):
    """The initial weights from the seed, on the device (or `devices`)."""
    key = ref.seed_key(seed)
    place = placement(jax.eval_shape(lambda k: ref.init(k, model), key),
                      devices)
    return _jit(lambda k: ref.init(k, model), place)(key)


def change_from(p0, params: dict) -> dict:
    """Leaf path -> ‖params − p0‖ of each leaf; `params` maps leaf paths to
    (device or host) arrays."""
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    return {key: float(diff(params[key], v))
            for key, v in _by_path(p0).items()}
