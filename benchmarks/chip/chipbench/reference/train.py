"""Plain reference of the first steps of an adaptive-batch training run.

Follows Algorithm 1 step by step from the seed: the corpus rows of each
step (`markov`), the model's loss and gradient (`dense_decoder`, float32
at `highest` matmul precision), the norm test's statistics over the step's
variance groups, the controller's decision, and global-norm-clipped AdamW
with the trainer's warmup-cosine learning rate.

Each step runs at the rung the reference's own norm test prescribed, so
it follows an adaptive run across its rung crossing.  It runs in blocks
of rows (the gradient of one block at a time, summed), so that its
weights, moments and gradient sums fit one chip at the cell's own sizes;
the initial weights are drawn again from the seed at the end rather than
kept.

`dtype=bfloat16` makes the control (the same step a precision lower), and
`fault="half_batch"` plants a fault the benchmark must catch in the
reference's own step: the second half of every microbatch's rows left
out, the mean taken over the rest.
"""

from __future__ import annotations

import contextlib
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from chipbench.reference import dense_decoder as dd
from chipbench.reference.markov import MarkovTokens, step_batch


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


def run(model: dict, opt: dict, *, seed: int, data_seed: int, steps: int,
        rows: int, workers: int, seq_len: int, total_samples: int,
        eta: float, rungs: list[int], schedule: str, test_interval: int,
        block_rows: int = 2, dtype=jnp.float32, fault: str | None = None,
        prog_params: dict | None = None, keep_params: bool = False
        ) -> RefRun:
    """The run's first `steps` steps, ACCUM-NORM: each step's rung is
    microbatches of `rows` sequences (`workers` data workers), one
    variance group per microbatch; the first step is at the base rung,
    and under the `adaptive` schedule every `test_interval`-th step's
    decision sets the next.  `prog_params` (leaf path -> host array) is
    the trainer's weights after the same steps: their change from the
    reference's initial weights is measured too.  `keep_params` returns
    the weights after the steps on the host (the control's side)."""
    key = dd.seed_key(seed)
    like = jax.eval_shape(lambda k: dd.init(k, model), key)
    params = jax.jit(lambda k: jax.tree.map(lambda a: a.astype(dtype),
                                            dd.init(k, model)))(key)
    zeros = jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, dtype), like))
    m_state, v_state = zeros(), zeros()
    src = MarkovTokens(model["vocab_size"], data_seed)
    grad = dd.block_grad(model)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    scale_tree = jax.jit(lambda a, s: jax.tree.map(
        lambda x: (x * s).astype(x.dtype), a))
    sq = jax.jit(dd.sqnorm)
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
    prec = (jax.default_matmul_precision("highest") if dtype == jnp.float32
            else contextlib.nullcontext())
    with prec:
        for s in range(steps):
            accum = rung // rows
            tokens, labels = step_batch(src, s, accum, rows, seq_len)
            keep = rows // 2 if fault == "half_batch" else rows
            acc, total_n, loss_s, group_sq = None, 0.0, 0.0, []
            for m in range(accum):
                gacc, n_g = None, 0.0
                for r0 in range(0, keep, block_rows):
                    r1 = min(r0 + block_rows, keep)
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
            k = len(group_sq)
            v_m = max((sum(group_sq) - k * gsq) / max(k - 1, 1), 0.0)
            var = v_m * workers / k if k > 1 else 0.0
            decision = decide(rung, var, gsq, eta, rungs)
            out.batch.append(rung)
            out.loss.append(loss_s / total_n)
            out.var_l1.append(var)
            out.grad_sqnorm.append(gsq)
            out.decision.append(decision)
            clip = min(1.0, opt["grad_clip"] / (math.sqrt(gsq) + 1e-12))
            g = scale_tree(g, jnp.float32(clip))
            if s == 0:
                out.first_grad = dd.leaf_norms(g)
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
        out.params = dd.host_leaves(params)
    p0 = initial_weights(model, seed)
    out.change = change_from(p0, _by_path(params))
    if prog_params is not None:
        out.prog_change = change_from(p0, prog_params)
    return out


def _by_path(tree) -> dict:
    return {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def initial_weights(model: dict, seed: int):
    """The initial weights from the seed, on the device."""
    return jax.jit(lambda k: dd.init(k, model))(dd.seed_key(seed))


def change_from(p0, params: dict) -> dict:
    """Leaf path -> ‖params − p0‖ of each leaf; `params` maps leaf paths to
    (device or host) arrays."""
    diff = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b.astype(jnp.float32)))))
    return {key: float(diff(params[key], v))
            for key, v in _by_path(p0).items()}
