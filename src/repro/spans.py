"""Names of the program's profiler spans and named scopes (DESIGN §16).

The training loop wraps each phase of a step in a host span
(`jax.profiler.TraceAnnotation`) named here, with the 1-based step as its
`step` argument; the model wraps its big blocks in `jax.named_scope`s,
the attention core in one more inside `attention`, and the experts'
grouped matmuls in one inside `moe`.
All of them land in the JAX profiler's own trace, on the device trace's
clock, and cost one level check each while no trace is active.
"""

from __future__ import annotations

import functools

import jax

# one iteration of the training loop, in loop order
BATCH = "train.batch"            # make_batch (+ pad_to_bucket)
LOOKUP = "train.lookup"          # the step executable: engine or legacy
PUT = "train.put"                # the batch to the device
LR = "train.lr"                  # warmup_cosine
DISPATCH = "train.dispatch"      # the step call (asynchronous)
READBACK = "train.readback"      # float(metrics[...])
FENCE = "train.fence"            # block_until_ready on the new state
CONTROLLER = "train.controller"  # controller_update (adaptive only)
WARMUP = "train.warmup"          # warmup_agreed (bucketed engine only)
LOOP = (BATCH, LOOKUP, PUT, LR, DISPATCH, READBACK, FENCE, CONTROLLER,
        WARMUP)

# named scopes of the model's step program
ATTENTION = "attention"
MLP = "mlp"
HEAD = "head"
SCOPES = (ATTENTION, MLP, HEAD)   # every decoder model's step holds these
# the whole expert layer (`models/moe.py`), in models with expert layers
MOE = "moe"
# nested in ATTENTION: the score-softmax-output core (`ops.attention_core`),
# without the projections and RoPE
ATTENTION_CORE = "attention_core"
# nested in MOE: the experts' three grouped matmuls (`ops.grouped_matmul`)
EXPERT_MATMUL = "expert_matmul"

# counters the step returns for a model with expert layers, read back with
# the statistics and kept in the run's history under these names
EXPERT_ROWS = "expert_rows"          # token-expert pairs on held experts
EXPERT_BUFFER_ROWS = "expert_buffer_rows"  # dispatch buffer rows they took
EXPERT_LOAD_MAX = "expert_load_max"  # largest held expert's pairs / mean
COUNTERS = (EXPERT_ROWS, EXPERT_BUFFER_ROWS, EXPERT_LOAD_MAX)


def span(name: str, step: int):
    """A host span of the training loop: `name` with the step it belongs
    to as its `step` argument."""
    return jax.profiler.TraceAnnotation(name, step=step)


def scoped(name: str):
    """Decorator: run the function under `jax.named_scope(name)`, entered
    afresh on each call (one shared scope object is not thread-safe, and
    the warm-up thread traces the model beside the loop)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
