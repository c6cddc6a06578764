"""The experts' grouped matmuls' share of the chip's bf16 peak on device
0: the operations they must do over the traced steps, over the summed
device time of the operations that do them.  Silent where the trainer
reports no `expert_rows` or no operation holds the scope.  Moves
`train_tokens_per_s`.

Operations: the trainer counts the token-expert pairs it computed on the
experts it holds, over layers and microbatches, per step
(`history["expert_rows"]`).  Each pair meets the three products of a
SwiGLU expert, 2·d·f operations each (d the hidden size, f the expert
width), run four times under `remat full`: forward, recomputed, and twice
in the backward (the input's and the weights' gradients).

Time: the device trace names an operation by its HLO text, and its name
stack (`tf_op`) holds the trainer's scopes; the grouped matmuls run under
`expert_matmul`, inside `moe`.  On the TPU they are the megablox Pallas
kernels, which the trace shows only as `tpu_custom_call`s (`gmm` forward
and for the input's gradient, `tgmm` for the weights'), each with the few
small operations that lay out its groups; all of them count."""

from chipbench import spans, trace as tr

SCOPE = "expert_matmul"
PASSES = 4          # forward, recomputed, and twice in the backward
PRODUCTS = 3        # gate, up and down projections


def ops_per_pair(model: dict) -> float:
    return PASSES * PRODUCTS * 2.0 * model["hidden_size"] \
        * model["moe_intermediate_size"]


def read(ctx):
    rows = ctx.history.get("expert_rows")
    if not rows or "moe_intermediate_size" not in ctx.model:
        return None
    lo, hi = ctx.window_ns
    ops = tr.device_ops(spans.traced(), 0, lo, hi)
    mine = [(e["start_ns"], e["start_ns"] + e["dur_ns"]) for e in ops
            if not tr.WRAPPER.search(tr.opcode(e["name"]))
            and spans.holds_scope(
                str(e.get("stats", {}).get(spans.NAME_STACK_STAT, "")),
                SCOPE)]
    secs = tr.length(tr.union(mine)) * 1e-9
    if secs <= 0:
        return None
    work = sum(rows[ctx.setup_steps:]) * ops_per_pair(ctx.model)
    return 100.0 * work / ctx.peak["bf16_flops_per_s"] / secs
