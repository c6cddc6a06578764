"""How full the expert layer's dispatch buffers ran over the traced steps:
the token-expert pairs computed on held experts over the buffer rows that
carried them, both summed over layers and microbatches per step by the
trainer (`history["expert_rows"]`, `history["expert_buffer_rows"]`), over
the window's steps.  Rows past the held pairs are gathered, cast, gated
and scattered for nothing, so a fuller buffer is less wasted work.
Silent where the trainer reports no buffer rows (a model without expert
layers, or a trainer without the counter).  Moves `train_tokens_per_s`."""


def read(ctx):
    rows = ctx.history.get("expert_rows")
    buffered = ctx.history.get("expert_buffer_rows")
    if not rows or not buffered or "moe_intermediate_size" not in ctx.model:
        return None
    total = sum(buffered[ctx.setup_steps:])
    if total <= 0:
        return None
    return 100.0 * sum(rows[ctx.setup_steps:]) / total
