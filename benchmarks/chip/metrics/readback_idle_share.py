"""Share of the traced window in which device 0 ran nothing while the
trainer's loop thread was inside `train.readback` (the per-step
`float(metrics[...])` reads).  With `input_idle_share` and
`loop_other_idle_share` it adds up to `device_idle_share`.  Silent where
the trace holds no loop span.  Moves `train_tokens_per_s`."""

from chipbench import spans


def read(ctx):
    split = spans.idle_split_of(ctx)
    return None if split is None else split["readback"]
