"""Share of the traced window in which device 0 ran nothing while the
trainer's loop thread was inside `train.batch` or `train.put` (building,
padding and placing the next batch).  Silent where the trace holds no loop
span.  Moves `train_tokens_per_s`."""

from chipbench import spans


def read(ctx):
    split = spans.idle_split_of(ctx)
    return None if split is None else split["input"]
