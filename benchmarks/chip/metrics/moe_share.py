"""Share of device 0's busy time in the traced window spent in operations
whose name stack holds the trainer's `moe` scope: the whole expert layer
(router, dispatch, the experts' grouped matmuls, combine and shared
experts), forward, backward and recomputed.  A fusion carries its root's
name.  Silent where no operation holds the scope (a model without expert
layers, or a trainer without the scope).  Moves `train_tokens_per_s`."""

from chipbench import spans


def read(ctx):
    return spans.scope_share_of(ctx, "moe")
