"""Share of device 0's busy time in the traced window spent in operations
whose name stack holds the trainer's `attention` scope: the attention
mixer's projections, scores, softmax and output, forward, backward and
recomputed.  A fusion carries its root's name.  Silent where no operation
holds the scope.  Moves `train_tokens_per_s`."""

from chipbench import spans


def read(ctx):
    return spans.scope_share_of(ctx, "attention")
