"""Share of device 0's busy time in the traced window spent in operations
whose name stack holds the trainer's `head` scope: the logits and the
cross-entropy, forward and backward.  A fusion carries its root's name.
Silent where no operation holds the scope.  Moves `train_tokens_per_s`."""

from chipbench import spans


def read(ctx):
    return spans.scope_share_of(ctx, "head")
