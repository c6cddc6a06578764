"""Share of device 0's busy time in the traced window spent in operations
whose name stack holds the trainer's `attention_core` scope: the scores,
softmax and output of attention (the flash kernels on TPU, forward,
backward and recomputed), without the projections and RoPE that the
enclosing `attention` scope also holds.  Silent where no operation holds
the scope (a trainer without it).  Moves `train_tokens_per_s`."""

from chipbench import spans


def read(ctx):
    return spans.scope_share_of(ctx, "attention_core")
