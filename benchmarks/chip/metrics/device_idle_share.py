"""Share of the traced window in which device 0 ran no operation: one minus
the union of its operations' intervals over the window.  Moves
`train_tokens_per_s`."""

from chipbench import trace as tr


def read(ctx):
    ops = ctx.device_ops.get(0)
    if not ops or ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_ns(ops) * 1e-9 / ctx.window_s)
