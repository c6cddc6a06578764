"""Share of the traced window in which a collective ran on a chip and no
compute did: the worker exchange's exposed time (FSDP-Norm's parameter
gather and its gradient and statistics reductions), as a mean over the
cell's chips.  A synchronous collective counts on the device's `XLA Ops`
line, an asynchronous one from its start to its done on `Async XLA Ops`;
control flow hides nothing, since its event spans its body.  Silent where
no chip ran a collective.  Moves `train_tokens_per_s`."""

from chipbench import trace as tr


def read(ctx):
    lo, hi = ctx.window_ns
    if hi <= lo:
        return None
    exposed = [tr.exposed_collective_ns(
        ops, tr.device_ops(ctx.events, dev, lo, hi, line=tr.ASYNC_OPS_LINE))
        for dev, ops in sorted(ctx.device_ops.items())]
    if all(ns is None for ns in exposed):
        return None
    return 100.0 * sum(ns or 0.0 for ns in exposed) / len(exposed) / (hi - lo)
