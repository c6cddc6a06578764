"""Seconds the trainer's engine blocked for an executable in the step that
crossed to a new rung (`history["lookup_wait_s"]`: the wait for the AOT
warm-up's compile, or a build).  The crossing falls in set-up, inside
`transition_stall_s`.  Silent without a crossing or where the trainer
keeps no such counter.  Moves `setup_s`."""

from chipbench import spans


def read(ctx):
    return spans.rung_wait(ctx.history)
