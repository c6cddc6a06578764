"""Seconds the first step on a new rung takes beyond that rung's later
steps: its fenced wall time (`history["step_s"]`) minus the median of the
rung's later steps in the same run.  The crossing falls in set-up, so
this is the part of `setup_s` that waiting for the new rung's executable
costs.  Silent where the run holds no crossing.  Moves `setup_s`."""

import statistics


def read(ctx):
    gb, st = ctx.history["global_batch"], ctx.history["step_s"]
    for i in range(1, len(gb)):
        if gb[i] != gb[i - 1]:
            later = [s for j, s in enumerate(st) if j > i and gb[j] == gb[i]]
            if not later:
                return None
            return st[i] - statistics.median(later)
    return None
