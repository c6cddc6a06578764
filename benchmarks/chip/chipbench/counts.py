"""Bytes the flat tail's algorithms need, whatever the model.

These are the yardstick's counts: a later change to the program does not
move them, only the time it takes to do the same work.  A model's own
counts (its parameters, its operations per token) come from the
configuration's reference module (`chipbench/reference/`).
"""

from __future__ import annotations

# bytes the flat tail's AdamW must move per parameter, by its algorithm
# (float32 state and parameters): read p, g, m, v; write p, m, v
ADAMW_BYTES_PER_PARAM = 7 * 4
# bytes FSDP-Norm's fused statistics must read per parameter: the worker's
# gradient g_j and the mean gradient g (float32), for ‖g_j − g‖² and ‖g‖²
WORKER_STATS_BYTES_PER_PARAM = 2 * 4


def tail_bytes_per_step(n_params: int, chips: int, step_impl: str) -> dict:
    """Bytes per step per device that the flat tail's kernels must move,
    by kernel family.  Each device updates its share of the buckets (all
    of them on one chip).  Under FSDP-Norm each worker also reads its whole
    gradient and the whole mean gradient for the statistics; ACCUM-NORM
    reads no worker gradient."""
    out = {"adamw": ADAMW_BYTES_PER_PARAM * n_params / chips}
    if step_impl == "fsdp_norm":
        out["stats"] = WORKER_STATS_BYTES_PER_PARAM * n_params
    elif step_impl != "accum_norm":
        raise ValueError(f"no tail count for step_impl {step_impl!r}")
    return out
