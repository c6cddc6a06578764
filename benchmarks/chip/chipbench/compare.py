"""The comparison that decides `correct`: the trainer's first steps against
the plain reference's, number by number, each against its own limit.

Numbers (all gaps relative to the reference):
  loss_gap        worst step of |loss − loss_ref| / loss_ref
  grad_sqnorm_gap worst step of the norm test's ‖g‖²
  var_l1_gap      worst step of the norm test's ‖Var‖₁
  first_grad_gap  worst leaf of the first (clipped) gradient's norm, as the
                  optimizer got it: its first moment after step 1 / (1 − β₁)
  change_gap      worst leaf of the norm of the weights' change over the
                  followed steps (leaves whose reference gradient is under
                  a thousandth of the median leaf's are left out: Adam moves
                  them by round-off alone)
  rung_mismatches steps whose batch is not the one the norm test (the
                  reference's own statistics where it followed the run, the
                  trainer's after) prescribes
A leaf's gap is |‖x‖ − ‖x_ref‖| / max(‖x_ref‖, median leaf's ‖x_ref‖).
"""

from __future__ import annotations

import statistics

from chipbench.reference.train import decide

LEAF_FLOOR = 1e-3


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    keys = [k for k in sorted(ref) if keep is None or k in keep]
    med = statistics.median(ref[k] for k in sorted(ref))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys)


def expected_rungs(hist: dict, ref_decisions: list[int], schedule: str,
                   rungs: list[int], eta: float, test_interval: int) -> list:
    """The batch of every step as Algorithm 1 prescribes it."""
    want, rung = [], rungs[0]
    for i in range(len(hist["global_batch"])):
        want.append(rung)
        step = i + 1
        if schedule != "adaptive" or rung == max(rungs):
            continue
        if test_interval > 1 and step % test_interval:
            continue
        if i < len(ref_decisions):
            rung = ref_decisions[i]
        else:
            rung = decide(rung, hist["var_l1"][i], hist["grad_sqnorm"][i],
                          eta, rungs)
    return want


def numbers(hist: dict, prog_first_grad: dict, ref, beta1: float,
            schedule: str, rungs: list[int], eta: float,
            test_interval: int) -> dict:
    n = len(ref.loss)
    out = {
        "loss_gap": max(_rel(hist["loss"][i], ref.loss[i]) for i in range(n)),
        "grad_sqnorm_gap": max(_rel(hist["grad_sqnorm"][i],
                                    ref.grad_sqnorm[i]) for i in range(n)),
        "var_l1_gap": max(_rel(hist["var_l1"][i], ref.var_l1[i])
                          for i in range(n)),
    }
    g1 = {k: v / (1.0 - beta1) for k, v in prog_first_grad.items()}
    out["first_grad_gap"] = leaf_gap(g1, ref.first_grad)
    med = statistics.median(ref.first_grad[k] for k in sorted(ref.first_grad))
    live = {k for k in sorted(ref.first_grad)
            if ref.first_grad[k] >= LEAF_FLOOR * med}
    out["change_gap"] = leaf_gap(ref.prog_change, ref.change, keep=live)
    want = expected_rungs(hist, ref.decision, schedule, rungs, eta,
                          test_interval)
    out["rung_mismatches"] = float(sum(
        int(a != b) for a, b in zip(hist["global_batch"], want)))
    return out


def verdict(nums: dict, limits: dict) -> tuple[bool, list[str]]:
    """(every number within its limit, one line per number)."""
    lines, ok = [], True
    for k in sorted(limits):
        v = nums.get(k, float("nan"))
        good = v <= limits[k]
        ok = ok and good
        lines.append(f"{k} {v!r} limit {limits[k]!r}"
                     f"{'' if good else ' EXCEEDED'}")
    return ok, lines
