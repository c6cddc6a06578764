"""The comparison's arithmetic: the rung Algorithm 1 prescribes, leaf gaps,
the step plan, and the verdict line by line."""

import chipbench_tiny
from chipbench import compare, harness
from chipbench.reference.train import decide

import pytest


def test_decide_takes_the_smallest_covering_rung():
    assert decide(8, 1.0, 1.0, 0.5, [8, 32]) == 8      # T = 4 <= 8
    assert decide(8, 3.0, 1.0, 0.5, [8, 16, 32]) == 16  # T = 12
    assert decide(8, 1e6, 1.0, 0.5, [8, 32]) == 32


def test_expected_rungs_test_only_every_interval():
    hist = {"global_batch": [8] * 6, "var_l1": [1e6] * 6,
            "grad_sqnorm": [1.0] * 6}
    want = compare.expected_rungs(hist, [8, 8, 8], "adaptive", [8, 32],
                                  0.5, 4)
    assert want == [8, 8, 8, 8, 32, 32]
    assert compare.expected_rungs(hist, [32], "constant", [8], 0.5, 1) == \
        [8] * 6


def test_leaf_gap_floors_at_the_median_leaf():
    ref = {"a": 1.0, "b": 1.0, "c": 1e-6}
    prog = {"a": 1.0, "b": 1.1, "c": 2e-6}
    # c's own norm is tiny: its gap is measured against the median (1.0)
    assert compare.leaf_gap(prog, ref) == pytest.approx(0.1)
    assert compare.leaf_gap(prog, ref, keep={"a"}) == 0.0


def test_verdict_names_each_number_and_fails_nan():
    ok, lines = compare.verdict({"x": 0.5, "y": float("nan")},
                                {"x": 1.0, "y": 1.0})
    assert not ok
    assert lines[0] == "x 0.5 limit 1.0"
    assert lines[1].endswith("EXCEEDED")


@pytest.mark.parametrize("test_interval,seconds,window", [
    # the crossing in set-up: every window step at the top rung; 30 s
    # holds 7.5 steps of 4 s -> 8 (half of the last one inside)
    (1, 30, 8),
    # the crossing after window step 6: 6 s at the base rung, then
    # 24 s / 4 s = 6 top-rung steps
    (8, 30, 12),
    # never fewer than two steps
    (1, 1, 2)])
def test_plan_fills_the_window_from_recorded_step_times(test_interval,
                                                         seconds, window):
    r = chipbench_tiny.tiny("phi3-mini-1chip.adaptive-cross")
    traffic = dict(r["traffic"], test_interval=test_interval)
    cell = {"step_s": {"8": 1.0, "32": 4.0}}
    assert harness.plan_steps(cell, traffic, seconds) == {
        "setup": 2, "window": window, "total": 2 + window}
