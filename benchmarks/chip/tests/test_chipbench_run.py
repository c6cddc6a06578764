"""The harness end to end on the CPU at a tiny size: the trainer's run
agrees with the plain reference; the run fails without a chip or without
the trainer; and each fault a cell can have, planted under the timed path,
makes `correct` false, as does the control."""

import os
import shutil
import subprocess
import sys
import time

import chipbench_tiny
from chipbench import harness

import jax
import jax.numpy as jnp
import pytest

CELL = "phi3-mini-1chip.adaptive-cross"


def run(cell=CELL, fault=None, seed=2147483659):
    r = chipbench_tiny.tiny(cell)
    return harness.run_cell(cell, seed, 0.5, False, time.time(),
                            resolved=r, require_tpu=False, fault=fault)


def test_trainer_agrees_with_reference():
    out = run()
    assert out["correct"], out["compared"]
    plan = harness.plan_steps(chipbench_tiny.tiny(CELL)["cell"],
                              chipbench_tiny.tiny(CELL)["traffic"], 0.5)
    assert out["attempted"] == plan["total"] and out["failed"] == 0
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["unit"] == "s"
    assert list(out)[-1] == "compared"


def _unchanged(i, fn, params, opt, batch, lr):
    keep = jax.tree.map(jnp.copy, (params, opt))
    out = fn(params, opt, batch, lr)
    return keep[0], keep[1], out[2]


def _half_batch(i, fn, params, opt, batch, lr):
    labels = batch["labels"]
    half = labels.shape[1] // 2
    batch = dict(batch, labels=labels.at[:, half:].set(-1))
    return fn(params, opt, batch, lr)


def _half_accum(i, fn, params, opt, batch, lr):
    """Half of the step's microbatches dropped, the mean over the rest."""
    labels = batch["labels"]
    half = labels.shape[0] // 2
    batch = dict(batch, labels=labels.at[half:].set(-1))
    return fn(params, opt, batch, lr)


def _from_call(first, fault):
    """`fault` on the step calls from `first` on, the step itself before."""
    def planted(i, fn, *args):
        return fault(i, fn, *args) if i >= first else fn(*args)
    return planted


# call 2 is the first step at the top rung, on its own executable
@pytest.mark.parametrize("fault", [
    _unchanged, _half_batch, _from_call(2, _unchanged),
    _from_call(2, _half_batch), _from_call(2, _half_accum)],
    ids=["state_unchanged", "half_batch", "top_rung_state_unchanged",
         "top_rung_half_batch", "top_rung_half_accum"])
def test_fault_under_timed_path_is_caught(fault):
    out = run(fault=fault)
    assert not out["correct"], out["compared"]


def test_crossing_falls_among_the_compared_steps():
    """The reference follows the cell's first steps across the crossing,
    so both rungs' executables are compared."""
    r = chipbench_tiny.tiny(CELL)
    k = r["traffic"]["setup_steps"]
    ref = harness.reference(r["config"], r["traffic"], 1, 99, k, 10)
    assert ref.batch == [8, 32] and ref.decision == [32, 32]


def test_traced_run_reaches_the_readers(monkeypatch):
    """A `--trace 1` run on the CPU goes through the trace's reduction and
    every reader of the cell; the CPU has no published peaks, so the
    chip's stand in, and no number here is a device metric."""
    from chipbench import peaks
    monkeypatch.setattr(harness.peaks, "peaks_for",
                        lambda kind: peaks.PEAKS["TPU v5 lite"])
    r = chipbench_tiny.tiny(CELL)
    out = harness.run_cell(CELL, 2147483693, 0.5, True, time.time(),
                           resolved=r, require_tpu=False)
    assert out["correct"], out["compared"]
    assert out["device"]["window_s"] > 0 and "busy_s" in out["device"]
    assert set(out["metrics"]) <= {m["name"] for m in
                                   r["metrics"]["per_layer"]}
    assert "step_mfu" in out["metrics"]


def test_control_fails():
    """The reference a precision lower (bfloat16) in the trainer's place."""
    import control
    r = chipbench_tiny.tiny(CELL)
    r["cell"]["limits"] = dict(chipbench_tiny.LIMITS)
    nums = control.control_numbers(r, 12345, ["bfloat16", "half_batch"],
                                   0.5)
    for variant in ("bfloat16", "half_batch"):
        ok, _ = harness.compare.verdict(nums[variant], r["cell"]["limits"])
        assert not ok, (variant, nums[variant])


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELL,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_no_tpu_fails_without_result():
    res = _cli(chipbench_tiny.ROOT)
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert "metrics" not in res.stdout


def test_without_the_trainer_fails(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark."""
    shutil.copy(os.path.join(chipbench_tiny.ROOT, "BENCHMARK.json"),
                tmp_path)
    shutil.copytree(chipbench_tiny.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _cli(str(tmp_path))
    assert res.returncode != 0
    assert "metrics" not in res.stdout
