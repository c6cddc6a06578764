"""The FSDP-Norm cell end to end on four host CPU devices at a tiny size:
the trainer's run agrees with the reference, which forms one variance
group per worker and spreads its state over the four; each fault the cell
can have, planted under the timed path, makes `correct` false, and so do
the control and the reference-side faults `control.py` reads."""

import json
import os
import subprocess
import sys

import chipbench_tiny

import pytest

CELL = "phi3-mini-fsdp4.adaptive-cross"

CASES = r'''
import json, sys, time
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp
import numpy as np
import chipbench_tiny
from chipbench import harness
import control

CELL = sys.argv[2]
J, MB = 4, 2


def as_fed(new):
    # placed as the loop places the batch (`jnp.asarray` of host rows)
    return jnp.asarray(np.asarray(new))


def per_worker(x):
    # (M, J*mb, T) -> (M, J, mb, T): worker j's rows of each microbatch
    return x.reshape(x.shape[0], J, MB, *x.shape[2:])


def unchanged(i, fn, params, opt, batch, lr):
    keep = jax.tree.map(jnp.copy, (params, opt))
    out = fn(params, opt, batch, lr)
    return keep[0], keep[1], out[2]


def half_batch(i, fn, params, opt, batch, lr):
    x = batch["labels"]
    labels = np.array(per_worker(np.asarray(x)))
    labels[:, :, MB // 2:] = -1
    return fn(params, opt, dict(batch, labels=as_fed(labels.reshape(
        x.shape))), lr)


def exchange(i, fn, params, opt, batch, lr):
    """The last worker's contribution missing from the reduced gradient:
    its rows replaced by the first worker's."""
    def swap(x):
        y = np.array(x)
        y[:, (J - 1) * MB:] = y[:, :MB]
        return as_fed(y)
    return fn(params, opt, jax.tree.map(swap, batch), lr)


out = {}
for name, fault in [("sound", None), ("state_unchanged", unchanged),
                    ("half_batch", half_batch), ("exchange", exchange)]:
    r = chipbench_tiny.tiny(CELL)
    res = harness.run_cell(CELL, 2147483659, 0.5, False, time.time(),
                           resolved=r, require_tpu=False, fault=fault)
    out[name] = {"correct": res["correct"], "compared": res["compared"]}
r = chipbench_tiny.tiny(CELL)
for variant, nums in control.control_numbers(
        r, 12345, ["bfloat16", "half_batch", "exchange"], 0.5).items():
    ok, _ = harness.compare.verdict(nums, r["cell"]["limits"])
    out["control_" + variant] = {"correct": ok, "compared": nums}
print("CASES " + json.dumps(out))
'''

EXPECT = {"sound": True, "state_unchanged": False, "half_batch": False,
          "exchange": False, "control_bfloat16": False,
          "control_half_batch": False, "control_exchange": False}


@pytest.fixture(scope="module")
def cases():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [chipbench_tiny.CHIP, os.path.join(chipbench_tiny.ROOT, "src")]
        + [p for p in [env.get("PYTHONPATH")] if p])
    res = subprocess.run(
        [sys.executable, "-c", CASES, chipbench_tiny.HERE, CELL],
        cwd=chipbench_tiny.ROOT, capture_output=True, text=True, env=env,
        timeout=900)
    assert res.returncode == 0, res.stderr[-4000:]
    line = [x for x in res.stdout.splitlines() if x.startswith("CASES ")]
    return json.loads(line[-1][len("CASES "):])


@pytest.mark.parametrize("case", sorted(EXPECT))
def test_fsdp_norm_correct_only_on_the_sound_path(cases, case):
    assert cases[case]["correct"] is EXPECT[case], cases[case]["compared"]


def test_fsdp_norm_reference_follows_the_crossing():
    """The tiny cell's reference grows the batch after step 1 from the four
    workers' groups, its state on the test process's one device here."""
    from chipbench import harness
    r = chipbench_tiny.tiny(CELL)
    ref = harness.reference(r["config"], r["traffic"], 4, 99,
                            r["traffic"]["setup_steps"], 10)
    assert ref.batch == [8, 32] and ref.decision == [32, 32]
