"""A configuration's `"reference"` names the module that gives its sizes,
its check against the trainer's model, its counts and its gradient: the
one-chip configuration reads through it exactly what it read before the
harness took the module by name, a module registered by this test alone
drives a whole cell, and an unknown name fails before anything runs."""

import hashlib
import sys
import time
import types

import chipbench_tiny
from chipbench import harness, spec

import jax
import pytest

CELL = "phi3-mini-1chip.adaptive-cross"

# the one-chip reference's numbers at the tiny size, seed 2147483659, bit
# for bit: any change to its arithmetic or to its blocking of rows shows here
PINNED = {
    "phi3-mini-1chip.adaptive-cross": {
        "batch": [8, 32], "decision": [32, 32],
        "loss": [5.553635597229004, 5.556902319192886],
        "var_l1": [0.780636727809906, 0.19402196109294892],
        "grad_sqnorm": [0.8185449838638306, 0.24771535396575928],
        "first_grad": "e55d75fa98578f33", "change": "f1a0c67c45bfdf2c"},
    "phi3-mini-1chip.constant-base": {
        "batch": [8, 8], "decision": [8, 8],
        "loss": [5.553635597229004, 5.545346617698669],
        "var_l1": [0.780636727809906, 0.7522777318954468],
        "grad_sqnorm": [0.8185449838638306, 0.8079406023025513],
        "first_grad": "e55d75fa98578f33", "change": "0e4ea567512b79cd"},
}


def digest(d: dict) -> str:
    return hashlib.sha256(repr(sorted(d.items())).encode()).hexdigest()[:16]


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_one_chip_reference_numbers_are_unchanged(cell):
    r = chipbench_tiny.tiny(cell)
    ref = harness.reference(r["config"], r["traffic"], 1, 2147483659,
                            r["traffic"]["setup_steps"], 10)
    want = PINNED[cell]
    got = {"batch": ref.batch, "decision": ref.decision, "loss": ref.loss,
           "var_l1": ref.var_l1, "grad_sqnorm": ref.grad_sqnorm,
           "first_grad": digest(ref.first_grad),
           "change": digest(ref.change)}
    assert got == want


def test_one_chip_sizes_and_check_come_from_dense_decoder():
    cfg = spec.load_json(spec.config_path("phi3-mini-1chip"))
    assert cfg["reference"] == "dense_decoder"
    ref = spec.reference_module(cfg["reference"])
    m = ref.sizes(cfg["model"])
    assert m == dict(cfg["model"], head_dim=96)
    from repro.configs import get_config
    prog = get_config(cfg["program"]["arch"]).replace(
        **cfg["program"]["overrides"])
    ref.check_program(prog, m)
    with pytest.raises(ValueError, match="d_ff"):
        ref.check_program(prog.replace(d_ff=8192 * 2), m)


def recording(name: str, scale_grad: float = 1.0) -> types.ModuleType:
    """A reference module that only this test registers: the dense block,
    every call recorded, its own counts, and its gradient scaled."""
    one_chip = spec.load_json(spec.config_path("phi3-mini-1chip"))
    dense = spec.reference_module(one_chip["reference"])
    mod = types.ModuleType(f"chipbench.reference.{name}")
    mod.calls = []

    def recorded(fn):
        def call(*a, **k):
            mod.calls.append(fn)
            return getattr(dense, fn)(*a, **k)
        return call
    for fn in ("sizes", "check_program", "seed_key", "init", "sqnorm",
               "leaf_norms", "host_leaves"):
        setattr(mod, fn, recorded(fn))

    def param_count(m):
        mod.calls.append("param_count")
        return 123457

    def flops_per_token(m, seq_len):
        mod.calls.append("flops_per_token")
        return 98765.0

    def block_grad(m):
        mod.calls.append("block_grad")
        f = dense.block_grad(m)

        def scaled(params, tokens, labels):
            s, n, g = f(params, tokens, labels)
            return s, n, jax.tree.map(lambda x: x * scale_grad, g)
        return scaled
    mod.param_count, mod.flops_per_token = param_count, flops_per_token
    mod.block_grad = block_grad
    return mod


@pytest.mark.parametrize("scale, correct", [(1.0, True), (2.0, False)],
                         ids=["dense_gradient", "doubled_gradient"])
def test_registered_reference_drives_the_cell(monkeypatch, capsys, scale,
                                              correct):
    name = "registered_by_test"
    mod = recording(name, scale)
    monkeypatch.setitem(sys.modules, mod.__name__, mod)
    r = chipbench_tiny.tiny(CELL)
    r["config"]["reference"] = name
    out = harness.run_cell(CELL, 2147483659, 0.5, False, time.time(),
                           resolved=r, require_tpu=False)
    assert out["correct"] is correct, out["compared"]
    for fn in ("sizes", "check_program", "param_count", "flops_per_token",
               "block_grad", "init", "sqnorm", "leaf_norms"):
        assert fn in mod.calls, fn
    assert mod.calls.index("check_program") < mod.calls.index("block_grad")
    err = capsys.readouterr().err
    assert f"reference {name}: 123457 parameters, 98765.0 operations" in err


@pytest.mark.parametrize("name", ["no_such_block", "train", "markov",
                                  "../dense_decoder", "Dense"])
def test_unknown_reference_fails_loudly(name):
    with pytest.raises(ValueError, match="reference"):
        spec.reference_module(name)


def test_unknown_reference_fails_before_the_trainer_runs(monkeypatch):
    import repro.launch.train as train_mod

    def never(job):
        raise AssertionError("the trainer ran")
    monkeypatch.setattr(train_mod, "run_training", never)
    r = chipbench_tiny.tiny(CELL)
    r["config"]["reference"] = "no_such_block"
    with pytest.raises(ValueError, match="no_such_block"):
        harness.run_cell(CELL, 1, 0.5, False, time.time(), resolved=r,
                         require_tpu=False)
