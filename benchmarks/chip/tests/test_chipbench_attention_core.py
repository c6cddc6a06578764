"""The `attention_core_share` reader: the share of busy time in the
attention core's scope, nested in the `attention` scope, on hand-made
device operations named as the compiled step names them."""

import types

import chipbench_tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import spans, spec, trace as tr

import pytest

STEP = "jit(step)/while/body/closed_call"


def op(name, start, dur, stack):
    return {"plane": "/device:TPU:0", "line": "XLA Ops",
            "thread": "/device:TPU:0#0",
            "name": f"%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p)",
            "start_ns": start, "dur_ns": dur, "stats": {"tf_op": stack}}


def read(events, monkeypatch, name):
    monkeypatch.setattr(spans, "traced", lambda: events)
    lo, hi = tr.window(events)
    ctx = types.SimpleNamespace(device_ops={0: tr.device_ops(events, 0, lo,
                                                             hi)},
                                window_ns=(lo, hi))
    return spec.metric_reader(name)(ctx)


def synthetic(core=True):
    """Window [0, 1000): busy [0, 800).  Projections 200, the core's
    forward, recomputed forward and backward kernels 100 + 100 + 200, the
    MLP 200."""
    mark = {"plane": "/host:CPU", "line": "python", "thread": "/host:CPU#0",
            "name": tr.WINDOW_MARK, "start_ns": 0, "dur_ns": 1000,
            "stats": {}}
    inner = "/attention_core" if core else ""
    return [
        mark,
        op("fusion.1", 0, 200, f"{STEP}/jvp()/attention/dot_general"),
        op("jvp_flash_attention_fwd_.1", 200, 100,
           f"{STEP}/jvp()/attention{inner}/jvp(flash_attention_fwd)/"
           "pallas_call"),
        op("jvp_flash_attention_fwd_.2", 300, 100,
           f"{STEP}/transpose(jvp())/checkpoint/rematted_computation/"
           f"attention{inner}/jvp(flash_attention_fwd)/pallas_call"),
        op("transpose_jvp_flash_attention_dkv__.1", 400, 200,
           f"{STEP}/transpose(jvp())/checkpoint/attention{inner}/"
           "transpose(jvp(flash_attention_dkv))/pallas_call"),
        op("fusion.2", 600, 200, f"{STEP}/transpose(jvp(mlp))/dot"),
    ]


def test_attention_core_share(monkeypatch):
    events = synthetic()
    assert read(events, monkeypatch, "attention_core_share") == \
        pytest.approx(50.0)
    assert read(events, monkeypatch, "attention_share") == \
        pytest.approx(75.0)
    assert not spans.holds_scope(f"{STEP}/attention/dot", "attention_core")


def test_attention_core_share_silent_without_the_scope(monkeypatch):
    """A trainer whose attention has no `attention_core` scope (the
    parent commit of the scope)."""
    events = synthetic(core=False)
    assert read(events, monkeypatch, "attention_core_share") is None
    assert read(events, monkeypatch, "attention_share") == \
        pytest.approx(75.0)
