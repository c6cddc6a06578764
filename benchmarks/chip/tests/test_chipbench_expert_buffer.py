"""`expert_buffer_fill`: the pairs on held experts over the dispatch buffer
rows that carried them, over the window's steps only, and silent where the
trainer keeps no buffer rows or the model has no experts."""

import types

import chipbench_tiny  # noqa: F401
from chipbench import spec

import pytest

MODEL = {"hidden_size": 4, "moe_intermediate_size": 2}


def _ctx(history, model=MODEL, setup_steps=1):
    return types.SimpleNamespace(history=history, model=model,
                                 setup_steps=setup_steps)


def test_fill_over_the_window_steps():
    read = spec.metric_reader("expert_buffer_fill")
    ctx = _ctx({"expert_rows": [900.0, 6000.0, 7000.0],
                "expert_buffer_rows": [49152.0, 12288.0, 24576.0]})
    assert read(ctx) == pytest.approx(100.0 * 13000.0 / 36864.0)
    ctx.setup_steps = 2
    assert read(ctx) == pytest.approx(100.0 * 7000.0 / 24576.0)


@pytest.mark.parametrize("history,model", [
    ({"expert_rows": [7.0, 10.0]}, MODEL),
    ({"expert_buffer_rows": [16.0, 16.0]}, MODEL),
    ({"expert_rows": [7.0, 10.0], "expert_buffer_rows": [16.0, 16.0]},
     {"hidden_size": 4}),
    ({"global_batch": [1, 1]}, {"hidden_size": 4}),
], ids=["no-buffer-counter", "no-pair-counter", "no-experts", "dense"])
def test_silent_without_the_counters_or_experts(history, model):
    assert spec.metric_reader("expert_buffer_fill")(
        _ctx(history, model)) is None
