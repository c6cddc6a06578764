"""The benchmark's operation and byte counts, by hand."""

import json

import chipbench_tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import counts, peaks, spec

import pytest

TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 4, "num_hidden_layers": 3,
        "vocab_size": 10, "hidden_act": "silu", "tie_word_embeddings": False}


def test_param_count_by_hand():
    # per layer: q, k, v, o 4 * 8 * 8 = 256; gate, up, down 3 * 8 * 16 = 384;
    # two norm scales 16.  Embedding and head 2 * 10 * 8; final norm 8
    assert counts.param_count(TINY) == 3 * (256 + 384 + 16) + 160 + 8


def test_flops_per_token_by_hand():
    # 6 per matmul weight: 3 layers * 640 + head 80 = 2000 -> 12000; causal
    # attention forward per layer 2 products * 2 * heads * dh * (T+1)/2
    # = 2 * 2 * 8 * 4.5 = 144 at T = 8; x3 layers, x3 forward+backward
    assert counts.flops_per_token(TINY, 8) == 12000 + 3 * 3 * 144


def test_tail_bytes_by_hand():
    assert counts.tail_bytes_per_step(1000, 1) == {"adamw": 28000}
    assert counts.tail_bytes_per_step(1000, 4) == {"adamw": 7000}


def test_phi3_mini_full_count_is_the_published_size():
    """At all 32 layers the configuration is Phi-3-mini's 3.8B parameters
    (within the registry test's 5%)."""
    with open(spec.config_path("phi3-mini-1chip")) as f:
        cfg = json.load(f)
    m = dict(cfg["model"], head_dim=96,
             num_hidden_layers=cfg["published"]["num_hidden_layers"])
    assert abs(counts.param_count(m) - 3.8e9) <= 0.05 * 3.8e9


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
