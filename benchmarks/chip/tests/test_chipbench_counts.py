"""The benchmark's operation and byte counts, by hand: a model's own
counts from its configuration's reference module, the tail's from
`counts`."""

import json

import chipbench_tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import counts, peaks, spec

import pytest


def reference_of(config: str):
    with open(spec.config_path(config)) as f:
        cfg = json.load(f)
    return cfg, spec.reference_module(cfg["reference"])


DENSE = reference_of("phi3-mini-1chip")[1]

TINY = {"hidden_size": 8, "intermediate_size": 16, "num_attention_heads": 2,
        "num_key_value_heads": 2, "head_dim": 4, "num_hidden_layers": 3,
        "vocab_size": 10, "hidden_act": "silu", "tie_word_embeddings": False}


def test_param_count_by_hand():
    # per layer: q, k, v, o 4 * 8 * 8 = 256; gate, up, down 3 * 8 * 16 = 384;
    # two norm scales 16.  Embedding and head 2 * 10 * 8; final norm 8
    assert DENSE.param_count(TINY) == 3 * (256 + 384 + 16) + 160 + 8


def test_flops_per_token_by_hand():
    # 6 per matmul weight: 3 layers * 640 + head 80 = 2000 -> 12000; causal
    # attention forward per layer 2 products * 2 * heads * dh * (T+1)/2
    # = 2 * 2 * 8 * 4.5 = 144 at T = 8; x3 layers, x3 forward+backward
    assert DENSE.flops_per_token(TINY, 8) == 12000 + 3 * 3 * 144


def test_tail_bytes_by_hand():
    assert counts.tail_bytes_per_step(1000, 1, "accum_norm") == {
        "adamw": 28000}
    assert counts.tail_bytes_per_step(1000, 4, "accum_norm") == {
        "adamw": 7000}
    # FSDP-Norm: each worker's share of AdamW, and the fused statistics
    # read the whole worker gradient and mean gradient on every worker
    assert counts.tail_bytes_per_step(1000, 4, "fsdp_norm") == {
        "adamw": 7000, "stats": 8000}
    with pytest.raises(ValueError):
        counts.tail_bytes_per_step(1000, 1, "local_sgd")


def test_phi3_mini_full_count_is_the_published_size():
    """At all 32 layers the configuration is Phi-3-mini's 3.8B parameters
    (within the registry test's 5%)."""
    cfg, ref = reference_of("phi3-mini-1chip")
    m = dict(ref.sizes(cfg["model"]),
             num_hidden_layers=cfg["published"]["num_hidden_layers"])
    assert m["head_dim"] == 96
    assert abs(ref.param_count(m) - 3.8e9) <= 0.05 * 3.8e9


@pytest.mark.parametrize("config, layers, params, flops", [
    ("phi3-mini-1chip", 3, 536761344, 2742736896.0),
    ("phi3-mini-fsdp4", 5, 763266048, 4177225728.0)])
def test_counts_of_each_configuration_are_pinned(config, layers, params,
                                                 flops):
    """The counts the cells' per-layer metrics divide by, at seq 2048: the
    one-chip configuration's as its dense counts gave them before the
    reference module carried them."""
    cfg, ref = reference_of(config)
    m = ref.sizes(cfg["model"])
    assert m["num_hidden_layers"] == layers
    assert ref.param_count(m) == params
    assert ref.flops_per_token(m, 2048) == flops


def test_peaks_are_keyed_by_device_kind():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
