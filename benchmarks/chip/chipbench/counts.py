"""Operations and bytes the algorithms need, from the configuration's sizes.

These are the yardstick's counts: a later change to the program does not
move them, only the time it takes to do the same work.
"""

from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    """Weights of one decoder layer that enter a matrix product."""
    d, h, kv, dh, ff = (m["hidden_size"], m["num_attention_heads"],
                        m["num_key_value_heads"], m["head_dim"],
                        m["intermediate_size"])
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    mlp = 3 * d * ff if m["hidden_act"] == "silu" else 2 * d * ff
    return attn + mlp


def param_count(m: dict) -> int:
    """Every parameter: embedding, layers (with their two norm scales),
    final norm and (untied) output head."""
    d, v, n = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    head = 0 if m.get("tie_word_embeddings") else v * d
    return v * d + head + n * (layer_matmul_params(m) + 2 * d) + d


def flops_per_token(m: dict, seq_len: int) -> float:
    """Forward plus backward operations per trained token, recomputation not
    counted: 6 per weight of every matrix product (layers and output head;
    the embedding is a lookup) and causal attention's two products, whose
    query at position i meets i + 1 keys, (seq_len + 1) / 2 on average."""
    d, v, n = m["hidden_size"], m["vocab_size"], m["num_hidden_layers"]
    h, dh = m["num_attention_heads"], m["head_dim"]
    matmul = n * layer_matmul_params(m) + v * d
    attn_fwd = n * 2 * 2 * h * dh * (seq_len + 1) / 2
    return 6.0 * matmul + 3.0 * attn_fwd


# bytes the flat tail's AdamW must move per parameter, by its algorithm
# (float32 state and parameters): read p, g, m, v; write p, m, v
ADAMW_BYTES_PER_PARAM = 7 * 4


def tail_bytes_per_step(n_params: int, chips: int) -> dict:
    """Bytes per step per device that the flat tail's kernels must move,
    by kernel family.  ACCUM-NORM updates each device's share of the
    buckets (all of them on one chip) and reads no worker gradient."""
    return {"adamw": ADAMW_BYTES_PER_PARAM * n_params / chips}
