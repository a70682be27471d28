"""Bytes of a llama/qwen2-shaped model's parameters from its config,
the arithmetic ``chip_smoke.expected_param_bytes`` proved on the chip
(PR 22), here for both weight types. It is what keeps a server that
quietly serves another preset from passing as this configuration."""

from __future__ import annotations


def param_bytes(conf: dict, weight_dtype: str) -> int:
    H, F = conf["hidden_size"], conf["intermediate_size"]
    V, L = conf["vocab_size"], conf["num_hidden_layers"]
    n_q = conf["num_attention_heads"]
    n_kv = conf.get("num_key_value_heads", n_q)
    D = conf.get("head_dim") or H // n_q
    q_dim, kv_dim = n_q * D, n_kv * D
    proj = [(H, q_dim), (H, kv_dim), (H, kv_dim), (q_dim, H),
            (H, F), (H, F), (F, H)]
    if weight_dtype == "int8":
        # int8 codes and one f32 scale per output column
        layer = sum(i * o + 4 * o for i, o in proj)
    elif weight_dtype == "bf16":
        layer = sum(2 * i * o for i, o in proj)
    else:
        raise ValueError(f"unknown weight dtype {weight_dtype!r}")
    layer += 2 * (2 * H)  # two norms, bf16
    if conf.get("model_type") == "qwen2":
        layer += 2 * (q_dim + 2 * kv_dim)  # q/k/v biases, bf16
    heads = 1 if conf.get("tie_word_embeddings") else 2
    return L * layer + heads * (V * H) * 2 + 2 * H
