"""Bytes and operations of a llama-shaped decoder from its config: per
layer four attention projections, a gated MLP of three and two norms;
an embedding, a final norm and (untied) a head. The byte arithmetic is
what ``chip_smoke.expected_param_bytes`` proved on the chip (PR 22),
here for both weight types. It is what keeps a server that quietly
serves another preset from passing as this configuration."""

from __future__ import annotations


def projections(conf: dict) -> list[tuple[int, int]]:
    """(in, out) of the seven matmul weights of one layer."""
    H, F = conf["hidden_size"], conf["intermediate_size"]
    n_q = conf["num_attention_heads"]
    n_kv = conf.get("num_key_value_heads", n_q)
    D = conf.get("head_dim") or H // n_q
    q_dim, kv_dim = n_q * D, n_kv * D
    return [(H, q_dim), (H, kv_dim), (H, kv_dim), (q_dim, H),
            (H, F), (H, F), (F, H)]


def param_bytes(conf: dict, weight_dtype: str, qkv_bias: bool = False) -> int:
    H, V, L = (conf["hidden_size"], conf["vocab_size"],
               conf["num_hidden_layers"])
    proj = projections(conf)
    if weight_dtype == "int8":
        # int8 codes and one f32 scale per output column
        layer = sum(i * o + 4 * o for i, o in proj)
    elif weight_dtype == "bf16":
        layer = sum(2 * i * o for i, o in proj)
    else:
        raise ValueError(f"unknown weight dtype {weight_dtype!r}")
    layer += 2 * (2 * H)  # two norms, bf16
    if qkv_bias:
        layer += 2 * sum(o for _, o in proj[:3])  # q/k/v biases, bf16
    heads = 1 if conf.get("tie_word_embeddings") else 2
    return L * layer + heads * (V * H) * 2 + 2 * H


def flops_per_token(conf: dict) -> dict:
    """Multiply-adds times two that the weights ask of one token:
    ``layers`` for a token run through the decoder stack, ``head`` for
    a position whose logits are made. The attention's own products,
    which grow with the context, are left out: a share of the peak
    built on this reads low, never high."""
    return {
        "layers": 2 * conf["num_hidden_layers"] * sum(
            i * o for i, o in projections(conf)),
        "head": 2 * conf["hidden_size"] * conf["vocab_size"],
    }
