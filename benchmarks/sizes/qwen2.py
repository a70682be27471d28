"""Qwen2 is the llama decoder with a bias on q, k and v: the same
arithmetic (sizes/llama.py), the biases counted."""

from __future__ import annotations

from lib.cell import sizes

_llama = sizes("llama")
flops_per_token = _llama.flops_per_token


def param_bytes(conf: dict, weight_dtype: str) -> int:
    return _llama.param_bytes(conf, weight_dtype, qkv_bias=True)
