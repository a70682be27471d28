"""Bytes and operations of a Qwen3-Next decoder as one expert-parallel
rank serves it, from its config: per layer a Gated DeltaNet or a gated
full-attention mixer (every ``full_attention_interval``-th), a router
over all experts, the experts this rank holds, a shared expert with its
gate, and two norms; the vocabulary slice's embedding and head.

``num_experts`` is the experts HELD; ``expert_parallel.size`` times as
many are scored. The decay's two per-head vectors are float32, every
other leaf the served 16-bit type."""

from __future__ import annotations

import os

from lib.cell import CHECKOUT, CellError

# A program without the Gated DeltaNet mixer would read this
# configuration as a dense llama and serve that: stop such a run here,
# before a chip is touched, and say why.
_NEEDS = "kubeinfer_tpu/inference/gdn.py"
if not os.path.isfile(os.path.join(CHECKOUT, _NEEDS)):
    raise CellError(f"the program next to this benchmark has no {_NEEDS}: "
                    "it cannot serve a qwen3_next configuration")


def _dims(conf: dict) -> dict:
    nk, nv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    ep = (conf.get("expert_parallel") or {}).get("size", 1)
    return {
        "H": conf["hidden_size"], "D": conf["head_dim"],
        "nq": conf["num_attention_heads"],
        "nkv": conf["num_key_value_heads"],
        "qkvz": 2 * nk * dk + 2 * nv * dv, "ba": 2 * nv,
        "conv": conf["linear_conv_kernel_dim"] * (2 * nk * dk + nv * dv),
        "nv": nv, "dv": dv, "out": nv * dv,
        "held": conf["num_experts"], "scored": conf["num_experts"] * ep,
        "F": conf["moe_intermediate_size"],
        "Fs": conf["shared_expert_intermediate_size"],
    }


def is_full(conf: dict, i: int) -> bool:
    return (i + 1) % conf["full_attention_interval"] == 0


def layer_matrices(conf: dict, i: int) -> dict:
    """Elements of layer i's matmul weights, by what a token pays for
    them: ``every`` token runs through these whole; ``expert`` is one
    routed expert's three."""
    d = _dims(conf)
    H = d["H"]
    if is_full(conf, i):
        mixer = H * d["nq"] * 2 * d["D"] + 2 * H * d["nkv"] * d["D"] \
            + d["nq"] * d["D"] * H
    else:
        mixer = H * d["qkvz"] + H * d["ba"] + d["out"] * H
    return {
        "every": mixer + H * d["scored"] + 3 * H * d["Fs"] + H,
        "expert": 3 * H * d["F"],
    }


def param_bytes(conf: dict, weight_dtype: str) -> int:
    if weight_dtype != "bf16":
        raise ValueError(f"unknown weight dtype {weight_dtype!r}")
    d = _dims(conf)
    H, V = d["H"], conf["vocab_size"]
    total = 0
    for i in range(conf["num_hidden_layers"]):
        m = layer_matrices(conf, i)
        total += 2 * (m["every"] + d["held"] * m["expert"])
        total += 2 * 2 * H  # two norms
        if is_full(conf, i):
            total += 2 * 2 * d["D"]  # q_norm, k_norm
        else:
            # convolution taps and the gated norm, 16-bit; A_log and
            # dt_bias, float32
            total += 2 * d["conv"] + 2 * d["dv"] + 2 * 4 * d["nv"]
    return total + 2 * (2 * V * H) + 2 * H


def recurrent_state_bytes(conf: dict, slots: int) -> int:
    """What the Gated DeltaNet layers hold for ``slots`` requests
    instead of pages: per layer and slot a FLOAT32 state of one
    Dk x Dv matrix a value head, and the convolution's last inputs
    (kernel - 1 rows of its channels) in the served 16-bit type. A
    state kept in a narrower type is another model (PERF.md, section
    2), and half these bytes."""
    d = _dims(conf)
    state = 4 * d["nv"] * conf["linear_key_head_dim"] * d["dv"]
    tail = 2 * (d["conv"] - d["conv"] // conf["linear_conv_kernel_dim"])
    linear = sum(not is_full(conf, i)
                 for i in range(conf["num_hidden_layers"]))
    return linear * slots * (state + tail)


def flops_per_token(conf: dict) -> dict:
    """Multiply-adds times two that the weights ask of one token here:
    the held pairs a token expects (top-k times held over scored) and
    everything every token runs through. The recurrences' and the
    attention's own products are left out: a share of the peak built on
    this reads low, never high."""
    d = _dims(conf)
    pairs = conf["num_experts_per_tok"] * d["held"] / d["scored"]
    layers = 0.0
    for i in range(conf["num_hidden_layers"]):
        m = layer_matrices(conf, i)
        layers += 2 * (m["every"] + pairs * m["expert"])
    return {"layers": layers,
            "head": 2 * d["H"] * conf["vocab_size"]}
