"""HuggingFace checkpoint loading for the native runtime.

Maps a llama-family HF checkpoint (config.json + *.safetensors, exactly
what the coordinator's ``huggingface-cli download`` drops into the model
cache — coordinator.go:99-105 parity path) onto model.py's param pytree.
Torch Linear weights are [out, in]; ours are [in, out] so the forward is
``x @ W`` — every projection transposes once at load time, never at
inference time.
"""

from __future__ import annotations

import json
import pathlib
from typing import Mapping

import jax.numpy as jnp
import numpy as np

from kubeinfer_tpu.inference.config import ModelConfig
from kubeinfer_tpu.inference.model import Params
from kubeinfer_tpu.inference.sharding import param_placer
from kubeinfer_tpu.inference.weight_quant import quantize_weight


def _to_np(t) -> np.ndarray:
    """Tensor-ish (torch / numpy / jax) -> numpy, bf16-safe."""
    if isinstance(t, np.ndarray):
        return t
    if hasattr(t, "detach"):  # torch
        t = t.detach()
        if t.dtype.__str__() == "torch.bfloat16":
            t = t.float()
        return t.cpu().numpy()
    return np.asarray(t)


def params_from_state_dict(
    sd: Mapping[str, object], cfg: ModelConfig, dtype=jnp.bfloat16,
    weight_dtype: str = "bf16", mesh=None,
) -> Params:
    """HF llama state dict (name -> tensor) -> model.py param pytree.

    ``weight_dtype="int8"`` quantizes each projection as it is mapped
    (weight_quant.quantize_weight on the host tensor), so the
    full-precision [in, out] device copy of a quantized leaf never
    exists — the largest device-resident transient is one layer's
    quantization scratch, not the whole bf16 model.

    ``mesh`` places each layer on its tensor-parallel shards as it is
    mapped, exactly as model.init_params does."""
    if weight_dtype not in ("bf16", "int8"):
        raise ValueError(f"weight_dtype must be bf16|int8: {weight_dtype!r}")

    put = param_placer(mesh, cfg)

    def get(name: str) -> np.ndarray:
        for key in (name, f"model.{name}"):
            if key in sd:
                return _to_np(sd[key])
        raise KeyError(f"checkpoint missing tensor {name!r}")

    def linear(name: str, quant: bool = False):
        w = get(name).T  # [out,in] -> [in,out]
        if quant and weight_dtype == "int8":
            return quantize_weight(jnp.asarray(w, jnp.float32))
        return jnp.asarray(w, dtype)

    layers = []
    for i in range(cfg.num_hidden_layers):
        p = f"layers.{i}"
        layer = {
            "input_layernorm": jnp.asarray(
                get(f"{p}.input_layernorm.weight"), dtype
            ),
            "post_attention_layernorm": jnp.asarray(
                get(f"{p}.post_attention_layernorm.weight"), dtype
            ),
            "q_proj": linear(f"{p}.self_attn.q_proj.weight", quant=True),
            "k_proj": linear(f"{p}.self_attn.k_proj.weight", quant=True),
            "v_proj": linear(f"{p}.self_attn.v_proj.weight", quant=True),
            "o_proj": linear(f"{p}.self_attn.o_proj.weight", quant=True),
        }
        if cfg.num_local_experts > 0:
            # Mixtral naming: block_sparse_moe.gate is the router,
            # experts.{e}.w1/w3/w2 are gate/up/down; stack experts on a
            # leading axis (moe.py's [E, ...] layout, sharded over ep)
            E = cfg.num_local_experts
            m = f"{p}.block_sparse_moe"
            layer["moe"] = {
                "router": linear(f"{m}.gate.weight"),
                "gate_proj": jnp.stack(
                    [linear(f"{m}.experts.{e}.w1.weight") for e in range(E)]
                ),
                "up_proj": jnp.stack(
                    [linear(f"{m}.experts.{e}.w3.weight") for e in range(E)]
                ),
                "down_proj": jnp.stack(
                    [linear(f"{m}.experts.{e}.w2.weight") for e in range(E)]
                ),
            }
        else:
            layer["gate_proj"] = linear(
                f"{p}.mlp.gate_proj.weight", quant=True
            )
            layer["up_proj"] = linear(f"{p}.mlp.up_proj.weight", quant=True)
            layer["down_proj"] = linear(
                f"{p}.mlp.down_proj.weight", quant=True
            )
        if cfg.qkv_bias:  # Qwen2 family
            layer["q_bias"] = jnp.asarray(
                get(f"{p}.self_attn.q_proj.bias"), dtype
            )
            layer["k_bias"] = jnp.asarray(
                get(f"{p}.self_attn.k_proj.bias"), dtype
            )
            layer["v_bias"] = jnp.asarray(
                get(f"{p}.self_attn.v_proj.bias"), dtype
            )
        layers.append(put("layer", layer))
    params: Params = {
        "embed_tokens": put(
            "embed_tokens", jnp.asarray(get("embed_tokens.weight"), dtype)
        ),
        "layers": layers,
        "norm": put("norm", jnp.asarray(get("norm.weight"), dtype)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = put("lm_head", linear("lm_head.weight"))
    return params


def load_pretrained(
    model_dir: str, dtype=jnp.bfloat16, weight_dtype: str = "bf16",
    mesh=None,
) -> tuple[Params, ModelConfig]:
    """Load (params, config) from an HF snapshot directory."""
    root = pathlib.Path(model_dir)
    with open(root / "config.json", "r", encoding="utf-8") as f:
        cfg = ModelConfig.from_hf_dict(json.load(f))

    from safetensors import safe_open

    sd: dict[str, np.ndarray] = {}
    shards = sorted(root.glob("*.safetensors"))
    if not shards:
        raise FileNotFoundError(f"no *.safetensors under {model_dir}")
    for shard in shards:
        with safe_open(str(shard), framework="np") as f:
            for name in f.keys():
                sd[name] = f.get_tensor(name)
    return params_from_state_dict(sd, cfg, dtype, weight_dtype, mesh), cfg
