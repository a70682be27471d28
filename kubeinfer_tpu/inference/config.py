"""Model configuration for the native inference runtime.

Field names follow the HuggingFace llama config vocabulary so checkpoints
map 1:1 (weights.py); presets cover the model families the reference's
samples reference (facebook/opt-style tiny demo models up through
llama-70B-class shapes for sizing math).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any


def _hf_head_dim_override(d: dict) -> int:
    """Explicit head width from a HF config dict, 0 when derivable.

    GemmaConfig defaults head_dim to 256 REGARDLESS of
    hidden_size/num_heads, so a gemma config.json that omits the key
    still means 256 — deriving it would build a wrong-geometry model
    whose q reshape fails against the checkpoint's 256-wide heads.
    """
    derived = d["hidden_size"] // d["num_attention_heads"]
    default = 256 if d.get("model_type") == "gemma" else derived
    hd = d.get("head_dim", default)
    return hd if hd != derived else 0


@dataclass(frozen=True)
class ModelConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32  # < heads => grouped-query attention
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    # Qwen2-family attention: biases on q/k/v projections (o stays
    # bias-free — the Qwen2 scheme; published llama checkpoints never
    # ship attention biases, so the hypothetical llama attention_bias
    # o-projection bias is deliberately unsupported)
    qkv_bias: bool = False
    # Mixtral-family sparse MLP: >0 replaces every dense MLP with a
    # top-k routed mixture of SwiGLU experts (moe.py)
    num_local_experts: int = 0
    num_experts_per_tok: int = 2
    # Gemma-family deltas from the llama recipe: tanh-approx GeGLU
    # instead of SwiGLU ("gelu_pytorch_tanh"), embeddings scaled by
    # sqrt(hidden_size) on the way in, and RMSNorm weights stored as an
    # OFFSET from 1 (x_norm * (1 + w), zero-init) rather than a gain
    hidden_act: str = "silu"
    scale_embeddings: bool = False
    rmsnorm_offset: bool = False
    # Explicit head width for families where it is NOT
    # hidden_size/num_heads (gemma-7b: 16 heads x 256 on a 3072 hidden —
    # the q/o projections are then [H, heads*head_dim] rectangles, which
    # the decoder already handles generically). 0 = derive.
    head_dim_override: int = 0
    # Qwen3-Next family (hybrid linear attention + sparse experts).
    # ``layer_types`` names each layer's mixer, "linear_attention"
    # (Gated DeltaNet, gdn.py) or "full_attention"; empty = every layer
    # is full attention, the only kind the other families have. A GDN
    # layer holds a fixed-size recurrent state per slot and no pages.
    layer_types: tuple[str, ...] = ()
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    # full-attention deltas of the family: a per-head sigmoid gate on
    # the attention output packed into q_proj ([q | gate] per head), RMS
    # norms on each q and k head, rotary on the head's first
    # ``partial_rotary_factor`` share only
    attn_output_gate: bool = False
    qk_norm: bool = False
    partial_rotary_factor: float = 1.0
    # routed experts: the router scores ``num_experts_routed`` experts,
    # this replica HOLDS ``num_local_experts`` of them starting at
    # ``expert_offset`` (one expert-parallel rank's share; pairs routed
    # to an absent expert add nothing here). 0 routed = the Mixtral
    # case, every scored expert is held. ``moe_intermediate_size`` is
    # the expert width when it is not ``intermediate_size``; a shared
    # expert (> 0) runs for every token behind a sigmoid gate.
    num_experts_routed: int = 0
    expert_offset: int = 0
    moe_intermediate_size: int = 0
    shared_expert_intermediate_size: int = 0

    @property
    def head_dim(self) -> int:
        if self.head_dim_override:
            return self.head_dim_override
        return self.hidden_size // self.num_attention_heads

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def router_width(self) -> int:
        return self.num_experts_routed or self.num_local_experts

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def recurrent(self) -> bool:
        """Some layer keeps a recurrent state instead of pages: prefix
        blocks alone cannot resume such a model (batching.py)."""
        return "linear_attention" in self.layer_types

    def check_serving(self, weight_dtype: str = "bf16",
                      kv_dtype: str = "bf16", tp: int = 1, sp: int = 1,
                      speculation: bool = False) -> None:
        """Refuse, at configuration time and by name, what this model
        cannot be served with yet."""
        if not self.recurrent:
            return
        refused = [
            what for what, on in (
                ("--weight-dtype int8", weight_dtype != "bf16"),
                ("--kv-dtype int8", kv_dtype != "bf16"),
                ("--tensor-parallel-size > 1", tp > 1),
                ("--sequence-parallel-size > 1", sp > 1),
                ("a draft model or speculation", speculation),
            ) if on
        ]
        if refused:
            raise ValueError(
                "not implemented for a model with linear-attention "
                "(recurrent-state) layers: " + ", ".join(refused)
            )

    def layer_is_linear(self, i: int) -> bool:
        return bool(self.layer_types) and (
            self.layer_types[i] == "linear_attention")

    @property
    def full_attention_layers(self) -> tuple[int, ...]:
        """Indices of the layers that keep keys and values in pages."""
        return tuple(i for i in range(self.num_hidden_layers)
                     if not self.layer_is_linear(i))

    def __post_init__(self) -> None:
        if not self.head_dim_override and (
            self.hidden_size % self.num_attention_heads
        ):
            raise ValueError("hidden_size must divide by num_attention_heads")
        # refuse-at-config-time (same convention as the attention_bias
        # check in from_hf_dict): an unknown activation would otherwise
        # only raise mid-jit-trace inside the first forward
        if self.hidden_act not in ("silu", "gelu_pytorch_tanh"):
            raise ValueError(
                f"unsupported hidden_act {self.hidden_act!r} "
                "(silu and gelu_pytorch_tanh are implemented)"
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                "num_attention_heads must divide by num_key_value_heads"
            )
        # a config read back from JSON (checkpoint.py) carries a list
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if self.layer_types:
            if len(self.layer_types) != self.num_hidden_layers:
                raise ValueError(
                    "layer_types must name every one of the "
                    f"{self.num_hidden_layers} layers"
                )
            bad = set(self.layer_types) - {
                "linear_attention", "full_attention"}
            if bad:
                raise ValueError(f"unknown layer type(s) {sorted(bad)}")
        if self.recurrent and (
            self.linear_num_value_heads % max(self.linear_num_key_heads, 1)
            or self.linear_conv_kernel_dim < 2
        ):
            raise ValueError(
                "linear attention needs value heads that divide by key "
                "heads and a convolution of at least 2 taps"
            )
        if self.num_experts_routed and not (
            0 <= self.expert_offset
            and self.expert_offset + self.num_local_experts
            <= self.num_experts_routed
        ):
            raise ValueError(
                "the held experts must lie inside the routed ones"
            )

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "ModelConfig":
        """Build from a HuggingFace config.json dict (llama family)."""
        # llama-style attention_bias=true also puts a bias on o_proj,
        # which this runtime does not model; loading such a checkpoint
        # with that bias silently dropped would corrupt every layer's
        # attention output, so refuse at config time instead.
        if d.get("attention_bias", False) and d.get("model_type") != "qwen2":
            raise ValueError(
                "attention_bias=true (o_proj bias) is not supported; "
                "only the Qwen2 q/k/v-bias scheme is implemented"
            )
        if d.get("model_type") == "qwen3_next":
            return cls._from_qwen3_next(d)
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_hidden_layers=d["num_hidden_layers"],
            num_attention_heads=d["num_attention_heads"],
            num_key_value_heads=d.get(
                "num_key_value_heads", d["num_attention_heads"]
            ),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            rope_theta=d.get("rope_theta", 10000.0),
            max_position_embeddings=d.get("max_position_embeddings", 4096),
            tie_word_embeddings=d.get("tie_word_embeddings", False)
            or d.get("model_type") == "gemma",
            qkv_bias=d.get("model_type") == "qwen2",
            num_local_experts=d.get("num_local_experts", 0),
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
            hidden_act=(
                "gelu_pytorch_tanh"
                if d.get("model_type") == "gemma"
                else d.get("hidden_act", "silu")
            ),
            scale_embeddings=d.get("model_type") == "gemma",
            rmsnorm_offset=d.get("model_type") == "gemma",
            head_dim_override=_hf_head_dim_override(d),
        )

    @classmethod
    def _from_qwen3_next(cls, d: dict[str, Any]) -> "ModelConfig":
        """Qwen3-Next: three Gated DeltaNet layers to one gated full-
        attention layer, every MLP a routed mixture with a shared
        expert. ``expert_parallel`` {"size", "rank"} (absent = 1, 0) is
        the share this replica serves: ``num_experts`` counts the
        experts it HOLDS, the router scores size times as many."""
        unsupported = [
            k for k, want in (("decoder_sparse_step", 1),
                              ("mlp_only_layers", []),
                              ("rope_scaling", None),
                              ("norm_topk_prob", True),
                              ("use_sliding_window", False))
            if d.get(k, want) != want
        ]
        if unsupported:
            raise ValueError(
                f"qwen3_next with {unsupported} set is not supported"
            )
        L = d["num_hidden_layers"]
        every = d.get("full_attention_interval", 4)
        types = d.get("layer_types") or [
            "full_attention" if (i + 1) % every == 0
            else "linear_attention" for i in range(L)
        ]
        ep = d.get("expert_parallel") or {}
        size, rank = ep.get("size", 1), ep.get("rank", 0)
        held = d["num_experts"]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d.get("intermediate_size", 0),
            num_hidden_layers=L,
            num_attention_heads=d["num_attention_heads"],
            num_key_value_heads=d["num_key_value_heads"],
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
            rope_theta=float(d.get("rope_theta", 10000.0)),
            max_position_embeddings=d.get("max_position_embeddings", 4096),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            num_local_experts=held,
            num_experts_per_tok=d["num_experts_per_tok"],
            hidden_act=d.get("hidden_act", "silu"),
            rmsnorm_offset=True,
            head_dim_override=_hf_head_dim_override(d),
            layer_types=tuple(types),
            linear_num_key_heads=d["linear_num_key_heads"],
            linear_num_value_heads=d["linear_num_value_heads"],
            linear_key_head_dim=d["linear_key_head_dim"],
            linear_value_head_dim=d["linear_value_head_dim"],
            linear_conv_kernel_dim=d["linear_conv_kernel_dim"],
            attn_output_gate=True,
            qk_norm=True,
            partial_rotary_factor=d.get("partial_rotary_factor", 0.25),
            num_experts_routed=held * size,
            expert_offset=held * rank,
            moe_intermediate_size=d["moe_intermediate_size"],
            shared_expert_intermediate_size=d.get(
                "shared_expert_intermediate_size", 0),
        )


PRESETS: dict[str, ModelConfig] = {
    # CI-sized model: small enough for the 1-core test box, GQA on
    "tiny": ModelConfig(
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=2,
        max_position_embeddings=512,
    ),
    # benchmark-sized model (~280M params): big enough that decode is
    # HBM-bound like production models, small enough to init on-chip in
    # seconds
    "bench-280m": ModelConfig(
        vocab_size=32000,
        hidden_size=1024,
        intermediate_size=4096,
        num_hidden_layers=16,
        num_attention_heads=16,
        num_key_value_heads=8,
        max_position_embeddings=4096,
    ),
    # serving-scale benchmark model (~1.7B params, llama-family shape):
    # big enough that HBM pressure, bucketing, and flash attention bite
    # (r4 verdict item 3 — every published serving number was 280M),
    # small enough to random-init on a 16GB v5e chip with headroom for
    # KV caches (bf16 weights ~3.5GB)
    "bench-1p7b": ModelConfig(
        vocab_size=32000,
        hidden_size=2048,
        intermediate_size=8192,
        num_hidden_layers=24,
        num_attention_heads=16,
        num_key_value_heads=8,
        max_position_embeddings=4096,
    ),
    "qwen2-7b": ModelConfig(
        vocab_size=152064,
        hidden_size=3584,
        intermediate_size=18944,
        num_hidden_layers=28,
        num_attention_heads=28,
        num_key_value_heads=4,
        rms_norm_eps=1e-6,
        rope_theta=1000000.0,
        max_position_embeddings=32768,
        qkv_bias=True,
    ),
    "tiny-gemma": ModelConfig(  # demo/e2e-sized gemma-family config
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        num_key_value_heads=1,
        rms_norm_eps=1e-6,
        max_position_embeddings=512,
        tie_word_embeddings=True,
        hidden_act="gelu_pytorch_tanh",
        scale_embeddings=True,
        rmsnorm_offset=True,
    ),
    "gemma-2b": ModelConfig(
        vocab_size=256000,
        hidden_size=2048,
        intermediate_size=16384,
        num_hidden_layers=18,
        num_attention_heads=8,
        num_key_value_heads=1,  # multi-query attention
        rms_norm_eps=1e-6,
        max_position_embeddings=8192,
        tie_word_embeddings=True,
        hidden_act="gelu_pytorch_tanh",
        scale_embeddings=True,
        rmsnorm_offset=True,
    ),
    "gemma-7b": ModelConfig(
        vocab_size=256000,
        hidden_size=3072,
        intermediate_size=24576,
        num_hidden_layers=28,
        num_attention_heads=16,
        num_key_value_heads=16,
        head_dim_override=256,  # 16 x 256 = 4096-wide q/o on 3072 hidden
        rms_norm_eps=1e-6,
        max_position_embeddings=8192,
        tie_word_embeddings=True,
        hidden_act="gelu_pytorch_tanh",
        scale_embeddings=True,
        rmsnorm_offset=True,
    ),
    "mixtral-8x7b": ModelConfig(
        vocab_size=32000,
        hidden_size=4096,
        intermediate_size=14336,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=8,
        rms_norm_eps=1e-5,
        rope_theta=1000000.0,
        max_position_embeddings=32768,
        num_local_experts=8,
        num_experts_per_tok=2,
    ),
    "llama-3-8b": ModelConfig(
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_hidden_layers=32,
        num_attention_heads=32,
        num_key_value_heads=8,
        rope_theta=500000.0,
        max_position_embeddings=8192,
    ),
    "llama-3-70b": ModelConfig(
        vocab_size=128256,
        hidden_size=8192,
        intermediate_size=28672,
        num_hidden_layers=80,
        num_attention_heads=64,
        num_key_value_heads=8,
        rope_theta=500000.0,
        max_position_embeddings=8192,
    ),
}
