"""Decoder-only transformer (llama family) in pure JAX.

TPU-first choices:

- **bf16 params, f32 accumulations where it matters** (RMSNorm stats and
  attention softmax run in f32; matmuls feed the MXU in bf16 by default).
- **Static shapes everywhere**: the forward takes [B, T] tokens plus an
  explicit position offset so the same compiled function serves prefill
  (T = padded prompt) and decode (T = 1) with a KV cache.
- **No module framework**: params are a plain pytree of jnp arrays with
  HF-compatible naming (weights.py maps safetensors 1:1), so sharding is
  a tree_map of PartitionSpecs (sharding.py) and checkpoints need no
  object graph.

Numerical parity with ``transformers`` LlamaForCausalLM is pinned by
tests/test_inference_model.py (same weights → logits within bf16/f32
tolerance).
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from kubeinfer_tpu.inference.config import ModelConfig
from kubeinfer_tpu.inference.kv_blocks import page_dims, write_tokens
from kubeinfer_tpu.inference.weight_quant import quantize_layer, wq_dot

Params = dict[str, Any]


# --- initialization --------------------------------------------------------


def init_params(
    cfg: ModelConfig, key: jax.Array, dtype=jnp.float32,
    weight_dtype: str = "bf16", mesh=None,
) -> Params:
    """Random init (normal, 0.02 std — HF default) with HF tree layout.

    ``weight_dtype="int8"`` quantizes each layer's projection leaves as
    it is built (weight_quant.quantize_layer), mirroring the load-time
    path in weights.params_from_state_dict — the full-precision layer
    never outlives the loop iteration. "bf16" (the dtype axis name, not
    a cast — ``dtype`` still controls precision) leaves the tree
    byte-identical to the pre-quantization layout.

    ``mesh`` (a tensor-parallel serving mesh) places each layer on its
    shards as soon as it is built (sharding.param_placer): same values
    as the unplaced tree, but the whole model never sits on one
    device."""
    if weight_dtype not in ("bf16", "int8"):
        raise ValueError(f"weight_dtype must be bf16|int8: {weight_dtype!r}")
    k_embed, k_layers, k_head = jax.random.split(key, 3)

    # lazy: sharding imports this module
    from kubeinfer_tpu.inference.sharding import param_placer

    put = param_placer(mesh, cfg)

    def dense(k, shape):
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    H, F, V = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    kv_dim = cfg.num_key_value_heads * cfg.head_dim
    q_dim = cfg.num_attention_heads * cfg.head_dim
    # gemma stores norm weights as a zero-init offset from gain 1
    norm_init = jnp.zeros if cfg.rmsnorm_offset else jnp.ones
    layers = []
    for i in range(cfg.num_hidden_layers):
        if cfg.layer_types:
            # Qwen3-Next family: its own key schedule, one key a leaf
            # whichever kind the layer is (benchmarks/reference/
            # qwen3_next.py draws the same)
            if weight_dtype != "bf16":
                raise ValueError(
                    "int8 weights are not implemented for models with "
                    "linear-attention layers and routed experts"
                )
            layers.append(put("layer", _init_hybrid_layer(
                cfg, jax.random.fold_in(k_layers, i), i, dtype)))
            continue
        ks = jax.random.split(jax.random.fold_in(k_layers, i), 7)
        layer = {
            "input_layernorm": norm_init((H,), dtype),
            "post_attention_layernorm": norm_init((H,), dtype),
            # weights stored [in, out] (transposed vs torch Linear) so
            # the forward is x @ W with no per-call transpose
            # q/o are [H, heads*head_dim] RECTANGLES when head_dim is
            # overridden (gemma-7b); square for every derived-head family
            "q_proj": dense(ks[0], (H, q_dim)),
            "k_proj": dense(ks[1], (H, kv_dim)),
            "v_proj": dense(ks[2], (H, kv_dim)),
            "o_proj": dense(ks[3], (q_dim, H)),
        }
        if cfg.num_local_experts > 0:  # Mixtral family: routed MLP
            from kubeinfer_tpu.inference.moe import init_moe_params

            layer["moe"] = init_moe_params(
                jax.random.fold_in(ks[4], 1), H, F,
                cfg.num_local_experts, dtype=dtype,
            )
        else:
            layer["gate_proj"] = dense(ks[4], (H, F))
            layer["up_proj"] = dense(ks[5], (H, F))
            layer["down_proj"] = dense(ks[6], (F, H))
        if cfg.qkv_bias:  # Qwen2 family
            layer["q_bias"] = jnp.zeros((q_dim,), dtype)
            layer["k_bias"] = jnp.zeros((kv_dim,), dtype)
            layer["v_bias"] = jnp.zeros((kv_dim,), dtype)
        if weight_dtype == "int8":
            layer = quantize_layer(layer)
        layers.append(put("layer", layer))
    params: Params = {
        "embed_tokens": put("embed_tokens", dense(k_embed, (V, H))),
        "layers": layers,
        "norm": put("norm", norm_init((H,), dtype)),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = put("lm_head", dense(k_head, (H, V)))
    return params


HYBRID_KEYS = 16  # keys drawn for a Qwen3-Next layer, by leaf below


def _init_hybrid_layer(cfg: ModelConfig, key, i: int, dtype) -> dict:
    """One Qwen3-Next layer: a Gated DeltaNet or a gated full-attention
    mixer, then routed experts with a shared one. Key j of the layer's
    split makes the same leaf in every layer, so the mixer's kind
    changes which keys are used and never what another leaf draws."""
    from kubeinfer_tpu.inference.gdn import init_gdn_params

    ks = jax.random.split(key, HYBRID_KEYS)
    H, D = cfg.hidden_size, cfg.head_dim
    n_q, n_kv = cfg.num_attention_heads, cfg.num_key_value_heads
    E, F = cfg.num_local_experts, cfg.expert_width
    Fs = cfg.shared_expert_intermediate_size

    def dense(k, shape):
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    layer = {
        "input_layernorm": jnp.zeros((H,), dtype),
        "post_attention_layernorm": jnp.zeros((H,), dtype),
        "moe": {
            "router": dense(ks[8], (H, cfg.router_width)),
            "gate_proj": dense(ks[9], (E, H, F)),
            "up_proj": dense(ks[10], (E, H, F)),
            "down_proj": dense(ks[11], (E, F, H)),
            "shared_gate_proj": dense(ks[12], (H, Fs)),
            "shared_up_proj": dense(ks[13], (H, Fs)),
            "shared_down_proj": dense(ks[14], (Fs, H)),
            "shared_expert_gate": dense(ks[15], (H, 1)),
        },
    }
    if cfg.layer_is_linear(i):
        layer["linear_attn"] = init_gdn_params(ks[4:8], cfg, dtype)
    else:
        # q_proj packs [query | gate] per head
        layer["q_proj"] = dense(ks[0], (H, n_q * 2 * D))
        layer["k_proj"] = dense(ks[1], (H, n_kv * D))
        layer["v_proj"] = dense(ks[2], (H, n_kv * D))
        layer["o_proj"] = dense(ks[3], (n_q * D, H))
        layer["q_norm"] = jnp.zeros((D,), dtype)
        layer["k_norm"] = jnp.zeros((D,), dtype)
    return layer


def layer_param_template(cfg: ModelConfig) -> dict:
    """Structure-only pytree of ONE decoder layer (None leaves).

    The single source of truth for which keys a layer carries per config
    (dense vs moe mlp, qkv biases); spec builders that cannot afford to
    materialize real params (pipeline.py's stage specs — a mixtral-8x7b
    init is tens of GB) tree.map over this instead of hardcoding key
    lists, which silently breaks when a family adds keys (r2 review
    finding: pp crashed on moe/bias layers).
    """
    layer: dict = {
        "input_layernorm": None,
        "post_attention_layernorm": None,
        "q_proj": None,
        "k_proj": None,
        "v_proj": None,
        "o_proj": None,
    }
    if cfg.num_local_experts > 0:
        layer["moe"] = {
            "router": None,
            "gate_proj": None,
            "up_proj": None,
            "down_proj": None,
        }
    else:
        layer["gate_proj"] = None
        layer["up_proj"] = None
        layer["down_proj"] = None
    if cfg.qkv_bias:
        layer["q_bias"] = None
        layer["k_bias"] = None
        layer["v_bias"] = None
    return layer


# --- building blocks -------------------------------------------------------


def rms_norm(
    x: jax.Array, weight: jax.Array, eps: float, offset: bool = False
) -> jax.Array:
    """RMSNorm with f32 statistics regardless of activation dtype.

    ``offset`` selects the Gemma convention: the stored weight is a
    zero-init delta and the gain is (1 + w) — folding it into the weight
    at load time would silently corrupt checkpoints saved back out, so
    the convention is applied at compute time.
    """
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    if offset:
        w = 1.0 + w
    return ((xf * scale) * w).astype(x.dtype)


def _mlp_act(cfg: ModelConfig):
    """The gated-MLP activation for this family: llama/qwen2/mixtral use
    SwiGLU (silu); gemma uses the tanh-approximate GeGLU
    ("gelu_pytorch_tanh" — exactly jax.nn.gelu(approximate=True))."""
    if cfg.hidden_act == "silu":
        return jax.nn.silu
    if cfg.hidden_act == "gelu_pytorch_tanh":
        return lambda x: jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unsupported hidden_act {cfg.hidden_act!r}")


def rope_tables(
    positions: jax.Array, head_dim: int, theta: float
) -> tuple[jax.Array, jax.Array]:
    """(cos, sin) tables for rotary embeddings at given positions [B, T]."""
    inv_freq = 1.0 / (
        theta
        ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    angles = positions[..., None].astype(jnp.float32) * inv_freq  # [B,T,D/2]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate [B, T, heads, head_dim] by position tables [B, T, head_dim/2].

    HF llama convention: the head dim is split into halves (x1 = first
    half, x2 = second half), not interleaved pairs.
    """
    rot = 2 * cos.shape[-1]
    if rot < x.shape[-1]:
        # partial rotary (Qwen3-Next): the head's first ``rot`` dims
        # turn, the rest pass
        return jnp.concatenate(
            [apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :].astype(x.dtype)
    s = sin[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(
    q: jax.Array,  # [B, T, n_heads, D]
    k: jax.Array,  # [B, S, n_kv, D]
    v: jax.Array,  # [B, S, n_kv, D]
    mask: jax.Array,  # bool[B, T, S] True = attend
) -> jax.Array:
    """GQA scaled-dot-product attention, f32 softmax, [B, T, n_heads, D]."""
    B, T, n_heads, D = q.shape
    n_kv = k.shape[2]
    group = n_heads // n_kv
    # fold heads into kv groups: [B, T, n_kv, group, D]
    qg = q.reshape(B, T, n_kv, group, D)
    scores = jnp.einsum(
        "btkgd,bskd->bkgts", qg, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(D))
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, v)
    return out.reshape(B, T, n_heads, D)


def decoder_layer(
    layer: Params,
    x: jax.Array,  # [B, T, H]
    cos: jax.Array,
    sin: jax.Array,
    mask: jax.Array,
    cfg: ModelConfig,
    kv_cache: tuple[jax.Array, jax.Array] | None = None,
    cache_offset: jax.Array | int = 0,
    attn_fn=attention,
    tp_axis: str | None = None,
    tp_size: int = 1,
    block_tables: jax.Array | None = None,  # i32[B, max_blocks] paged write
    wq_gspmd: bool = False,
    valid_len: jax.Array | None = None,  # i32[B] real tokens of each row
    moe_stats: list | None = None,  # routed layers append their counters
) -> tuple[jax.Array, tuple[jax.Array, jax.Array] | None]:
    """One pre-norm block; returns (x, updated kv cache or None).

    What a layer caches follows its kind: a
    full-attention layer's ``kv_cache`` is the (k, v) pair described
    below; a linear-attention layer's is its (state, convolution tail)
    pair, which ``valid_len`` keeps padding and idle rows out of
    (stepper.SlotState says which layer holds what).

    Projection matmuls route through weight_quant.wq_dot so a layer
    whose leaves are quantized dicts rides the fused dequant-matmul;
    plain leaves take the literal ``x @ w`` (identical trace to the
    pre-quantization engine). ``wq_gspmd`` pins the dense dequant route
    under GSPMD sharding — the same custom-call constraint as the
    attention kernels.

    ``block_tables`` switches the cache write to the paged layout: the
    cache operands are then the POOL tensors [num_blocks, n_kv,
    block_size, D] (head-major pages, kv_blocks' page layout) shared
    across rows, and row b's token at logical position
    ``cache_offset[b]`` lands in block ``block_tables[b, off // bs]``
    at slot ``off % bs`` of each head (kv_blocks.write_tokens).
    Decode-only (T == 1 with per-row offsets) —
    prefill into the pool goes through the engine's gather/scatter
    admit step, not through here. The paired ``attn_fn`` must read the
    pool through the same tables (batching wires
    decode_attention_blocks_auto).

    ``tp_axis``/``tp_size`` run the block in MANUAL tensor parallelism
    (inside a shard_map with Megatron-sharded weights,
    sharding.param_specs): projections arrive column-sharded so this
    device computes heads/tp_size attention heads and F/tp_size mlp
    lanes, and the two row-parallel contractions (o_proj, down_proj /
    moe down) psum over ``tp_axis``. The GSPMD path (jit + sharded
    params) needs none of this — the compiler inserts the same psums —
    but shard_map bodies (the sequence-parallel ring) see local shards
    and must say the collectives out loud.
    """
    B, T, H = x.shape
    D = cfg.head_dim
    n_q = cfg.num_attention_heads // tp_size
    n_kv = cfg.num_key_value_heads // tp_size
    h = rms_norm(
        x, layer["input_layernorm"], cfg.rms_norm_eps,
        offset=cfg.rmsnorm_offset,
    )
    if "linear_attn" in layer:  # static: pytree structure
        from kubeinfer_tpu.inference.gdn import gdn_mixer, init_gdn_state

        if valid_len is None:
            valid_len = jnp.full((B,), T, jnp.int32)
        state, tail = kv_cache if kv_cache is not None else \
            init_gdn_state(cfg, B, x.dtype)
        out, state, tail = gdn_mixer(
            layer["linear_attn"], h, cfg, state, tail, valid_len)
        if kv_cache is not None:
            kv_cache = (state, tail)
        return _mlp_residual(layer, x + out, cfg, tp_axis, wq_gspmd,
                             valid_len, moe_stats), kv_cache
    q = wq_dot(h, layer["q_proj"], gspmd=wq_gspmd)
    k = wq_dot(h, layer["k_proj"], gspmd=wq_gspmd)
    v = wq_dot(h, layer["v_proj"], gspmd=wq_gspmd)
    if cfg.qkv_bias:  # Qwen2 family; o_proj stays bias-free
        q = q + layer["q_bias"]
        k = k + layer["k_bias"]
        v = v + layer["v_bias"]
    gate = None
    if cfg.attn_output_gate:  # q_proj packs [query | gate] per head
        q, gate = jnp.split(q.reshape(B, T, n_q, 2 * D), 2, axis=-1)
    q = q.reshape(B, T, n_q, D)
    k = k.reshape(B, T, n_kv, D)
    v = v.reshape(B, T, n_kv, D)
    if cfg.qk_norm:  # over each head's width, before the rotation
        q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps,
                     offset=cfg.rmsnorm_offset)
        k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps,
                     offset=cfg.rmsnorm_offset)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if kv_cache is not None:
        ck, cv = kv_cache
        if block_tables is not None:
            if getattr(cache_offset, "ndim", 0) != 1:
                raise ValueError(
                    "block_tables requires per-row cache_offset "
                    "(prefill writes go through the engine's paged "
                    "admit, not decoder_layer)"
                )
            # row b writes its T tokens at contiguous logical positions
            # cache_offset[b] + t (T == 1 a decode step, T > 1 the
            # speculative verify window)
            rows = jnp.arange(block_tables.shape[0])[:, None]
            pos = cache_offset[:, None] + jnp.arange(T)
            if isinstance(ck, tuple):
                # quantized pool: the cache entry is (int8 pages,
                # scales, bf16 tails). Fresh K/V lands in the per-slot
                # TAIL, never the pool — quantize-on-commit happens at
                # the window boundary (stepper._commit_full_tails), so
                # a partial block never round-trips through int8. Tail
                # slot rel = pos//bs - offset//bs is 0 or 1: the tail
                # was pinned to offset // bs at window start and the
                # window writes at most T <= k + 1 < block_size
                # positions, so one boundary crossing max. Inactive
                # rows scribble into their OWN tail slots — harmless,
                # (re)admit rewrites them.
                kq, ks, ktail = ck
                vq, vs, vtail = cv
                bs = page_dims(kq)[0]
                lead = (rows, pos // bs - (cache_offset // bs)[:, None])
                # repack and fall through: the quantized attn_fn
                # unpacks the triple, and the epilogue below is
                # dtype-agnostic
                ck = (kq, ks, write_tokens(ktail, lead, pos % bs, k))
                cv = (vq, vs, write_tokens(vtail, lead, pos % bs, v))
            else:
                # paged write: one batched scatter of the tokens' n_kv
                # rows into the (donated) pool, in place: nothing else
                # here has the pool's shape. Within a live row the
                # (block, slot) pairs are distinct. Rows of a retired
                # slot carry an all-null table, so their write lands in
                # the sacrificial block 0 — duplicate indices there
                # make block 0's content nondeterministic, which is
                # fine because nothing ever attends to it.
                bs = page_dims(ck)[0]
                lead = (block_tables[rows, pos // bs],)
                ck = write_tokens(ck, lead, pos % bs, k)
                cv = write_tokens(cv, lead, pos % bs, v)
        elif getattr(cache_offset, "ndim", 0) == 1:
            # per-row offsets (continuous-batching / ragged decode:
            # rows at different sequence positions in one dispatch)
            if T == 1:
                # decode writes one token per row: a batched scatter
                # lowers to a single fused scatter instead of the
                # vmapped DUS's per-row gather/update chain — same
                # values, so the vmap branch's exactness tests cover it
                rows = jnp.arange(ck.shape[0])
                ck = ck.at[rows, cache_offset].set(k[:, 0])
                cv = cv.at[rows, cache_offset].set(v[:, 0])
            else:

                def row_update(cache, new):
                    return jax.vmap(
                        lambda c, n, o: jax.lax.dynamic_update_slice(
                            c, n, (o, 0, 0)
                        )
                    )(cache, new, cache_offset)

                ck = row_update(ck, k)
                cv = row_update(cv, v)
        else:
            ck = jax.lax.dynamic_update_slice(ck, k, (0, cache_offset, 0, 0))
            cv = jax.lax.dynamic_update_slice(cv, v, (0, cache_offset, 0, 0))
        k, v = ck, cv
        kv_cache = (ck, cv)

    attn = attn_fn(q, k, v, mask)
    if gate is not None:
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
            attn.dtype)
    attn_out = wq_dot(
        attn.reshape(B, T, n_q * D), layer["o_proj"], gspmd=wq_gspmd
    )
    if tp_axis is not None:
        # row-parallel epilogue: each device contracted its own heads
        attn_out = jax.lax.psum(attn_out, tp_axis)
    x = x + attn_out
    return _mlp_residual(layer, x, cfg, tp_axis, wq_gspmd, valid_len,
                         moe_stats), kv_cache


def _mlp_residual(layer, x, cfg, tp_axis, wq_gspmd, valid_len, moe_stats):
    """``x + mlp(norm(x))``: the half of a block every kind of layer
    shares, dense or routed."""
    h = rms_norm(
        x, layer["post_attention_layernorm"], cfg.rms_norm_eps,
        offset=cfg.rmsnorm_offset,
    )
    if "moe" in layer:  # routed experts (static: pytree structure)
        from kubeinfer_tpu.inference.moe import moe_forward

        m, stats = moe_forward(
            layer["moe"], h, cfg.num_experts_per_tok,
            expert_offset=cfg.expert_offset, act=_mlp_act(cfg),
            valid=None if valid_len is None else (
                jnp.arange(x.shape[1])[None, :] < valid_len[:, None]),
            gspmd=wq_gspmd or tp_axis is not None,
        )
        if moe_stats is not None:
            moe_stats.append(stats)
        if tp_axis is not None:
            # experts shard like the dense mlp (param_specs): each
            # device holds every expert's F/tp lanes; the router sees
            # replicated h, so gating is identical across devices and
            # one psum after the expert-weighted sum completes the
            # row-parallel down contraction
            m = jax.lax.psum(m, tp_axis)
        x = x + m
    else:
        gate = _mlp_act(cfg)(wq_dot(h, layer["gate_proj"], gspmd=wq_gspmd))
        mlp = wq_dot(
            gate * wq_dot(h, layer["up_proj"], gspmd=wq_gspmd),
            layer["down_proj"], gspmd=wq_gspmd,
        )
        if tp_axis is not None:
            mlp = jax.lax.psum(mlp, tp_axis)
        x = x + mlp
    return x


# --- full forward ----------------------------------------------------------


def causal_mask(T: int, dtype=bool) -> jax.Array:
    return jnp.tril(jnp.ones((T, T), dtype))


def forward(
    params: Params,
    tokens: jax.Array,  # i32[B, T]
    cfg: ModelConfig,
    positions: jax.Array | None = None,  # i32[B, T]; default arange
    attn_mask: jax.Array | None = None,  # bool[B, T, S]
    kv_caches: list[tuple[jax.Array, jax.Array]] | None = None,
    cache_offset: jax.Array | int = 0,
    attn_fn=None,
    tp_axis: str | None = None,
    tp_size: int = 1,
    return_hidden: bool = False,
    block_tables: jax.Array | None = None,  # i32[B, max_blocks] paged write
    wq_gspmd: bool = False,
    valid_len: jax.Array | None = None,  # i32[B] real tokens of each row
    moe_stats: list | None = None,  # routed layers append their counters
) -> tuple[jax.Array, list | None]:
    """Logits [B, T, V] (+ updated KV caches when provided).

    ``kv_caches`` holds one entry per layer, of the layer's own kind: a
    linear-attention layer's is its (state, convolution tail). Rows
    whose tokens are not all real (bucket padding, a slot that is not
    decoding) say so in ``valid_len``, which such layers and the routed
    experts honour; ``moe_stats`` collects each routed layer's counters
    (moe.STATS).

    ``return_hidden=True`` returns the post-norm hidden states [B, T, H]
    instead of logits — prefill consumes logits at ONE position per row,
    so chunked_prefill selects the hidden row first and pays the
    full-vocab head matmul once per prompt instead of once per chunk
    token (~20% of prefill FLOPs on a 32k-vocab model, plus the [C, V]
    f32 materialization per chunk).

    ``tp_axis``/``tp_size``: manual tensor parallelism for shard_map
    bodies (see decoder_layer). The returned logits are then
    vocab-sharded [B, T, V/tp] when the model has a separate ``lm_head``
    (column-parallel per sharding.param_specs) and full-width when
    embeddings are tied (embed_tokens is replicated) — the caller's
    out_specs must match.

    ``attn_fn=None`` (the default) means auto: the plain causal no-cache
    path derives its mask in-kernel on TPU (causal_attention_auto);
    every other path gets the dense ``attention``. Pass a callable to
    pin a specific implementation.

    Without caches: plain causal self-attention over T (prefill/training).
    With caches: keys/values are written at ``cache_offset`` and attention
    runs over the full cache length (decode); ``attn_mask`` must then mask
    cache positions ≥ the true length.
    """
    B, T = tokens.shape
    if positions is None:
        base = jnp.arange(T, dtype=jnp.int32)[None, :]
        if getattr(cache_offset, "ndim", 0) == 1:
            positions = base + cache_offset[:, None]  # per-row offsets
        else:
            positions = jnp.broadcast_to(base + cache_offset, (B, T))
    if attn_mask is None:
        if kv_caches is not None:
            raise ValueError("decode with kv_caches requires attn_mask")
        attn_mask = jnp.broadcast_to(causal_mask(T)[None], (B, T, T))
        if attn_fn is None:
            # default causal forward (training / full-sequence prefill):
            # derive the mask in-kernel on TPU instead of shipping the
            # [B, T, T] tensor; the dense mask above survives only as
            # the fallback operand (DCE'd when the kernel path runs).
            # Callers that must stay on the dense einsum (e.g. GSPMD-
            # sharded jits, where a Pallas custom call cannot partition)
            # pass attn_fn=attention explicitly. Lazy import:
            # flash_attention imports this module.
            from kubeinfer_tpu.inference.flash_attention import (
                causal_attention_auto,
            )

            attn_fn = causal_attention_auto
    if attn_fn is None:
        attn_fn = attention

    cos, sin = rope_tables(positions, cfg.rotary_dim, cfg.rope_theta)
    x = params["embed_tokens"][tokens]
    if cfg.scale_embeddings:
        # Gemma scales embeddings into the residual stream; the HF
        # reference casts the sqrt(H) normalizer to the activation dtype
        # BEFORE multiplying — mirrored for checkpoint-level parity
        x = x * jnp.asarray(
            float(cfg.hidden_size) ** 0.5, x.dtype
        )
    new_caches = [] if kv_caches is not None else None
    for i, layer in enumerate(params["layers"]):
        cache = kv_caches[i] if kv_caches is not None else None
        x, cache = decoder_layer(
            layer, x, cos, sin, attn_mask, cfg,
            kv_cache=cache, cache_offset=cache_offset, attn_fn=attn_fn,
            tp_axis=tp_axis, tp_size=tp_size, block_tables=block_tables,
            wq_gspmd=wq_gspmd, valid_len=valid_len, moe_stats=moe_stats,
        )
        if new_caches is not None:
            new_caches.append(cache)
    x = rms_norm(
        x, params["norm"], cfg.rms_norm_eps, offset=cfg.rmsnorm_offset
    )
    if return_hidden:
        return x, new_caches
    logits = (x @ lm_head_matrix(params, cfg)).astype(jnp.float32)
    return logits, new_caches


def lm_head_matrix(params: Params, cfg: ModelConfig) -> jax.Array:
    """The [H, V] output projection (tied or separate) — one home for
    the tie_word_embeddings branch so late head application
    (chunked_prefill) cannot drift from forward's."""
    return (
        params["embed_tokens"].T
        if cfg.tie_word_embeddings
        else params["lm_head"]
    )


@partial(jax.jit, static_argnames=("cfg",))
def forward_jit(params: Params, tokens: jax.Array, cfg: ModelConfig):
    """Jitted no-cache forward (training/prefill compile target)."""
    return forward(params, tokens, cfg)[0]
