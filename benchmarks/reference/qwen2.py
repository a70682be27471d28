"""The plain reference of the qwen2/llama decoder: the forward pass in
straightforward ``jax.numpy`` and float32, with no kernels, no cache
and no batching tricks, written from the published description (the
Qwen2 technical report and the HuggingFace ``Qwen2ForCausalLM`` layer
equations), independent of ``kubeinfer_tpu/inference/model.py``.

    h   = embed[tokens]
    per layer:
      x   = rmsnorm(h, w_in)
      q,k,v = x Wq + bq, x Wk + bk, x Wv + bv      (biases: qwen2 only)
      q,k = rope(q), rope(k)                       (half-split rotation)
      a   = softmax(causal(q k^T / sqrt(D))) v     (each KV head serves
                                                    n_q / n_kv query heads)
      h   = h + a Wo
      x   = rmsnorm(h, w_post)
      h   = h + (silu(x Wg) * (x Wu)) Wd
    logits = rmsnorm(h, w_norm) W_head

Weights are stored [in, out] as the program stores them; quantised
leaves ({"qw", "scale"}) are dequantised first, so the reference holds
the program to the weights it actually serves. On a TPU a float32
matmul runs in lower precision unless the precision is raised, so the
whole pass runs under ``jax.default_matmul_precision("highest")``.

The server answers with token ids only. ``reference/compare.py`` runs
this over the prompts and the tokens a run served, layer by layer on
weights made here from the seed, and reads how far below the
reference's best logit each served token lies; ``tests/test_reference.py``
holds the program's forward to it at a small size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _w(leaf):
    if isinstance(leaf, dict):  # int8 codes, one f32 scale per column
        return leaf["qw"].astype(jnp.float32) * leaf["scale"].astype(
            jnp.float32)
    return leaf.astype(jnp.float32)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _w(w)


def _rope(x, theta):
    """x: [T, heads, D]; rotate halves by position."""
    T, _, D = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv  # [T, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _dims(conf: dict):
    n_q = conf["num_attention_heads"]
    n_kv = conf.get("num_key_value_heads", n_q)
    D = conf.get("head_dim") or conf["hidden_size"] // n_q
    return n_q, n_kv, D


def layer(h, lp: dict, conf: dict):
    """One decoder layer on one sequence: ``h`` f32[T, H] in and out.
    Call it under ``jax.default_matmul_precision("highest")``."""
    n_q, n_kv, D = _dims(conf)
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    T = h.shape[0]
    causal = jnp.tril(jnp.ones((T, T), bool))
    x = _rmsnorm(h, lp["input_layernorm"], eps)
    q, k, v = (x @ _w(lp[n + "_proj"]) for n in "qkv")
    if "q_bias" in lp:
        q, k, v = (y + _w(lp[n + "_bias"])
                   for y, n in ((q, "q"), (k, "k"), (v, "v")))
    q = _rope(q.reshape(T, n_q, D), theta)
    k = _rope(k.reshape(T, n_kv, D), theta)
    v = v.reshape(T, n_kv, D)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(D))
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    h = h + a.reshape(T, n_q * D) @ _w(lp["o_proj"])
    x = _rmsnorm(h, lp["post_attention_layernorm"], eps)
    return h + (jax.nn.silu(x @ _w(lp["gate_proj"]))
                * (x @ _w(lp["up_proj"]))) @ _w(lp["down_proj"])


def embed(params: dict, tokens):
    return _w(params["embed_tokens"])[tokens]


def logits(params: dict, h, conf: dict):
    """Final norm and head on the rows ``h`` f32[N, H]."""
    h = _rmsnorm(h, params["norm"], conf["rms_norm_eps"])
    head = params.get("lm_head")
    head = _w(head) if head is not None else _w(params["embed_tokens"]).T
    return h @ head


def forward(params: dict, tokens, conf: dict):
    """Logits f32[T, V] of one sequence ``tokens`` i32[T]. ``conf`` is
    the configuration file's dict (the source's config.json keys)."""
    with jax.default_matmul_precision("highest"):
        h = embed(params, tokens)
        for lp in params["layers"]:
            h = layer(h, lp, conf)
        return logits(params, h, conf)


# --- the served weights, made here from the seed and from nothing of
# the program's: the draw the configuration's ``assumed.weights`` names
# (normal, 0.02, one key a leaf, cast to the served type), and the
# configuration's weight type applied to the seven projections

QUANT_TILE = 128  # output columns that share one scale
PROJECTIONS = ("q_proj", "k_proj", "v_proj", "o_proj",
               "gate_proj", "up_proj", "down_proj")
BITS = {"int8": 127.0, "int4": 7.0}


def quantise(w, weight_dtype: str):
    """Symmetric absmax codes, one f32 scale per tile of output columns
    (scale = amax / 127 for int8, / 7 for int4), as a {"qw", "scale"}
    leaf; bf16 leaves ``w`` as it is."""
    if weight_dtype == "bf16":
        return w
    top = BITS[weight_dtype]
    K, N = w.shape
    wf = w.astype(jnp.float32)
    nt = -(-N // QUANT_TILE)
    wp = jnp.pad(wf, ((0, 0), (0, nt * QUANT_TILE - N)))
    amax = jnp.max(jnp.abs(wp.reshape(K, nt, QUANT_TILE)), axis=(0, 2))
    scale = jnp.where(amax > 0, amax / top, 1.0).astype(jnp.float32)
    scol = jnp.repeat(scale, QUANT_TILE)[:N]
    q = jnp.clip(jnp.round(wf / scol[None, :]), -top, top).astype(jnp.int8)
    return {"qw": q, "scale": scol}


def _dense(key, shape):
    return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16)


def weight_keys(seed: int):
    """(embedding, layers, head) keys of the draw."""
    return jax.random.split(jax.random.PRNGKey(seed), 3)


def make_layer(k_layers, i, conf: dict, weight_dtype: str) -> dict:
    """Layer ``i``'s weights; ``i`` may be traced, so one compiled
    program makes every layer."""
    n_q, n_kv, D = _dims(conf)
    H, F = conf["hidden_size"], conf["intermediate_size"]
    shapes = [(H, n_q * D), (H, n_kv * D), (H, n_kv * D), (n_q * D, H),
              (H, F), (H, F), (F, H)]
    ks = jax.random.split(jax.random.fold_in(k_layers, i), 7)
    lp = {name: quantise(_dense(k, shape), weight_dtype)
          for name, k, shape in zip(PROJECTIONS, ks, shapes)}
    lp["input_layernorm"] = jnp.ones((H,), jnp.bfloat16)
    lp["post_attention_layernorm"] = jnp.ones((H,), jnp.bfloat16)
    if conf.get("model_type") == "qwen2":
        for n, (_, out) in zip("qkv", shapes):
            lp[n + "_bias"] = jnp.zeros((out,), jnp.bfloat16)
    return lp


def make_ends(k_embed, k_head, conf: dict) -> dict:
    """Embedding, final norm and head: never quantised."""
    H, V = conf["hidden_size"], conf["vocab_size"]
    out = {"embed_tokens": _dense(k_embed, (V, H)),
           "norm": jnp.ones((H,), jnp.bfloat16)}
    if not conf.get("tie_word_embeddings"):
        out["lm_head"] = _dense(k_head, (H, V))
    return out
