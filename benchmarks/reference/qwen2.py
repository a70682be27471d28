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

The server answers with token ids only, so the benchmark cannot hold
its logits against this on the chip yet (PERF.md, Open questions);
``tests/test_reference.py`` holds the program's forward to it at a
small size.
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


def forward(params: dict, tokens, conf: dict):
    """Logits f32[T, V] of one sequence ``tokens`` i32[T]. ``conf`` is
    the configuration file's dict (the source's config.json keys)."""
    n_q = conf["num_attention_heads"]
    n_kv = conf.get("num_key_value_heads", n_q)
    D = conf.get("head_dim") or conf["hidden_size"] // n_q
    eps, theta = conf["rms_norm_eps"], conf["rope_theta"]
    T = tokens.shape[0]
    causal = jnp.tril(jnp.ones((T, T), bool))
    with jax.default_matmul_precision("highest"):
        h = _w(params["embed_tokens"])[tokens]
        for lp in params["layers"]:
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
            h = h + (jax.nn.silu(x @ _w(lp["gate_proj"]))
                     * (x @ _w(lp["up_proj"]))) @ _w(lp["down_proj"])
        h = _rmsnorm(h, params["norm"], eps)
        head = params.get("lm_head")
        head = _w(head) if head is not None else _w(
            params["embed_tokens"]).T
        return h @ head
