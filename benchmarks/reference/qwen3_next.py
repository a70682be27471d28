"""The plain reference of the Qwen3-Next decoder as one expert-parallel
rank serves it: float32 ``jax.numpy`` with no kernels, no cache, no
chunking and no batching, written from the published layer equations
(the Qwen3-Next model card and the HuggingFace ``Qwen3NextForCausalLM``
layers), independent of ``kubeinfer_tpu``.

Layer i of L is full attention when (i + 1) % full_attention_interval
is 0, else Gated DeltaNet. Every layer: h += mixer(norm(h)); h +=
experts(norm(h)), norms zero-centred (x^ (1 + w)).

  full attention   q_proj packs [query | gate] per head; zero-centred
                   RMS norm over each q and k head; rotate-half rotary
                   on the head's first partial_rotary_factor share;
                   causal softmax; out = o_proj(attn * sigmoid(gate))
  Gated DeltaNet   in_proj_qkvz per key head [q | k | v v | z z],
                   in_proj_ba per key head [b b | a a]; depthwise
                   causal convolution (4 taps) over concat(q, k, v),
                   SiLU; q, k L2-normalised, q scaled by dk^-0.5;
                   beta = sigmoid(b), g = -exp(A_log) softplus(a +
                   dt_bias); per value head, token by token, from S = 0:
                       S <- exp(g) S;  d = beta (v - S^T k)
                       S <- S + k d^T;  o = S^T q
                   out = out_proj(rmsnorm(o) w silu(z))
  experts          softmax over all scored experts, top k, renormalised;
                   the held experts' SwiGLUs, pairs of experts held
                   elsewhere dropped; plus sigmoid(shared_expert_gate x)
                   times the shared expert

The share: ``num_experts`` of the file counts the experts HELD
(``expert_parallel`` {"size", "rank"} says which), ``vocab_size`` the
vocabulary slice. ``make_layer`` draws both mixers' weights for every
layer, so that one compiled program makes and runs each
(``reference/compare.py`` calls it with a traced index); the leaf a
layer does not use costs a draw and nothing else.

``weight_dtype`` names the controls too, the same weights served
wrongly. Lower precisions: "int8" and "int4" put every projection of
the mixers and the experts (the router, the convolution's taps, the
norms and the decay's vectors stay as they are, as a quantised server
keeps them) through symmetric absmax codes, one scale a column;
"state_bf16" rounds the recurrent state to bfloat16 after every token.
A structural fault: "no_decay" leaves the first layer's decay out
(g = 0). The cell's limits have to fail the lower weight precision
(PERF.md, section 2, has every control's readings); the state's
precision moves no served token, so it is held by its bytes
(checks/recurrent_state.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
KEYS = 16  # one key a leaf, the same leaf in every layer


def _w(leaf):
    return leaf.astype(F32)


def _norm(x, w, eps):
    """Zero-centred RMS norm: the stored weight is an offset from 1."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + _w(w))


def _share(conf: dict):
    ep = conf.get("expert_parallel") or {}
    held = conf["num_experts"]
    return held, held * ep.get("size", 1), held * ep.get("rank", 0)


def _rope(x, theta, rot):
    """x [T, heads, D]: rotate-half on the first ``rot`` dims."""
    T = x.shape[0]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=F32) / rot)
    ang = jnp.arange(T, dtype=F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _full_attention(x, lp, conf):
    n_q, n_kv, D = (conf["num_attention_heads"],
                    conf["num_key_value_heads"], conf["head_dim"])
    eps, T = conf["rms_norm_eps"], x.shape[0]
    qg = (x @ _w(lp["q_proj"])).reshape(T, n_q, 2 * D)
    q, gate = qg[..., :D], qg[..., D:]
    k = (x @ _w(lp["k_proj"])).reshape(T, n_kv, D)
    v = (x @ _w(lp["v_proj"])).reshape(T, n_kv, D)
    rot = int(D * conf["partial_rotary_factor"])
    q = _rope(_norm(q, lp["q_norm"], eps), conf["rope_theta"], rot)
    k = _rope(_norm(k, lp["k_norm"], eps), conf["rope_theta"], rot)
    k = jnp.repeat(k, n_q // n_kv, axis=1)
    v = jnp.repeat(v, n_q // n_kv, axis=1)
    s = jnp.einsum("thd,shd->hts", q, k) * D ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    a = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), v)
    a = a * jax.nn.sigmoid(gate)
    return a.reshape(T, n_q * D) @ _w(lp["o_proj"])


def _gated_delta_net(x, lp, conf):
    nk, nv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    K, r, T = conf["linear_conv_kernel_dim"], nv // nk, x.shape[0]
    qkvz = (x @ _w(lp["in_proj_qkvz"])).reshape(T, nk, 2 * dk + 2 * r * dv)
    ba = (x @ _w(lp["in_proj_ba"])).reshape(T, nk, 2 * r)
    q, k = qkvz[..., :dk], qkvz[..., dk:2 * dk]
    v = qkvz[..., 2 * dk:2 * dk + r * dv]
    z = qkvz[..., 2 * dk + r * dv:].reshape(T, nv, dv)
    b, a = ba[..., :r].reshape(T, nv), ba[..., r:].reshape(T, nv)

    mixed = jnp.concatenate([q.reshape(T, -1), k.reshape(T, -1),
                             v.reshape(T, -1)], -1)
    past = jnp.concatenate([jnp.zeros((K - 1, mixed.shape[1]), F32), mixed])
    taps = _w(lp["conv1d"])  # [tap, channel]; tap K-1 is the token itself
    conv = jax.nn.silu(sum(taps[j] * past[j:j + T] for j in range(K)))
    q = conv[:, :nk * dk].reshape(T, nk, dk)
    k = conv[:, nk * dk:2 * nk * dk].reshape(T, nk, dk)
    v = conv[:, 2 * nk * dk:].reshape(T, nv, dv)

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(unit(q) * dk ** -0.5, r, axis=1)
    k = jnp.repeat(unit(k), r, axis=1)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(_w(lp["A_log"])) * jax.nn.softplus(a + _w(lp["dt_bias"]))
    g = jnp.where(lp["no_decay"], 0.0, g)  # a control

    def token(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[:, None, None]
        d = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", S, kt))
        S = S + kt[:, :, None] * d[:, None, :]
        o = jnp.einsum("hkv,hk->hv", S, qt)
        # a control: a state that is kept in bfloat16. reduce_precision
        # and not a cast there and back, which the TPU compiler takes
        # out (it may keep excess precision) so that nothing is rounded
        S = jnp.where(lp["state_bf16"],
                      jax.lax.reduce_precision(S, 8, 7), S)
        return S, o

    _, o = jax.lax.scan(token, jnp.zeros((nv, dk, dv), F32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + conf["rms_norm_eps"])
    o = o * _w(lp["gdn_norm"]) * jax.nn.silu(z)
    return o.reshape(T, nv * dv) @ _w(lp["out_proj"])


def _experts(x, lp, conf):
    held, scored, first = _share(conf)
    top = conf["num_experts_per_tok"]
    probs = jax.nn.softmax(x @ _w(lp["router"]), -1)  # [T, scored]
    w, idx = jax.lax.top_k(probs, top)
    w = w / jnp.sum(w, -1, keepdims=True)
    # each token's weight on every held expert, 0 where it chose none
    on = jnp.sum(w[..., None] * (idx[..., None] == first
                                 + jnp.arange(held)), axis=1)  # [T, held]
    up = jnp.einsum("th,ehf->tef", x, _w(lp["experts_up"]))
    act = jax.nn.silu(jnp.einsum("th,ehf->tef", x, _w(lp["experts_gate"])))
    y = jnp.einsum("tef,efh,te->th", act * up, _w(lp["experts_down"]), on)
    shared = (jax.nn.silu(x @ _w(lp["shared_gate"]))
              * (x @ _w(lp["shared_up"]))) @ _w(lp["shared_down"])
    return y + jax.nn.sigmoid(x @ _w(lp["shared_expert_gate"])) * shared


def layer(h, lp: dict, conf: dict):
    """One decoder layer on one sequence: ``h`` f32[T, H] in and out.
    Call it under ``jax.default_matmul_precision("highest")``."""
    eps = conf["rms_norm_eps"]
    x = _norm(h, lp["input_layernorm"], eps)
    h = h + jax.lax.cond(
        lp["is_full"], lambda: _full_attention(x, lp, conf),
        lambda: _gated_delta_net(x, lp, conf))
    return h + _experts(_norm(h, lp["post_attention_layernorm"], eps),
                        lp, conf)


def embed(params: dict, tokens):
    return _w(params["embed_tokens"])[tokens]


def logits(params: dict, h, conf: dict):
    """Final norm and head on the rows ``h`` f32[N, H]."""
    return _norm(h, params["norm"], conf["rms_norm_eps"]) \
        @ _w(params["lm_head"])


def forward(params: dict, tokens, conf: dict):
    """Logits f32[T, V] of one sequence ``tokens`` i32[T]."""
    with jax.default_matmul_precision("highest"):
        h = embed(params, tokens)
        for lp in params["layers"]:
            h = layer(h, lp, conf)
        return logits(params, h, conf)


# --- the served weights, made here from the seed and from nothing of the
# program's: the draw the configuration's ``assumed.weights`` names


def _dense(key, shape, std=0.02):
    return (std * jax.random.normal(key, shape, F32)).astype(jnp.bfloat16)


LEVELS = {"int8": 127.0, "int4": 7.0}
QUANTISED = ("q_proj", "k_proj", "v_proj", "o_proj", "in_proj_qkvz",
             "in_proj_ba", "out_proj", "experts_gate", "experts_up",
             "experts_down", "shared_gate", "shared_up", "shared_down")


def quantise(w, weight_dtype: str):
    """``w [..., K, N]`` as a lower weight type holds it: symmetric
    absmax codes with one float32 scale a column (and expert), back in
    float32; the served type leaves ``w`` as it is."""
    top = LEVELS.get(weight_dtype)
    if top is None:
        return w
    wf = w.astype(F32)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.where(amax > 0, amax / top, 1.0)
    return jnp.clip(jnp.round(wf / scale), -top, top) * scale


def weight_keys(seed: int):
    """(embedding, layers, head) keys of the draw."""
    return jax.random.split(jax.random.PRNGKey(seed), 3)


def make_layer(k_layers, i, conf: dict, weight_dtype: str) -> dict:
    """Layer ``i``'s weights, both mixers'; ``i`` may be traced."""
    if weight_dtype not in ("bf16", "state_bf16", "no_decay", *LEVELS):
        raise ValueError(f"unknown weight dtype {weight_dtype!r}")
    H, D = conf["hidden_size"], conf["head_dim"]
    n_q, n_kv = conf["num_attention_heads"], conf["num_key_value_heads"]
    nk, nv = conf["linear_num_key_heads"], conf["linear_num_value_heads"]
    dk, dv = conf["linear_key_head_dim"], conf["linear_value_head_dim"]
    held, scored, _ = _share(conf)
    F, Fs = conf["moe_intermediate_size"], \
        conf["shared_expert_intermediate_size"]
    ks = jax.random.split(jax.random.fold_in(k_layers, i), KEYS)
    bf = jnp.bfloat16
    # exp(g) per token at a = 0, even in the log from 0.999 to 0.5
    rates = jnp.exp(jnp.linspace(math.log(1e-3), math.log(math.log(2.0)),
                                 nv, dtype=F32))
    lp = {
        "is_full": (i + 1) % conf["full_attention_interval"] == 0,
        "state_bf16": jnp.asarray(weight_dtype == "state_bf16"),
        "no_decay": (i == 0) & (weight_dtype == "no_decay"),
        "input_layernorm": jnp.zeros((H,), bf),
        "post_attention_layernorm": jnp.zeros((H,), bf),
        "q_proj": _dense(ks[0], (H, n_q * 2 * D)),
        "k_proj": _dense(ks[1], (H, n_kv * D)),
        "v_proj": _dense(ks[2], (H, n_kv * D)),
        "o_proj": _dense(ks[3], (n_q * D, H)),
        "q_norm": jnp.zeros((D,), bf),
        "k_norm": jnp.zeros((D,), bf),
        "in_proj_qkvz": _dense(ks[4], (H, 2 * nk * dk + 2 * nv * dv)),
        "in_proj_ba": _dense(ks[5], (H, 2 * nv)),
        "conv1d": _dense(ks[6], (conf["linear_conv_kernel_dim"],
                                 2 * nk * dk + nv * dv), std=0.5),
        "A_log": jnp.log(rates),
        "dt_bias": jnp.full((nv,), math.log(math.e - 1.0), F32),
        "gdn_norm": jnp.ones((dv,), bf),
        "out_proj": _dense(ks[7], (nv * dv, H)),
        "router": _dense(ks[8], (H, scored)),
        "experts_gate": _dense(ks[9], (held, H, F)),
        "experts_up": _dense(ks[10], (held, H, F)),
        "experts_down": _dense(ks[11], (held, F, H)),
        "shared_gate": _dense(ks[12], (H, Fs)),
        "shared_up": _dense(ks[13], (H, Fs)),
        "shared_down": _dense(ks[14], (Fs, H)),
        "shared_expert_gate": _dense(ks[15], (H, 1)),
    }
    for name in QUANTISED:
        lp[name] = quantise(lp[name], weight_dtype)
    return lp


def make_ends(k_embed, k_head, conf: dict) -> dict:
    """Embedding, final norm and head of the vocabulary slice."""
    H, V = conf["hidden_size"], conf["vocab_size"]
    return {"embed_tokens": _dense(k_embed, (V, H)),
            "norm": jnp.zeros((H,), jnp.bfloat16),
            "lm_head": _dense(k_head, (H, V))}
