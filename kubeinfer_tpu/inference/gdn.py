"""Gated DeltaNet: the linear-attention mixer of the Qwen3-Next family.

A layer keeps, per request, a float32 state ``S[Hv, Dk, Dv]`` and the
last ``K - 1`` inputs of a short depthwise convolution instead of pages
of keys and values. Per value head and token ``t`` (Yang et al., "Gated
Delta Networks", 2024):

    S <- exp(g_t) * S
    d  = beta_t * (v_t - S^T k_t)
    S <- S + k_t d^T
    o_t = S^T q_t

Three forms of the same recurrence live here: the token-by-token scan
(:func:`gdn_recurrence`, the oracle and the CPU branch of decode), the
chunked scan prefill runs (:func:`gdn_chunk_scan`, the within-chunk
triangular solve of the delta rule) and the one-token Pallas step
(:func:`gdn_decode_step`) that updates the state of the decoding slots
in place and touches no other. A token with ``g = 0`` and ``beta = 0``
leaves the state exactly as it was, which is how bucket padding and, in
the scans, rows that are not decoding pass through (:func:`gdn_mixer`
sets both from ``valid_len``).

The state stays float32 everywhere: in bfloat16 it is a different model
(benchmarks/reference/qwen3_next.py has that as its control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeinfer_tpu.inference.config import ModelConfig

CHUNK = 64  # tokens solved together in prefill
_HI = lax.Precision.HIGHEST
# value heads the decode kernel's one grid step a slot may hold: the
# slot's state in and out, double-buffered, is 256 KB a head of VMEM
_MAX_DECODE_HEADS = 32


# --- the recurrence, three ways --------------------------------------------


def gdn_recurrence(q, k, v, g, beta, state):
    """Token by token. q, k f32[B, T, Hv, Dk]; v f32[B, T, Hv, Dv];
    g, beta f32[B, T, Hv]; state f32[B, Hv, Dk, Dv].
    Returns (o f32[B, T, Hv, Dv], state)."""

    def step(S, xs):
        qt, kt, vt, gt, bt = xs
        S = S * jnp.exp(gt)[..., None, None]
        d = bt[..., None] * (vt - jnp.einsum("bhkv,bhk->bhv", S, kt,
                                             precision=_HI))
        S = S + kt[..., :, None] * d[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, qt, precision=_HI)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
    state, o = lax.scan(step, state, xs)
    return jnp.moveaxis(o, 0, 1), state


def gdn_chunk_scan(q, k, v, g, beta, state, chunk: int = CHUNK):
    """The same recurrence, ``chunk`` tokens at a time (operands as
    :func:`gdn_recurrence`). With G the running sum of g inside a chunk
    and S0 the state it starts from, the writes d solve the unit lower
    triangular system

        d_t + beta_t sum_{s<t} e^{G_t-G_s} (k_t.k_s) d_s
            = beta_t (v_t - e^{G_t} S0^T k_t)

    and then o_t = e^{G_t} S0^T q_t + sum_{s<=t} e^{G_t-G_s} (q_t.k_s) d_s,
    S <- e^{G_C} S0 + sum_s e^{G_C-G_s} k_s d_s^T. Every exponent is of a
    difference that is <= 0. T is padded to whole chunks with tokens
    that touch nothing (g = beta = 0)."""
    B, T, H, _ = q.shape
    Dv = v.shape[-1]
    C = min(chunk, T)
    pad = -T % C
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta)
        )
    n = (T + pad) // C

    def chunks(x):  # [B, n*C, H, ...] -> [n, B, H, C, ...]
        x = x.reshape((B, n, C) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    lower = jnp.tril(jnp.ones((C, C), bool))
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    eye = jnp.eye(C, dtype=jnp.float32)

    def step(S, xs):
        qc, kc, vc, gc, bc = xs  # [B, H, C, D], [B, H, C]
        G = jnp.cumsum(gc, axis=-1)
        decay = jnp.exp(
            jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf)
        )  # [B, H, t, s]: e^{G_t - G_s} for s <= t, else 0
        kk = jnp.einsum("bhtd,bhsd->bhts", kc, kc, precision=_HI)
        A = jnp.where(strict, bc[..., None] * decay * kk, 0.0)
        eG = jnp.exp(G)[..., None]
        rhs = bc[..., None] * (
            vc - eG * jnp.einsum("bhtk,bhkv->bhtv", kc, S, precision=_HI)
        )
        d = _unit_lower_solve(eye + A, rhs)
        qk = jnp.einsum("bhtd,bhsd->bhts", qc, kc, precision=_HI)
        o = eG * jnp.einsum("bhtk,bhkv->bhtv", qc, S, precision=_HI) \
            + jnp.einsum("bhts,bhsv->bhtv", decay * qk, d, precision=_HI)
        to_end = jnp.exp(G[..., -1:] - G)[..., None]  # e^{G_C - G_s}
        S = jnp.exp(G[..., -1])[..., None, None] * S + jnp.einsum(
            "bhsk,bhsv->bhkv", kc * to_end, d, precision=_HI)
        return S, o

    xs = tuple(chunks(x) for x in (q, k, v, g, beta))
    state, o = lax.scan(step, state, xs)
    o = jnp.moveaxis(jnp.moveaxis(o, 2, 3), 0, 1)  # [B, n, C, H, Dv]
    return o.reshape(B, n * C, H, Dv)[:, :T], state


def _unit_lower_solve(L, rhs):
    """x with L x = rhs, L unit lower triangular [..., C, C]: forward
    substitution in float32 on the vector unit, one row a step. (XLA's
    triangular solve on a TPU multiplies in bfloat16 passes, which the
    state does not survive.)"""
    C = L.shape[-1]

    def row(t, x):
        # rows >= t of x are still zero, so the full dot is the sum
        # over s < t; L's own diagonal contributes nothing yet
        Lt = lax.dynamic_index_in_dim(L, t, axis=-2, keepdims=False)
        rt = lax.dynamic_index_in_dim(rhs, t, axis=-2, keepdims=False)
        xt = rt - jnp.sum(Lt[..., :, None] * x, axis=-2)
        return lax.dynamic_update_index_in_dim(x, xt, t, axis=-2)

    return lax.fori_loop(0, C, row, jnp.zeros_like(rhs))


def _decode_block(x_ref, s_ref, s_out, o_out):
    """One slot's value heads (all, or a block of them). x rows per
    head: q, k, beta*v, exp(g) and exp(g)*beta (both spread over the
    lanes)."""
    Dk = s_ref.shape[2]
    ii = lax.broadcasted_iota(jnp.int32, (Dk, Dk), 0)
    jj = lax.broadcasted_iota(jnp.int32, (Dk, Dk), 1)
    eye = ii == jj

    def column(row):  # [1, Dk] along lanes -> [Dk, 1] along sublanes
        return jnp.sum(
            jnp.where(eye, jnp.broadcast_to(row, (Dk, Dk)), 0.0),
            axis=1, keepdims=True)

    for h in range(s_ref.shape[1]):
        x = x_ref[0, h]
        S = s_ref[0, h]
        kcol = column(x[1:2])
        u = jnp.sum(S * kcol, axis=0, keepdims=True)  # S^T k
        d = x[2:3] - x[4:5] * u
        S = x[3:4] * S + kcol * d
        s_out[0, h] = S
        o_out[0, h:h + 1, :] = jnp.sum(
            S * column(x[0:1]), axis=0, keepdims=True)


def _decode_kernel(rows_ref, n_ref, x_ref, s_ref, s_out, o_out):
    """Grid step b: every head of slot ``rows_ref[b]``. Steps past the
    count sit on the last live slot's block, so Pallas fetches and
    writes back nothing more; with no live slot at all the one block
    they sit on is copied through unchanged."""
    b, n = pl.program_id(0), n_ref[0]

    @pl.when(b < n)
    def _():
        _decode_block(x_ref, s_ref, s_out, o_out)

    @pl.when((n == 0) & (b == 0))
    def _():
        s_out[...] = s_ref[...]


def decoding_slots(valid_len):
    """The slots a one-token step updates, from ``valid_len`` i32[B]
    (a row decodes if it is 1): (rows i32[B], count i32[1]), the live
    slots in order at the front, the rest repeating the last of them
    (slot 0 if none is live)."""
    B = valid_len.shape[0]
    live = valid_len > 0
    n = jnp.sum(live, dtype=jnp.int32)
    (rows,) = jnp.nonzero(live, size=B, fill_value=0)
    rows = jnp.where(jnp.arange(B) < n, rows, rows[jnp.maximum(n - 1, 0)])
    return rows.astype(jnp.int32), n[None]


def _step_rows(q, k, v, g, beta):
    """The kernel's x, f32[B, Hv, 8, Dk]: the rows :func:`_decode_block`
    reads, padded to a whole sublane tile."""
    a = jnp.exp(g)
    x = jnp.stack([
        q, k, beta[..., None] * v,
        jnp.broadcast_to(a[..., None], v.shape),
        jnp.broadcast_to((a * beta)[..., None], v.shape),
    ], axis=2)
    return jnp.pad(x, ((0, 0), (0, 0), (0, 3), (0, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(5,))
def gdn_decode_step(q, k, v, g, beta, state, rows, n,
                    interpret: bool = False):
    """One token for the slots ``rows[:n[0]]`` (:func:`decoding_slots`),
    the state updated in place: every other slot's state is not read or
    written, and its o is 0. q, k f32[B, Hv, Dk]; v f32[B, Hv, Dv]; g,
    beta f32[B, Hv]; state f32[B, Hv, Dk, Dv]; rows i32[B]; n i32[1].
    Returns (o f32[B, Hv, Dv], state)."""
    B, H, Dk = q.shape
    Dv = v.shape[-1]
    x = _step_rows(q, k, v, g, beta)

    def slot(b, rows_ref, n_ref):  # past the count: the last live slot
        return rows_ref[b], 0, 0, 0

    state, o = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, 8, Dk), slot),
                pl.BlockSpec((1, H, Dk, Dv), slot),
            ],
            out_specs=[
                pl.BlockSpec((1, H, Dk, Dv), slot),
                pl.BlockSpec((1, H, Dv), lambda b, rows_ref, n_ref: (
                    rows_ref[b], 0, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, jnp.float32),
            jax.ShapeDtypeStruct((B, H, Dv), jnp.float32),
        ],
        # operands: rows, n, x, state
        input_output_aliases={3: 0},
        # the steps past the count lean on the step before them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="gdn_decode_step",
    )(rows, n, x, state)
    walked = jnp.any((jnp.arange(B)[:, None] == rows[None, :])
                     & (jnp.arange(B)[None, :] < n[0]), axis=1)
    return jnp.where(walked[:, None, None], o, 0.0), state


def gdn_decode_available(H: int, Dk: int, Dv: int) -> bool:
    """Shapes the kernel takes on the current default backend: square
    lane-wide heads (the row-to-column turn is Dk x Dk), and a slot's
    heads few enough for VMEM."""
    return (
        jax.default_backend() == "tpu"
        and Dk == Dv and Dk % 128 == 0
        and H <= _MAX_DECODE_HEADS
    )


def gdn_decode_step_auto(q, k, v, g, beta, state, valid_len):
    """Decode-step router, like the attention routers: the Pallas
    kernel over the decoding slots where the backend and shapes allow,
    the scan's one step over every slot otherwise."""
    if gdn_decode_available(q.shape[1], q.shape[2], v.shape[2]):
        return gdn_decode_step(q, k, v, g, beta, state,
                               *decoding_slots(valid_len))
    o, state = gdn_recurrence(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None],
        state)
    return o[:, 0], state


# --- the mixer ---------------------------------------------------------------


def init_gdn_params(keys, cfg: ModelConfig, dtype) -> dict:
    """One layer's mixer weights from six keys. The decay's two
    per-head vectors are not drawn: a random state has to stay alive
    over hundreds of tokens for any comparison to see it, so the heads'
    decay per token exp(g) is spread evenly in the log from 0.999 to 0.5
    at a = 0 (``softplus(dt_bias) = 1``, ``exp(A_log)`` the rate)."""
    H = cfg.hidden_size
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    K = cfg.linear_conv_kernel_dim

    def dense(k, shape, std=0.02):
        return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    return {
        "in_proj_qkvz": dense(keys[0], (H, 2 * nk * dk + 2 * nv * dv)),
        "in_proj_ba": dense(keys[1], (H, 2 * nv)),
        # [tap, channel]; torch's Conv1d default spread for 4 taps
        "conv1d": dense(keys[2], (K, 2 * nk * dk + nv * dv), std=0.5),
        "A_log": jnp.log(decay_rates(nv)),
        "dt_bias": jnp.full((nv,), jnp.log(jnp.e - 1.0), jnp.float32),
        "norm": jnp.ones((dv,), dtype),
        "out_proj": dense(keys[3], (nv * dv, H)),
    }


def decay_rates(n_heads: int) -> jax.Array:
    """-g per token at a = 0, one per value head: e^-rate runs from
    0.999 to 0.5."""
    return jnp.exp(jnp.linspace(jnp.log(1e-3), jnp.log(jnp.log(2.0)),
                                n_heads, dtype=jnp.float32))


def init_gdn_state(cfg: ModelConfig, n_slots: int, dtype):
    """(state f32[B, Hv, Dk, Dv], convolution tail [B, K-1, channels])."""
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    return (
        jnp.zeros((n_slots, nv, dk, dv), jnp.float32),
        jnp.zeros((n_slots, cfg.linear_conv_kernel_dim - 1,
                   2 * nk * dk + nv * dv), dtype),
    )


def gdn_mixer(p: dict, x, cfg: ModelConfig, state, tail, valid_len):
    """The mixer on x [B, T, H], from the state and the convolution
    tail the rows hold. ``valid_len`` i32[B]: row b's first
    ``valid_len[b]`` tokens are real, the rest (bucket padding, a row
    that is not decoding) must leave state and tail as they were.
    Returns (out [B, T, H], state, tail)."""
    B, T, _ = x.shape
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    rep = nv // nk
    f32 = jnp.float32

    qkvz = (x @ p["in_proj_qkvz"]).reshape(B, T, nk, 2 * dk + 2 * rep * dv)
    ba = (x @ p["in_proj_ba"]).reshape(B, T, nk, 2 * rep)
    q, k, v, z = jnp.split(qkvz, [dk, 2 * dk, 2 * dk + rep * dv], axis=-1)
    b, a = ba[..., :rep].reshape(B, T, nv), ba[..., rep:].reshape(B, T, nv)
    z = z.reshape(B, T, nv, dv)

    # short causal convolution over concat(q, k, v), then SiLU
    mixed = jnp.concatenate(
        [q.reshape(B, T, nk * dk), k.reshape(B, T, nk * dk),
         v.reshape(B, T, nv * dv)], axis=-1)
    full = jnp.concatenate([tail.astype(mixed.dtype), mixed], axis=1)
    K = cfg.linear_conv_kernel_dim
    w = p["conv1d"].astype(f32)
    conv = sum(w[j] * full[:, j:j + T].astype(f32) for j in range(K))
    conv = jax.nn.silu(conv)
    # the K-1 inputs before the first token that has not come yet
    tail = jax.vmap(
        lambda f, n: lax.dynamic_slice_in_dim(f, n, K - 1, axis=0)
    )(full, valid_len).astype(tail.dtype)

    q, k, v = jnp.split(conv, [nk * dk, 2 * nk * dk], axis=-1)

    def unit(y):
        y = y.reshape(B, T, nk, dk)
        y = y * lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)
        return jnp.repeat(y, rep, axis=2)  # key head i serves 2i, 2i+1

    q, k = unit(q) * dk ** -0.5, unit(k)
    v = v.reshape(B, T, nv, dv)
    live = jnp.arange(T)[None, :, None] < valid_len[:, None, None]
    beta = jnp.where(live, jax.nn.sigmoid(b.astype(f32)), 0.0)
    g = jnp.where(live, -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        a.astype(f32) + p["dt_bias"].astype(f32)), 0.0)

    if T == 1:
        o, state = gdn_decode_step_auto(
            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
            valid_len)
        o = o[:, None]
    else:
        o, state = gdn_chunk_scan(q, k, v, g, beta, state)

    # gated norm over each head's width: plain weight, not 1 + w
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.rms_norm_eps)
    o = o * p["norm"].astype(f32) * jax.nn.silu(z.astype(f32))
    out = o.astype(x.dtype).reshape(B, T, nv * dv) @ p["out_proj"]
    return out, state, tail
