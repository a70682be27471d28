"""Ring attention: causal attention over a sequence-sharded axis.

Long-context scaling (SURVEY.md §2 checklist "Sequence/Context parallel":
absent in the reference; first-class here): the sequence axis is sharded
over mesh devices, each holding a [B, T/P, ...] block of Q, K, V. K/V
blocks rotate around the ring via ``ppermute`` (ICI neighbor exchange —
bandwidth-optimal, no all-gather materializing the full sequence), while
each device folds one block per step into its local attention state using
the online-softmax recurrence (running max m, normalizer l, accumulator
o — the same algebra as FlashAttention's outer loop):

    m' = max(m, rowmax(S));  a = exp(m - m');  b = exp(S - m')
    l' = a*l + rowsum(b);    o' = a*o + b @ V

After P steps every Q block has attended to every K/V block; o/l is the
exact softmax attention. Causality folds into a per-step block mask from
GLOBAL positions (device r holds positions [r*T_loc, (r+1)*T_loc)), so
no [T, T] global mask ever exists.

Compute/communication overlap is XLA's job (the ppermute is independent
of the block compute); the recurrence keeps f32 state regardless of
input dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def _block_attention(
    q: jax.Array,  # [B, Tq, n_kv, G, D] grouped query block
    k: jax.Array,  # [B, Tk, n_kv, D]
    v: jax.Array,  # [B, Tk, n_kv, D]
    mask: jax.Array,  # bool[Tq, Tk] True = attend
    m: jax.Array,  # f32[B, n_kv, G, Tq] running rowmax
    l: jax.Array,  # f32[B, n_kv, G, Tq] running normalizer
    o: jax.Array,  # f32[B, Tq, n_kv, G, D] running accumulator
):
    D = q.shape[-1]
    s = jnp.einsum(
        "btkgd,bskd->bkgts", q, k, preferred_element_type=jnp.float32
    ) / jnp.sqrt(jnp.float32(D))
    s = jnp.where(mask[None, None, None, :, :], s, -1e30)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # renormalize old state; -1e30 rows (nothing attendable yet) stay 0
    # because exp(-1e30 - m_new) underflows to 0 exactly
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = alpha * l + p.sum(axis=-1)
    o_new = o * alpha.transpose(0, 3, 1, 2)[..., None] + jnp.einsum(
        "bkgts,bskd->btkgd", p, v.astype(jnp.float32)
    )
    return m_new, l_new, o_new


def ring_attention(
    q: jax.Array,  # [B, T_loc, n_heads, D] local query block
    k: jax.Array,  # [B, T_loc, n_kv, D] local key block
    v: jax.Array,  # [B, T_loc, n_kv, D]
    *,
    axis_name: str,
    causal: bool = True,
    extra_vary: tuple[str, ...] = (),
) -> jax.Array:
    """Exact causal attention with K/V rotating around ``axis_name``.

    Must run inside shard_map with the sequence axis sharded over
    ``axis_name``. Returns the local attention output block
    [B, T_loc, n_heads, D]. ``extra_vary`` names additional manual mesh
    axes the INPUT blocks vary over (e.g. ``("tp",)`` when the head axis
    is tensor-parallel-sharded) — the scan's accumulator carries must be
    declared varying over exactly the same axes as the per-step values
    merged into them, or shard_map's manual-axes type check rejects the
    carry.
    """
    P = lax.axis_size(axis_name)
    r = lax.axis_index(axis_name)
    B, T_loc, n_heads, D = q.shape
    n_kv = k.shape[2]
    G = n_heads // n_kv
    qg = q.reshape(B, T_loc, n_kv, G, D)

    q_pos = r * T_loc + jnp.arange(T_loc)  # global positions of this block
    perm = [(i, (i + 1) % P) for i in range(P)]  # ring: send right

    # pcast to 'varying': the accumulators start as device-invariant
    # constants but the scan writes device-varying values into them;
    # shard_map's manual-axes type check requires the carry declared
    # varying up front.
    def vary(x):
        return lax.pcast(x, (axis_name,) + extra_vary, to="varying")

    m = vary(jnp.full((B, n_kv, G, T_loc), -jnp.inf, jnp.float32))
    l = vary(jnp.zeros((B, n_kv, G, T_loc), jnp.float32))
    o = vary(jnp.zeros((B, T_loc, n_kv, G, D), jnp.float32))

    def step(carry, i):
        k_blk, v_blk, m, l, o = carry
        # block i arrived from device (r - i) mod P: its global offset
        src = (r - i) % P
        k_pos = src * T_loc + jnp.arange(T_loc)
        mask = (
            q_pos[:, None] >= k_pos[None, :]
            if causal
            else jnp.ones((T_loc, T_loc), bool)
        )
        m, l, o = _block_attention(qg, k_blk, v_blk, mask, m, l, o)
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, m, l, o), ()

    (k, v, m, l, o), _ = lax.scan(
        step, (k, v, m, l, o), jnp.arange(P), length=P
    )
    # rows with no attendable position (never in causal mode) keep l=0;
    # guard the division anyway so non-causal edge uses stay finite
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(B, T_loc, n_heads, D).astype(q.dtype)
