"""Mixture-of-experts block with expert parallelism over an ``ep`` axis.

The reference has no parallelism of its own at all (SURVEY.md §2); the
native engine's MoE tier adds the last member of the dp/tp/sp/pp/ep
family. Design: a top-k softmax router and E SwiGLU experts. Under
expert parallelism each device holds E/ep experts (the expert-stacked
weights shard on their leading axis), computes its local experts'
weighted contribution for the full token set, and a single ``psum``
combines — no token all-to-all, which at this scale costs more than it
saves (the all-to-all dispatch becomes worthwhile when E and token
counts are large enough that compute dominates the replicated-token
waste; the psum form is the correct-first baseline the scaling book
recommends starting from).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

Params = dict


def init_moe_params(
    key: jax.Array,
    hidden: int,
    ffn: int,
    n_experts: int,
    dtype=jnp.float32,
) -> Params:
    ks = jax.random.split(key, 4)

    def dense(k, shape):
        return (0.02 * jax.random.normal(k, shape, jnp.float32)).astype(dtype)

    return {
        "router": dense(ks[0], (hidden, n_experts)),
        # expert-stacked [E, ...]: the leading axis shards over ep
        "gate_proj": dense(ks[1], (n_experts, hidden, ffn)),
        "up_proj": dense(ks[2], (n_experts, hidden, ffn)),
        "down_proj": dense(ks[3], (n_experts, ffn, hidden)),
    }


def _router_weights(params: Params, x: jax.Array, top_k: int):
    """[B, T, E] routing weights: softmax over the top-k expert logits,
    zero elsewhere (standard switch/mixtral routing)."""
    logits = (
        x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    )  # [B, T, E]
    E = logits.shape[-1]
    top_vals, _ = lax.top_k(logits, top_k)
    thresh = top_vals[..., -1:]
    masked = jnp.where(logits >= thresh, logits, -jnp.inf)
    return jax.nn.softmax(masked, axis=-1)  # zeros off the top-k


def moe_block(params: Params, x: jax.Array, top_k: int = 2) -> jax.Array:
    """Dense reference implementation: every expert sees every token."""
    w = _router_weights(params, x, top_k)  # [B, T, E]
    gate = jax.nn.silu(jnp.einsum("bth,ehf->betf", x, params["gate_proj"]))
    up = jnp.einsum("bth,ehf->betf", x, params["up_proj"])
    y = jnp.einsum("betf,efh->beth", gate * up, params["down_proj"])
    return jnp.einsum("beth,bte->bth", y, w.astype(x.dtype))



@functools.cache
def _ep_fn(mesh: Mesh, top_k: int):
    """Memoized jitted shard_map per (mesh, top_k) — building it inside
    moe_block_ep would defeat the jit cache and recompile every call."""

    def body(p_local, x_full):
        r = lax.axis_index("ep")
        E_local = p_local["gate_proj"].shape[0]
        # router weights need ALL experts' logits: router is replicated
        w = _router_weights(
            {"router": p_local["router"]}, x_full,
            top_k,
        )  # [B, T, E_total]
        w_local = lax.dynamic_slice_in_dim(
            w, r * E_local, E_local, axis=2
        )
        gate = jax.nn.silu(
            jnp.einsum("bth,ehf->betf", x_full, p_local["gate_proj"])
        )
        up = jnp.einsum("bth,ehf->betf", x_full, p_local["up_proj"])
        y = jnp.einsum("betf,efh->beth", gate * up, p_local["down_proj"])
        out = jnp.einsum("beth,bte->bth", y, w_local.astype(x_full.dtype))
        return lax.psum(out, "ep")

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(
                {
                    "router": P(),  # replicated: routing needs all logits
                    "gate_proj": P("ep"),
                    "up_proj": P("ep"),
                    "down_proj": P("ep"),
                },
                P(),
            ),
            out_specs=P(),
        )
    )


def moe_block_ep(
    params: Params, x: jax.Array, mesh: Mesh, top_k: int = 2
) -> jax.Array:
    """Expert-parallel form: experts shard over ``ep``, outputs psum.

    Bit-compatible with ``moe_block`` up to reduction order
    (parity-tested to fp tolerance).
    """
    return _ep_fn(mesh, top_k)(params, x)


def make_ep_mesh(ep: int) -> Mesh:
    from kubeinfer_tpu.inference.sharding import make_axis_mesh

    return make_axis_mesh("ep", ep)
