"""Pipeline parallelism: decoder stages across a ``pp`` mesh axis.

GPipe-style schedule under ``shard_map``: the L layers split into P
contiguous stages (device p holds only its stage's weights — the stacked
layer pytree shards over ``pp``, so an 80-layer model's params divide
across the axis). The batch splits into M microbatches; activations hop
stage-to-stage via ``ppermute`` (neighbor ICI transfer, never a global
gather). The classic (M + P - 1)-tick schedule fills and drains the
bubble; utilization is M/(M+P-1).

Embedding runs on stage 0 and the head on the last stage; the final
logits are broadcast back with a ``psum`` so every device returns the
same value (convenient for loss computation under pure SPMD callers).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from kubeinfer_tpu.inference.config import ModelConfig
from kubeinfer_tpu.inference.model import (
    Params,
    causal_mask,
    decoder_layer,
    layer_param_template,
    rms_norm,
    rope_tables,
)


def stack_stage_params(params: Params, n_stages: int) -> Params:
    """Regroup per-layer params into [n_stages, layers_per_stage, ...]
    stacked arrays (the leading axis shards over ``pp``)."""
    L = len(params["layers"])
    if L % n_stages:
        raise ValueError(f"{L} layers do not divide into {n_stages} stages")
    per = L // n_stages
    stages = []
    for s in range(n_stages):
        chunk = params["layers"][s * per : (s + 1) * per]
        stages.append(
            jax.tree.map(lambda *xs: jnp.stack(xs), *chunk)
        )
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *stages)
    out = dict(params)
    out["layers"] = stacked  # pytree of [n_stages, per, ...]
    return out



@functools.cache
def _pp_fn(cfg: ModelConfig, mesh: Mesh, M: int, tied: bool):
    """Memoized jitted shard_map per (cfg, mesh, microbatches): building
    it per call would retrace and recompile every forward."""
    # spec tree derived from the layer's actual key structure (family-
    # dependent: dense vs moe mlp, qkv biases) — a hardcoded key list
    # here broke every non-llama family under pp
    layer_spec = jax.tree.map(
        lambda _: P("pp"), layer_param_template(cfg),
        is_leaf=lambda x: x is None,
    )
    other_keys = ["embed_tokens", "norm"] + ([] if tied else ["lm_head"])
    other_spec = {k: P() for k in other_keys}

    PP = mesh.shape["pp"]

    def body(layers_stage, other, toks):
        # layers_stage: this device's [1, per, ...] slice (squeeze below)
        B, T = toks.shape
        p = lax.axis_index("pp")
        mask = jnp.broadcast_to(
            causal_mask(T)[None], (B // M, T, T)
        )
        positions = jnp.broadcast_to(
            jnp.arange(T, dtype=jnp.int32)[None, :], (B // M, T)
        )
        cos, sin = rope_tables(positions, cfg.head_dim, cfg.rope_theta)
        per = jax.tree.leaves(layers_stage)[0].shape[1]

        def run_stage(x):
            def layer_step(x, i):
                layer = jax.tree.map(lambda a: a[0, i], layers_stage)
                x, _ = decoder_layer(layer, x, cos, sin, mask, cfg)
                return x, ()

            x, _ = lax.scan(layer_step, x, jnp.arange(per))
            return x

        def embed(mb):
            x = other["embed_tokens"][mb]
            if cfg.scale_embeddings:  # gemma residual-stream scaling
                x = x * jnp.asarray(
                    float(cfg.hidden_size) ** 0.5, x.dtype
                )
            return x

        mbs = toks.reshape(M, B // M, T)
        H = cfg.hidden_size
        perm_fwd = [(i, (i + 1) % PP) for i in range(PP)]

        # pcast to 'varying': carries start as invariant zeros but hold
        # device-varying values after the first tick (shard_map scan
        # manual-axes typing, as in ring_attention.py)
        buf = lax.pcast(
            jnp.zeros((B // M, T, H), other["norm"].dtype),
            ("pp",), to="varying",
        )  # inbound activation from the previous stage
        # carry ACTIVATIONS, not logits: a vocab-sized carry would be
        # ~16-32x bigger for real models, and projecting per tick would
        # run the model's largest matmul PP*(M+PP-1) times instead of
        # once post-scan
        acts = lax.pcast(
            jnp.zeros((M, B // M, T, H), other["norm"].dtype),
            ("pp",), to="varying",
        )

        def tick(carry, t):
            buf, acts = carry
            # stage 0 injects microbatch t (if still filling)
            x_in = jnp.where(
                (p == 0) & (t < M),
                embed(mbs[jnp.clip(t, 0, M - 1)]).astype(buf.dtype),
                buf,
            )
            x_out = run_stage(x_in)
            # last stage records microbatch (t - PP + 1) when valid
            emit_idx = t - (PP - 1)
            acts = jnp.where(
                (p == PP - 1) & (emit_idx >= 0),
                acts.at[jnp.clip(emit_idx, 0, M - 1)].set(x_out),
                acts,
            )
            buf = lax.ppermute(x_out, "pp", perm_fwd)
            return (buf, acts), ()

        (buf, acts), _ = lax.scan(
            tick, (buf, acts), jnp.arange(M + PP - 1)
        )
        # only the last stage holds real activations; broadcast, then
        # norm + head ONCE over the full batch
        acts = lax.psum(jnp.where(p == PP - 1, acts, 0.0), "pp")
        x = rms_norm(
            acts.reshape(B, T, H), other["norm"], cfg.rms_norm_eps,
            offset=cfg.rmsnorm_offset,
        )
        h = (
            other["embed_tokens"].T
            if cfg.tie_word_embeddings
            else other["lm_head"]
        )
        return (x @ h).astype(jnp.float32)

    return jax.jit(
        shard_map(
            body,
            mesh=mesh,
            in_specs=(layer_spec, other_spec, P()),
            out_specs=P(),
        )
    )


def pipeline_forward(
    params: Params,
    tokens: jax.Array,  # i32[B, T]
    cfg: ModelConfig,
    mesh: Mesh,
    n_microbatches: int = 4,
) -> jax.Array:
    """Causal-LM logits with layers pipelined over the mesh's ``pp`` axis.

    ``B`` must divide by ``n_microbatches``. Numerically identical to the
    dense forward (parity-tested); only the schedule differs. Hot loops
    should call ``stack_stage_params`` once and invoke the memoized
    ``_pp_fn(cfg, mesh, M, tied)`` result directly — this convenience
    wrapper re-stacks the layer tree (a device copy) every call.
    """
    B, _ = tokens.shape
    if B % n_microbatches:
        raise ValueError(
            f"batch {B} must divide into {n_microbatches} microbatches"
        )
    stacked = stack_stage_params(params, mesh.shape["pp"])
    other = {k: v for k, v in stacked.items() if k != "layers"}
    fwd = _pp_fn(cfg, mesh, n_microbatches, cfg.tie_word_embeddings)
    return fwd(stacked["layers"], other, tokens)


def make_pp_mesh(pp: int) -> Mesh:
    from kubeinfer_tpu.inference.sharding import make_axis_mesh

    return make_axis_mesh("pp", pp)
