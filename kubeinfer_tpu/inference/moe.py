"""Mixture-of-experts block: sparse dispatch on one device, expert
parallelism over an ``ep`` axis.

One routing function serves every family (:func:`route`): softmax over
all scored experts in float32, the top k, their weights renormalised to
sum 1 — which equals Mixtral's softmax over the top-k logits.

:func:`moe_forward` is the serving path. The (row, expert) pairs whose
expert this replica holds are sorted by expert and go through one
grouped matmul per projection (:func:`grouped_matmul`, a Pallas kernel
that reads an expert's weights only if some row reached it); pairs of
experts held elsewhere are dropped, a shared expert (Qwen3-Next) runs
for every row. No capacity factor: no held pair is ever dropped.

:func:`moe_block` (every expert sees every token) stays as the test
oracle, and as the body of :func:`moe_block_ep`, where each device
holds E/ep experts, computes their weighted contribution for the full
token set and a single ``psum`` combines.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

Params = dict

ROW_TILE = 128  # rows of sorted pairs one grid step of the kernel takes
# what one MoE call adds to the device-side counters (SlotState.moe_stats)
STATS = ("routed_pairs", "held_pairs", "experts_reached", "max_pairs",
         "calls")


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


def route(router: jax.Array, x: jax.Array, top_k: int):
    """(weights f32[..., k], experts i32[..., k]) of every row of
    ``x [..., H]``: softmax in float32 over all ``router.shape[1]``
    experts, the top k, renormalised to sum 1."""
    # full float32 products: a bfloat16 pass moves which experts tie
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = lax.top_k(probs, top_k)
    return w / jnp.sum(w, axis=-1, keepdims=True), idx


def _router_weights(params: Params, x: jax.Array, top_k: int):
    """[B, T, E] routing weights of the dense forms: :func:`route`'s,
    scattered to the experts' own columns, zero elsewhere."""
    w, idx = route(params["router"], x, top_k)
    E = params["router"].shape[1]
    return jnp.sum(w[..., None] * jax.nn.one_hot(idx, E, dtype=w.dtype),
                   axis=-2)


def moe_block(params: Params, x: jax.Array, top_k: int = 2) -> jax.Array:
    """Dense oracle: every expert sees every token (tests only)."""
    w = _router_weights(params, x, top_k)  # [B, T, E]
    gate = jax.nn.silu(jnp.einsum("bth,ehf->betf", x, params["gate_proj"]))
    up = jnp.einsum("bth,ehf->betf", x, params["up_proj"])
    y = jnp.einsum("betf,efh->beth", gate * up, params["down_proj"])
    return jnp.einsum("beth,bte->bth", y, w.astype(x.dtype))


# --- sparse dispatch: the serving path -------------------------------------


def _gmm_kernel(tile_ref, group_ref, n_work_ref, bounds_ref,
                x_ref, w_ref, o_ref):
    """Work item ``i``: rows of tile ``tile_ref[i]`` that belong to
    expert ``group_ref[i]`` times that expert's weights. Items come
    sorted by tile, so an output tile stays in VMEM over its items and
    is zeroed by the first."""
    i = pl.program_id(0)
    tile, group = tile_ref[i], group_ref[i]
    first = (i == 0) | (tile_ref[jnp.maximum(i - 1, 0)] != tile)

    @pl.when(first)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_work_ref[0])
    def _():
        row = tile * ROW_TILE + lax.broadcasted_iota(
            jnp.int32, (ROW_TILE, 1), 0)
        mine = (row >= bounds_ref[group]) & (row < bounds_ref[group + 1])
        y = jnp.dot(x_ref[...], w_ref[0],
                    preferred_element_type=jnp.float32)
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])


def _work_items(sizes: jax.Array, n_tiles: int):
    """(tile, group, n_work) of the (row tile, expert) pairs that share
    a row, in row order; the list is padded to its static length with
    repeats of the last item, which the kernel skips and which move no
    block."""
    E = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    # tiles an expert's rows touch: first..last, none when it has no row
    t_first = starts // ROW_TILE
    t_last = jnp.where(sizes > 0, (ends - 1) // ROW_TILE, t_first - 1)
    per = t_last - t_first + 1
    W = n_tiles + E - 1
    item_end = jnp.cumsum(per)
    n_work = item_end[-1]
    i = jnp.minimum(jnp.arange(W), jnp.maximum(n_work - 1, 0))
    group = jnp.searchsorted(item_end, i, side="right").astype(jnp.int32)
    group = jnp.minimum(group, E - 1)
    tile = t_first[group] + (i - (item_end[group] - per[group]))
    tile = jnp.clip(tile, 0, n_tiles - 1).astype(jnp.int32)
    return tile, group, n_work.astype(jnp.int32)[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(x, w, sizes, interpret: bool = False):
    """``x [M, K]`` holds rows sorted by expert, ``sizes i32[E]`` how
    many each of the E experts has (their sum may be less than M),
    ``w [E, K, N]`` the experts' weights: row r of the result is
    ``x[r] @ w[e]`` for the expert e that owns it. Rows past the sum
    are not computed and come back as they lie in memory: the caller
    masks them. An expert with no row is never read."""
    M, K = x.shape
    E, _, N = w.shape
    assert M % ROW_TILE == 0, (M, ROW_TILE)
    n_tiles = M // ROW_TILE
    tile, group, n_work = _work_items(sizes, n_tiles)
    bounds = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sizes).astype(jnp.int32)])
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_tiles + E - 1,),
            in_specs=[
                pl.BlockSpec((ROW_TILE, K),
                             lambda i, t, g, n, b: (t[i], 0)),
                pl.BlockSpec((1, K, N),
                             lambda i, t, g, n, b: (g[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((ROW_TILE, N),
                                   lambda i, t, g, n, b: (t[i], 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="moe_grouped_matmul",
    )(tile, group, n_work, bounds, x, w)


def grouped_matmul_available(x, w) -> bool:
    """Shapes the kernel takes on the current default backend: whole
    row tiles, lane-aligned widths, one expert's weights (twice, for
    the pipeline) well inside VMEM."""
    M, K = x.shape
    _, _, N = w.shape
    return (
        jax.default_backend() == "tpu"
        and M % ROW_TILE == 0 and K % 128 == 0 and N % 128 == 0
        and x.dtype == w.dtype == jnp.bfloat16
        and K * N * 2 <= 4 * 1024 * 1024
    )


def grouped_matmul_auto(x, w, sizes, gspmd: bool = False):
    """Grouped-matmul router, like the attention routers: the Pallas
    kernel where backend and shapes allow, ``lax.ragged_dot`` otherwise
    (GSPMD cannot split a custom call)."""
    if (not gspmd) and grouped_matmul_available(x, w):
        return grouped_matmul(x, w, sizes)
    return lax.ragged_dot(x, w, sizes.astype(jnp.int32))


def moe_forward(params: Params, x: jax.Array, top_k: int,
                expert_offset: int = 0, act=jax.nn.silu,
                valid: jax.Array | None = None, gspmd: bool = False):
    """``x [B, T, H]`` through the routed experts this replica holds
    (``params["gate_proj"].shape[0]`` of ``params["router"].shape[1]``,
    from ``expert_offset``) and the shared expert if there is one.
    ``valid`` bool[B, T] marks the rows that are real: the pairs of the
    others are dropped like those of absent experts, so padding reads
    no expert's weights. Returns (out [B, T, H], stats u32[len(STATS)]
    of this call)."""
    B, T, H = x.shape
    E = params["gate_proj"].shape[0]
    N = B * T
    xf = x.reshape(N, H)
    w, idx = route(params["router"], xf, top_k)  # [N, k]
    local = idx - expert_offset
    held = (local >= 0) & (local < E)
    if valid is not None:
        held &= valid.reshape(N, 1)
    # absent pairs sort behind every held one
    key = jnp.where(held, local, E).reshape(N * top_k)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(
        jax.nn.one_hot(key, E + 1, dtype=jnp.int32), axis=0)[:E]
    n_held = jnp.sum(sizes)
    M = N * top_k
    pad = -M % ROW_TILE
    rows = jnp.pad(order // top_k, (0, pad))  # the token of each pair
    xs = xf[rows]
    g = grouped_matmul_auto(xs, params["gate_proj"], sizes, gspmd)
    u = grouped_matmul_auto(xs, params["up_proj"], sizes, gspmd)
    y = grouped_matmul_auto(act(g) * u, params["down_proj"], sizes, gspmd)
    y = jnp.where((jnp.arange(M + pad) < n_held)[:, None], y, 0)
    # back to pair order, weighted, summed over a token's k experts
    inv = jnp.argsort(order)
    y = y[inv].reshape(N, top_k, H)
    out = jnp.sum(y * jnp.where(held, w, 0.0)[..., None].astype(y.dtype),
                  axis=1)
    if "shared_gate_proj" in params:
        s = (act(xf @ params["shared_gate_proj"])
             * (xf @ params["shared_up_proj"])) @ params["shared_down_proj"]
        gate = jax.nn.sigmoid(
            (xf @ params["shared_expert_gate"]).astype(jnp.float32))
        out = out + (s * gate.astype(s.dtype))
    n_rows = N if valid is None else jnp.sum(valid)
    stats = jnp.stack([
        n_rows * top_k, n_held, jnp.sum(sizes > 0), jnp.max(sizes),
        jnp.ones((), jnp.int32),
    ]).astype(jnp.uint32)
    return out.reshape(B, T, H), stats


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
