"""Int8 weights: load-time per-tile quantization + fused dequant-matmul.

The reference hands quantization to vLLM as an opaque engine argument
(vllm.go:57-61 — the flag rides the subprocess command line and the Go
plane never sees a weight); here the engine owns the execution plane,
so the quantized representation must compose with everything the plane
already does: TP sharding (scale planes shard along the weight's out
axis, sharding.expand_quant_specs), speculative verify and chunked
prefill (both just call model.forward, which routes every projection
matmul through ``wq_dot``), and checkpointing (meta.json records
``weight_dtype`` so a restore never double-quantizes).

Quantization math is kv_blocks.quantize_blocks' absmax scheme applied
per (out-column tile) instead of per (block, head): symmetric,
scale = amax/127 with the zero-tile guard pinning scale to 1.0, and the
same dequant→requant-exact property. Granularity rationale: one scale
per out-tile (default 128 columns — one MXU lane tile) keeps the scale
plane a single f32 row that dequantizes INSIDE the matmul epilogue
(acc * scale after the int8 dot), so the bf16 weight never exists in
HBM — not at load (quantization happens on the host copy) and not at
step time (the kernel reads int8 pages + one f32 row per out tile).
Scales are stored per-COLUMN (values constant within a tile) so the
plane shards along the same mesh axis as its weight's out dimension
with no tile-divisibility coupling to the TP degree.

A quantized leaf is the dict ``{"qw": int8[in, out], "scale":
f32[out]}`` replacing the plain ``[in, out]`` array. Only the
matmul-heavy projections quantize (QUANT_LEAVES); embeddings, norms,
biases, lm_head, and the MoE expert stacks stay in the load dtype, so
``weight_dtype="bf16"`` leaves the pytree — and therefore traces and
the compile cache — byte-identical to the pre-quantization engine.

Kernel discipline per the solver invariant: ``quant_matmul`` (Pallas)
and ``quant_matmul_jnp`` (twin) share ``_tile_operands`` /
``_wq_tile_dot`` / ``_wq_finish`` verbatim and accumulate over
identically-shaped [bm, bk] x [bk, bn] tile dots in the same k order —
the twin iterates the tile grid with lax.map/scan rather than issuing
one whole-array dot precisely because XLA may re-associate a
differently-shaped contraction. ``quant_matmul_dense`` is the
tolerance-class dense route (CPU fallback and the GSPMD path, like
flash_attention.dequant_gather_block_kv): one whole dot_general whose
every op partitions cleanly under TP.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Param leaves that route through the fused kernel. 2-D projections
# only: the MoE expert stacks are [E, ...] gathers with tiny per-token
# activation, not weight-bandwidth-bound, and lm_head/embed stay full
# precision because logit quality is the whole product.
QUANT_LEAVES = (
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
)

DEFAULT_TILE = 128


# --- quantization (host/load-time) -----------------------------------------


def quantize_weight(w, tile: int = DEFAULT_TILE):
    """Per-out-tile symmetric absmax int8: kv_blocks.quantize_blocks'
    math (scale = amax/127, zero guard to 1.0) with the group axis
    being ``tile`` consecutive out columns. Returns the quantized-leaf
    dict; ragged final tiles reduce over zero padding, which cannot
    raise an absmax."""
    if w.ndim != 2:
        raise ValueError(f"quantize_weight expects 2-D, got {w.shape}")
    K, N = w.shape
    wf = jnp.asarray(w, jnp.float32)
    nt = -(-N // tile)
    wp = jnp.pad(wf, ((0, 0), (0, nt * tile - N)))
    amax = jnp.max(jnp.abs(wp.reshape(K, nt, tile)), axis=(0, 2))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    scol = jnp.repeat(scale, tile)[:N]
    q = jnp.clip(jnp.round(wf / scol[None, :]), -127, 127).astype(jnp.int8)
    return {"qw": q, "scale": scol}


def dequantize_weight(d, dtype=jnp.float32):
    """Inverse of quantize_weight (kv_blocks.dequantize_blocks' cast
    order: int8 -> f32, scale in f32, cast last)."""
    return (
        d["qw"].astype(jnp.float32) * d["scale"].astype(jnp.float32)[None, :]
    ).astype(dtype)


def _is_quant_leaf(v) -> bool:
    return isinstance(v, dict) and "qw" in v and "scale" in v


def quantize_layer(layer: dict, tile: int = DEFAULT_TILE) -> dict:
    """New layer dict with every QUANT_LEAVES member quantized; norms,
    biases, and the moe subtree pass through untouched."""
    out = dict(layer)
    for name in QUANT_LEAVES:
        w = layer.get(name)
        if w is not None and not _is_quant_leaf(w):
            out[name] = quantize_weight(w, tile=tile)
    return out


def quantize_params(params: dict, tile: int = DEFAULT_TILE) -> dict:
    """Quantize the projection leaves of a full param pytree. Raises on
    an already-quantized tree — double quantization would silently
    re-derive scales from int8 codes (checkpoint.py restores rely on
    this guard)."""
    if params_weight_dtype(params) == "int8":
        raise ValueError(
            "params already weight-quantized (double-quantize guard)"
        )
    out = dict(params)
    out["layers"] = [quantize_layer(l, tile=tile) for l in params["layers"]]
    return out


def dequantize_params(params: dict, dtype=None) -> dict:
    """Plain-array pytree from a quantized one (checkpoint export path
    and the parity tests' exact-grid reference construction)."""
    if dtype is None:
        dtype = params["norm"].dtype
    out = dict(params)
    layers = []
    for layer in params["layers"]:
        nl = dict(layer)
        for name, v in layer.items():
            if _is_quant_leaf(v):
                nl[name] = dequantize_weight(v, dtype)
        layers.append(nl)
    out["layers"] = layers
    return out


def params_weight_dtype(params: dict) -> str:
    """The tree's weight_dtype axis value, inferred from representation
    (quant-dict leaves present or not) so engines/checkpoints never
    need a side channel."""
    for layer in params.get("layers", ()):
        for name in QUANT_LEAVES:
            if _is_quant_leaf(layer.get(name)):
                return "int8"
    return "bf16"


# --- fused dequant-matmul kernel + bit-identical twin ----------------------


def _wq_tile_dot(x_tile, qw_tile):
    """One [bm, bk] x [bk, bn] tile contraction with the int8 tile cast
    to the activation dtype (exact: |q| <= 127 is representable in
    bf16) and f32 accumulation. Shared verbatim by the kernel and the
    twin — the bit-identity contract runs through this function like
    flash_attention's _dequant_tile."""
    return jax.lax.dot_general(
        x_tile, qw_tile.astype(x_tile.dtype),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _wq_finish(acc, scale_row, out_dtype):
    """Dequant epilogue: fold the per-column scale into the f32
    accumulator, cast once. Shared verbatim by kernel and twin."""
    return (acc * scale_row.astype(jnp.float32)).astype(out_dtype)


def _tile_operands(x, qw, scale, bm: int, bn: int, bk: int):
    """Zero-pad all three operands to whole tiles. Shared by the kernel
    wrapper and the twin so both walk the same padded grid; zero k rows
    contribute exact +0.0 to the f32 accumulation, so padding is
    bit-neutral on the un-sliced region."""
    M, K = x.shape
    N = qw.shape[1]
    mt, nt, kt = -(-M // bm), -(-N // bn), -(-K // bk)
    xp = jnp.pad(x, ((0, mt * bm - M), (0, kt * bk - K)))
    qp = jnp.pad(qw, ((0, kt * bk - K), (0, nt * bn - N)))
    sp = jnp.pad(scale, (0, nt * bn - N)).reshape(1, nt * bn)
    return xp, qp, sp, mt, nt, kt


def _quant_matmul_kernel(x_ref, qw_ref, s_ref, o_ref, acc_ref):
    """Grid (mt, nt, kt), k innermost: the out tile and its f32 scratch
    accumulator stay VMEM-resident across the whole k walk; the scale
    row is read once at the finish step."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] = acc_ref[:] + _wq_tile_dot(x_ref[:], qw_ref[:])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        o_ref[:] = _wq_finish(acc_ref[:], s_ref[0], o_ref.dtype)


def quant_matmul(
    x: jax.Array,  # [M, K] activations (f32 or bf16)
    qw: jax.Array,  # int8 [K, N]
    scale: jax.Array,  # f32 [N] per-column (constant within a tile)
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused dequant-matmul: int8 pages stream through VMEM, dequant
    happens on the f32 accumulator in the epilogue — N*K bf16 bytes
    never exist. Twin: quant_matmul_jnp (bit-identical — parity in
    tests/test_weight_quant.py)."""
    M, N = x.shape[0], qw.shape[1]
    xp, qp, sp, mt, nt, kt = _tile_operands(
        x, qw, scale, block_m, block_n, block_k
    )
    out = pl.pallas_call(
        _quant_matmul_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(mt, nt, kt),
            in_specs=[
                pl.BlockSpec(
                    (block_m, block_k), lambda m, n, k: (m, k),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (block_k, block_n), lambda m, n, k: (k, n),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, block_n), lambda m, n, k: (0, n),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (block_m, block_n), lambda m, n, k: (m, n),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mt * block_m, nt * block_n), x.dtype),
        interpret=interpret,
        name="quant_matmul",
    )(xp, qp, sp)
    return out[:M, :N]


def quant_matmul_jnp(
    x: jax.Array,
    qw: jax.Array,
    scale: jax.Array,
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
) -> jax.Array:
    """The kernel's jnp twin: same padded grid (shared _tile_operands),
    same per-tile [bm, bk] x [bk, bn] dots via the shared _wq_tile_dot,
    same ascending-k f32 accumulation, same epilogue. Deliberately NOT
    one whole-array dot — XLA may re-associate a differently-shaped
    contraction, and the twin's job is to pin the kernel's arithmetic,
    not to be fast."""
    M, N = x.shape[0], qw.shape[1]
    xp, qp, sp, mt, nt, kt = _tile_operands(
        x, qw, scale, block_m, block_n, block_k
    )
    xt = xp.reshape(mt, block_m, kt, block_k).transpose(0, 2, 1, 3)
    qt = qp.reshape(kt, block_k, nt, block_n).transpose(0, 2, 1, 3)
    st = sp.reshape(nt, block_n)

    def _tile(idx):
        m, n = idx // nt, idx % nt

        def step(acc, k):
            return acc + _wq_tile_dot(xt[m, k], qt[k, n]), None

        acc, _ = jax.lax.scan(
            step,
            jnp.zeros((block_m, block_n), jnp.float32),
            jnp.arange(kt, dtype=jnp.int32),
        )
        return _wq_finish(acc, st[n], x.dtype)

    tiles = jax.lax.map(_tile, jnp.arange(mt * nt, dtype=jnp.int32))
    out = tiles.reshape(mt, nt, block_m, block_n).transpose(
        0, 2, 1, 3
    ).reshape(mt * block_m, nt * block_n)
    return out[:M, :N]


def quant_matmul_dense(x: jax.Array, qw: jax.Array, scale: jax.Array):
    """Dense fallback AND the GSPMD route (custom calls cannot be
    partitioned — flash_attention.dequant_gather_block_kv's
    constraint): one whole dot_general over the last axis, scale folded
    after. Tolerance-class vs the kernel/twin pair, exact in
    expectation; handles arbitrary leading batch dims."""
    acc = jax.lax.dot_general(
        x, qw.astype(x.dtype),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return (acc * scale.astype(jnp.float32)).astype(x.dtype)


def quant_matmul_available() -> bool:
    """Kernel gate: real TPU only (ragged shapes are padded away, so
    there is no alignment door — CPU runs the dense route, interpret
    mode is for tests)."""
    return jax.default_backend() == "tpu"


def quant_matmul_auto(
    x: jax.Array, qw: jax.Array, scale: jax.Array, *, gspmd: bool = False
) -> jax.Array:
    """Route one projection matmul: Pallas on TPU (leading dims folded
    into M), dense otherwise and always under gspmd."""
    if (not gspmd) and quant_matmul_available():
        lead = x.shape[:-1]
        out = quant_matmul(x.reshape(-1, x.shape[-1]), qw, scale)
        return out.reshape(*lead, qw.shape[1])
    return quant_matmul_dense(x, qw, scale)


def wq_dot(x: jax.Array, w, *, gspmd: bool = False) -> jax.Array:
    """``x @ w`` for a param leaf that may be plain or quantized — the
    single call site model.decoder_layer threads every projection
    through, so bf16 engines trace the exact pre-PR graph (plain leaf
    -> plain matmul, no new ops)."""
    if _is_quant_leaf(w):
        return quant_matmul_auto(x, w["qw"], w["scale"], gspmd=gspmd)
    return x @ w


@functools.partial(jax.jit, static_argnames=("gspmd",), donate_argnums=(0,))
def quant_matmul_step(x, qw, scale, gspmd=False):
    """Standalone jitted entry for the fused kernel (bench phases and
    the analysis registries — jitlint/donatecheck collect decoration
    forms). Donates the activation: a projection consumes its input."""
    return quant_matmul_auto(x, qw, scale, gspmd=gspmd)
