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
and ``quant_matmul_jnp`` (twin) share ``quant_matmul_tiles`` /
``_tile_operands`` / ``_wq_tile_dot`` / ``_wq_finish`` verbatim and
accumulate over identically-shaped [bm, 128] x [128, bn] slice dots in
the same k order — the twin iterates the tile grid with lax.map/scan
rather than issuing one whole-array dot precisely because XLA may
re-associate a differently-shaped contraction. ``quant_matmul_dense``
is the tolerance-class dense route (CPU fallback and the GSPMD path,
like flash_attention.dequant_gather_block_kv): one whole dot_general
whose every op partitions cleanly under TP.

The tile rule (``quant_matmul_tiles``, a pure function of M, K, N and
the activation's width; no flag, nothing read from the model). A Pallas
grid step costs ~0.3 us whatever it moves, so a call takes tens of fat
steps: ``block_k`` is the whole of K up to 5120 and above that the
fewest equal parts that divide its 128-lanes (18944 = 4 x 4736);
``block_n`` the widest divisor of N's lanes that keeps the int8 tile
under 2.5 MiB (512 at every qwen2-7b extent: 18944 = 37 x 512);
``block_m`` all of M up to 512 rows, padded to the activation's sublane
pack (16 rows of bf16 for decode's 8) and not to 128 — the count is
memory-bound there and a 128-row tile re-reads down_proj's activation
seven times. Tiles that divide the extents leave the weight unpadded:
padding would copy it on every call. qwen2-7b: 37 + 37 + 28 + 7 + 7 +
1 + 1 = 118 steps a layer where 128^3 tiles took 14,224.

Why the k-split reads K only: the order in which a row's products are
summed is (k step, then 128-deep slice inside the tile, then the MXU's
own pass), and none of it may depend on how many rows ride beside the
row — the same prompt is answered cold in a 512-row chunk or a
128/256/512-row admit bucket, warm from the radix cache, and in decode
windows of 1 to 8 live rows, and must give the same tokens. So
``block_k`` and the slice depth are functions of K alone, and the
slices' f32 sum is written out in ``_wq_tile_dot`` instead of left to
what Mosaic and XLA each make of a 3584-deep dot (on the chip 128-deep
slices are bit-identical between kernel and twin, 256- and 512-deep
ones are not; one whole-tile dot also was, at the six shapes tried,
but then the order is the two compilers' to keep, not this file's).

VMEM: x and int8 tiles double-buffered, one slice cast up, the f32
accumulator, a slice's product and the out tile — 4 MiB at decode,
19 MiB for the 512-row chunk of down_proj. Mosaic's default scoped
limit is 16 MiB of a v5e's 128, so the call states
``VMEM_LIMIT_BYTES`` (48 MiB) and the chooser plans inside
``VMEM_BUDGET_BYTES`` (32 MiB), taking fewer rows a tile where a shape
would pass it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

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

DEFAULT_TILE = 128  # columns a scale covers; the kernel's tiles are below


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


# --- tile chooser -----------------------------------------------------------
# One Pallas grid step costs about 0.3 us on a v5e whatever it moves
# (PERF.md section 6, PR 33: 398,272 steps of one [128,128] x [128,128]
# dot were 85 % of a qwen2-7b decode step). The tiles are therefore a
# pure function of the call's shape: a call takes tens of steps, and
# every step streams megabytes of int8.

LANE = 128
# Depth of one sub-dot inside a tile: one MXU pass. A tile's k extent is
# walked in slices of this depth with an explicit f32 sum (_wq_tile_dot),
# so the order a row's products are added in is written down here and
# not left to whatever Mosaic and XLA each make of a 3584-deep dot.
K_SLICE = 128
MAX_BLOCK_K = 5120  # 18944 splits 4 x 4736; 3584, 4096 stay whole
MAX_BLOCK_N = 2048  # where K is small; served shapes take 512
WEIGHT_TILE_BYTES = 5 << 19  # 2.5 MiB of int8 a step: [4736, 512]
MAX_BLOCK_M = 512  # the prefill chunk: its weights stream once
# Stated to Mosaic (its default scoped limit is 16 MiB of v5e's 128):
# the fattest served call, 512 x 18944 x 3584, plans 19 MiB.
VMEM_LIMIT_BYTES = 48 << 20
# What the chooser may plan for: Mosaic's own temporaries (the unrolled
# slices' spills) are not in its estimate.
VMEM_BUDGET_BYTES = 32 << 20


class QuantMatmulTiles(NamedTuple):
    block_m: int
    block_n: int
    block_k: int
    grid_steps: int
    vmem_bytes: int


def _even_parts(units: int, cap: int) -> tuple[int, int]:
    """(parts, units a part): the fewest equal parts of at most ``cap``
    units. A count that divides ``units`` is preferred up to twice the
    fewest, because a part that does not divide pads the operand, and
    padding an int8 weight copies all of it on every call."""
    fewest = -(-units // cap)
    for parts in range(fewest, min(2 * fewest, units) + 1):
        if units % parts == 0:
            return parts, units // parts
    return fewest, -(-units // fewest)


def _vmem_bytes(bm: int, bn: int, bk: int, x_bytes: int) -> int:
    return (
        2 * bm * bk * x_bytes  # x tile, double-buffered
        + 2 * bk * bn  # int8 tile, double-buffered
        + 2 * 8 * bn * 4  # scale row, padded to a sublane tile
        + 2 * bm * bn * x_bytes  # out tile, double-buffered
        + bm * bn * 4  # f32 accumulator scratch
        + min(K_SLICE, bk) * bn * x_bytes  # one slice cast up
        + 2 * bm * bn * 4  # a slice's product and the running sum
    )


def quant_matmul_tiles(
    M: int, K: int, N: int, x_bytes: int = 2
) -> QuantMatmulTiles:
    """Tiles and grid of one [M, K] x int8 [K, N] call, from its shape
    alone. ``block_k`` reads K and nothing else: the k-split is the
    order a row's products are summed in, and a prompt must read the
    same bits whether it rides in a 512-row chunk, a 128-row admit or
    beside 7 other decode rows. ``block_n`` follows N under the weight
    tile's byte budget. ``block_m`` follows M: the activation's sublane
    pack at least (a custom call's activation never has one row), the
    whole of M up to MAX_BLOCK_M so the weight streams once, fewer
    rows a tile where the plan would pass the VMEM budget."""
    sublane = 32 // x_bytes
    kt, k_lanes = _even_parts(-(-K // LANE), MAX_BLOCK_K // LANE)
    block_k = k_lanes * LANE
    n_cap = min(MAX_BLOCK_N, WEIGHT_TILE_BYTES // block_k) // LANE
    nt, n_lanes = _even_parts(-(-N // LANE), max(1, n_cap))
    block_n = n_lanes * LANE
    subs = -(-M // sublane)
    mt = -(-subs * sublane // MAX_BLOCK_M)
    while True:
        block_m = -(-subs // mt) * sublane
        vmem = _vmem_bytes(block_m, block_n, block_k, x_bytes)
        if vmem <= VMEM_BUDGET_BYTES or block_m == sublane:
            break
        mt += 1
    mt = -(-M // block_m)
    return QuantMatmulTiles(block_m, block_n, block_k, mt * nt * kt, vmem)


# --- fused dequant-matmul kernel + bit-identical twin ----------------------


def _wq_tile_dot(x_tile, qw_tile):
    """One [bm, bk] x [bk, bn] tile contraction: an ascending walk over
    K_SLICE-deep slices of k, each int8 slice cast to the activation
    dtype (exact: |q| <= 127 is representable in bf16) and multiplied
    there with f32 accumulation, the slices' products summed in f32 in
    walk order. Shared verbatim by the kernel (which hands it Refs, so
    only a slice is ever loaded and cast) and the twin (arrays) — the
    bit-identity contract runs through this function like
    flash_attention's _dequant_tile."""
    bk = qw_tile.shape[0]
    acc = None
    for k0 in range(0, bk, K_SLICE):
        k1 = min(k0 + K_SLICE, bk)
        x_s = x_tile[:, k0:k1]
        part = jax.lax.dot_general(
            x_s, qw_tile[k0:k1, :].astype(x_s.dtype),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = part if acc is None else acc + part
    return acc


def _wq_finish(acc, scale_row, out_dtype):
    """Dequant epilogue: fold the per-column scale into the f32
    accumulator, cast once. Shared verbatim by kernel and twin."""
    return (acc * scale_row.astype(jnp.float32)).astype(out_dtype)


def _tile_operands(x, qw, scale, bm: int, bn: int, bk: int):
    """Zero-pad all three operands to whole tiles. Shared by the kernel
    wrapper and the twin so both walk the same padded grid; zero k rows
    contribute exact +0.0 to the f32 accumulation, so padding is
    bit-neutral on the un-sliced region. The chooser's tiles divide the
    served extents, so there the weight is passed through untouched."""
    M, K = x.shape
    N = qw.shape[1]
    mt, nt, kt = -(-M // bm), -(-N // bn), -(-K // bk)
    xp = jnp.pad(x, ((0, mt * bm - M), (0, kt * bk - K)))
    qp = jnp.pad(qw, ((0, kt * bk - K), (0, nt * bn - N)))
    sp = jnp.pad(scale, (0, nt * bn - N)).reshape(1, nt * bn)
    return xp, qp, sp, mt, nt, kt


def _resolve_tiles(x, qw, block_m, block_n, block_k):
    """The chooser's tiles, each overridable (tests pin odd ones)."""
    t = quant_matmul_tiles(
        x.shape[0], x.shape[1], qw.shape[1], x.dtype.itemsize)
    return (block_m or t.block_m, block_n or t.block_n,
            block_k or t.block_k)


def _quant_matmul_kernel(x_ref, qw_ref, s_ref, o_ref, acc_ref):
    """Grid (nt, mt, kt), k innermost: the out tile and its f32 scratch
    accumulator stay VMEM-resident across the whole k walk; the scale
    row is read once at the finish step."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    acc_ref[:] = acc_ref[:] + _wq_tile_dot(x_ref, qw_ref)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        o_ref[:] = _wq_finish(acc_ref[:], s_ref[0], o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "block_k", "interpret"))
def quant_matmul(
    x: jax.Array,  # [M, K] activations (f32 or bf16)
    qw: jax.Array,  # int8 [K, N]
    scale: jax.Array,  # f32 [N] per-column (constant within a tile)
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Fused dequant-matmul: int8 pages stream through VMEM, dequant
    happens on the f32 accumulator in the epilogue — N*K bf16 bytes
    never exist. Tiles default to quant_matmul_tiles' for the shape.
    n is the outermost grid axis: where k is not split a weight tile
    stays resident across the m tiles, so rows past MAX_BLOCK_M do not
    stream the weight again. Jitted in its own right, so a step program
    lowers the kernel once for each shape it issues and calls that 28
    times: the unrolled slice walk costs ~50 ms a lowering, which 196
    call sites x 7 programs would add to every start. Twin:
    quant_matmul_jnp (bit-identical — parity in
    tests/test_weight_quant.py)."""
    M, N = x.shape[0], qw.shape[1]
    block_m, block_n, block_k = _resolve_tiles(
        x, qw, block_m, block_n, block_k)
    xp, qp, sp, mt, nt, kt = _tile_operands(
        x, qw, scale, block_m, block_n, block_k
    )
    out = pl.pallas_call(
        _quant_matmul_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0,
            grid=(nt, mt, kt),
            in_specs=[
                pl.BlockSpec(
                    (block_m, block_k), lambda n, m, k: (m, k),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (block_k, block_n), lambda n, m, k: (k, n),
                    memory_space=pltpu.VMEM,
                ),
                pl.BlockSpec(
                    (1, block_n), lambda n, m, k: (0, n),
                    memory_space=pltpu.VMEM,
                ),
            ],
            out_specs=pl.BlockSpec(
                (block_m, block_n), lambda n, m, k: (m, n),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((mt * block_m, nt * block_n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="quant_matmul",
    )(xp, qp, sp)
    return out[:M, :N]


def quant_matmul_jnp(
    x: jax.Array,
    qw: jax.Array,
    scale: jax.Array,
    *,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
) -> jax.Array:
    """The kernel's jnp twin: same tiles (quant_matmul_tiles), same
    padded grid (shared _tile_operands), same per-tile slice walk via
    the shared _wq_tile_dot, same ascending-k f32 accumulation, same
    epilogue. Deliberately NOT one whole-array dot — XLA may
    re-associate a differently-shaped contraction, and the twin's job
    is to pin the kernel's arithmetic, not to be fast."""
    M, N = x.shape[0], qw.shape[1]
    block_m, block_n, block_k = _resolve_tiles(
        x, qw, block_m, block_n, block_k)
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
