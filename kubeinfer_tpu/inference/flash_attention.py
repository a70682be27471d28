"""Pallas TPU flash attention for the prefill/training hot op.

The dense path (model.attention) materializes [B, n_kv, G, T, S] f32
scores through HBM; at long context that is the dominant memory term
(a 512-token chunk against a 128k cache is 0.5GB of scores per layer at
B=8, H=32). These kernels stream K/V tiles through VMEM with the online
softmax recurrence (running rowmax m, normalizer l, accumulator o — the
same algebra as ring_attention.py's block fold, here over the LOCAL S
axis instead of a device ring), so the f32 score/probability tensors
never touch HBM.

Two mask sources share one softmax body (``_softmax_fold``):

- ``flash_attention_ragged``: the causal+length mask
  ((s <= q_offset + t) & (s < row_len)) derived IN-KERNEL from two
  scalars via iotas — nothing [T, S]-sized exists anywhere, in HBM or
  out. This covers BOTH production mask shapes: the engine's chunked
  prefill (q_offset = chunk start, row_len = prompt length) and plain
  causal self-attention (q_offset = 0, row_len = S —
  ``causal_attention_auto``, the no-cache forward's path). r2 shipped
  the general kernel an int8 [B, T, S] mask (O(B·T·S) HBM traffic);
  r2's verdict item 8 relegates that to the arbitrary-mask fallback
  below.
- ``flash_attention``: a caller-supplied bool[B, T, S] mask ships to
  the kernel as int8 (head-independent — 4*n_kv*G times smaller than
  the scores it replaces). The ARBITRARY-mask fallback: correct for any
  mask, but pays the mask's HBM traffic — production paths use the
  in-kernel variants; this remains for exotic masks (blockwise-sparse
  experiments, bidirectional scoring).

A third variant serves the decode step: ``decode_attention`` (T == 1,
per-row live lengths as a scalar-prefetch operand) reads only each
row's live KV tiles — the BlockSpec index_map clamps past the length so
the dead tiles' DMAs are elided, not just their compute. Its twin
``decode_attention_jnp`` shares ``_fold_tile_math`` and is bit-identical
(parity in tests/test_flash_attention.py).

Layout: GQA folds the (T, G) axes into MXU rows — q becomes
[B*n_kv, T*G, D], each S tile is one [T_q*G, D] x [D, S_k] matmul plus
one [T_q*G, S_k] x [S_k, D] matmul, and the mask penalty (which depends
on T alone, not G) broadcasts across the G subrows in-register. The S
grid axis is innermost with the accumulators in VMEM scratch, so state
stays resident across the sweep (same accumulate-across-grid idiom as
the solver's accept kernel).

Fully-masked rows reproduce the dense path's uniform-softmax output
exactly (all scores -1e30 -> p == 1 everywhere -> o/l is the mean over
S), so parity holds even on padding rows.

Backward (r3 verdict item 6): ``flash_attention_causal_diff`` wraps the
ragged kernel in a custom_vjp with the recompute-based backward from the
public flash-attention literature — the forward additionally emits the
per-row logsumexp L = m + log(l), and the backward re-materializes each
[tile_t*G, tile_s] probability block in VMEM from (q, k, L) instead of
ever having stored it: dv += p^T dO, ds = p * (dO v^T − rowsum(dO*O)),
dq += ds k, dk += ds^T q. Two kernels mirror the forward's
accumulate-across-inner-grid idiom: dq sweeps S with a resident [TqG, D]
accumulator; dk/dv sweep T with resident [Sk, D] accumulators (the GQA
row fold makes the G-group reduction implicit in the ds^T q contraction).
``causal_attention_auto`` routes through the differentiable wrapper, so
training no longer pins the dense path (train.causal_lm_loss).

No reference counterpart: the reference delegates all attention to the
external vLLM process (SURVEY.md §2, vllm.go:93-112).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from kubeinfer_tpu.inference.kv_blocks import (
    dequantize_blocks,
    page_dims,
    pages_to_rows,
)
from kubeinfer_tpu.inference.model import attention as dense_attention

TILE_T = 256  # query positions per tile (rows = TILE_T * G)
TILE_S = 512  # key/value positions per tile

# Mosaic gives one kernel 16 MiB of scoped VMEM by default on v5e (the
# smallest of the supported chips); past it the compile is refused, and
# by then the outer jit is lowering and nothing can fall back. The model
# below is an estimate of the compiler's own count (it read 17.0 MiB
# where the compiler reported 16.67 at gemma-2b widths), so the budget
# keeps a quarter of the limit as margin.
_VMEM_BUDGET = 12 << 20


def _prefill_tiles(q, k, tile_t, tile_s):
    """(tile_t, tile_s) the prefill kernels run with, from the
    [B, T, n_heads, D] / [B, S, n_kv, D] operands every entry point
    (forward, lse-forward, backward) holds — one resolution, so primal,
    vjp-fwd and bwd always tile alike. The requested sizes are clamped
    to the array, then tile_t is halved until the tile's VMEM footprint
    fits the budget. The footprint grows with the MXU row count
    tile_t * groups and with D — 8 query heads on one KV head at D=256
    (gemma-2b) is 4x llama-3-8b's — so a fixed TILE_T cannot serve
    every admitted model. Only tile_t moves: rows are independent of
    one another, whereas tile_s sets the order the S sweep accumulates
    in, and that order is what parity tests pin."""
    T, groups, D = q.shape[1], q.shape[2] // k.shape[2], q.shape[3]
    itemsize = q.dtype.itemsize
    tile_t, tile_s = min(tile_t, T), min(tile_s, k.shape[1])

    def footprint(tt):
        rows = tt * groups
        return (
            2 * 2 * rows * D * itemsize  # q and o blocks, double-buffered
            + 2 * 2 * tile_s * D * itemsize  # k and v blocks, likewise
            + rows * D * 4  # acc scratch
            + 2 * rows * 128 * 4  # m and l scratch, lane-padded
            + 2 * rows * tile_s * 4  # f32 scores and probabilities
        )

    # a halved tile must stay sublane-aligned (flash_available's T % 8)
    while footprint(tile_t) > _VMEM_BUDGET and tile_t % 16 == 0:
        tile_t //= 2
    return tile_t, tile_s


def _fold_tile_math(
    q,  # [TqG, D] folded (t, g) query rows
    k,  # [Sk, D]
    v,  # [Sk, D]
    pen,  # f32[Tq, Sk]: 0 = attend, -1e30 = masked
    m_prev,  # f32[TqG, 1]
    l_prev,  # f32[TqG, 1]
    acc_prev,  # f32[TqG, D]
    *,
    groups: int,
    scale: float,
):
    """The pure value-level online-softmax step: one (q-tile, s-tile)
    fold of the running (m, l, acc) state. Shared between the Pallas
    kernels (via _softmax_fold / _decode_attn_kernel) and the decode
    jnp twin — bit-identity between a kernel and its twin is only
    checkable if both run THIS function, not a re-derivation (the same
    contract as pallas_kernels.mega_rounds_jnp sharing the round math).

    Batched use: the decode twin vmaps this over the B*n_kv axis, so
    every operand here is one grid instance's tile."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [TqG, Sk]
    # Masking as an f32 additive penalty broadcast across the G
    # subrows. Mosaic cannot relayout i1 vectors ("unsupported shape
    # cast" on a bool [Tq, 1, Sk] broadcast), so rank changes happen
    # on f32 values; the add is exact (|s| << 1e23, so s + -1e30
    # rounds to -1e30).
    tq, sk = pen.shape
    s = (s.reshape(tq, groups, sk) + pen[:, None, :]).reshape(
        tq * groups, sk
    )

    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)  # [TqG, Sk] f32
    l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [TqG, D]
    acc_new = acc_prev * alpha + pv
    return m_new, l_new, acc_new


def _softmax_fold(
    q_ref,  # [1, TILE_T * G, D] folded (t, g) query rows
    k_ref,  # [1, TILE_S, D]
    v_ref,  # [1, TILE_S, D]
    pen,  # f32[TILE_T, TILE_S]: 0 = attend, -1e30 = masked
    o_ref,  # [1, TILE_T * G, D] out
    m_scr,  # f32[TILE_T * G, 1] scratch: running rowmax
    l_scr,  # f32[TILE_T * G, 1] scratch: running normalizer
    acc_scr,  # f32[TILE_T * G, D] scratch: running accumulator
    *,
    groups: int,
    scale: float,
    s_tiles: int,
    active=None,  # scalar bool: False = this tile provably contributes 0
):
    """One S-tile step of the online softmax, shared by both kernels —
    the recurrence, scratch lifecycle, and GQA penalty broadcast must
    never diverge between the mask-tensor and iota-mask variants.

    ``active=False`` skips the fold for a tile whose every slot is
    masked. BIT-identical by construction, not an approximation: with
    pen == -1e30 everywhere, s == -1e30 exactly (f32 absorbs the |qk|
    term), so m_new == m_prev, alpha == 1, and p == exp(-1e30 - m)
    underflows to exactly 0 — the skipped fold would add 0 to l and acc
    and rewrite m with itself. The one exception is a row that has seen
    NO unmasked tile yet (m == -1e30, making p == 1, not 0) — callers
    must keep such rows' tiles active (the ragged kernels run row_len==0
    rows dense, preserving their defined uniform-average output). This
    is the prefill MFU lever (r4 verdict item 2): the causal upper
    triangle is ~half of every prefill grid, and the fold's exp/max VPU
    sweep — not the MXU matmuls — is what those tiles burn."""
    ts = pl.program_id(2)  # innermost: S sweep with resident scratch

    @pl.when(ts == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def _fold():
        m_new, l_new, acc_new = _fold_tile_math(
            q_ref[0], k_ref[0], v_ref[0], pen,
            m_scr[:], l_scr[:], acc_scr[:],
            groups=groups, scale=scale,
        )
        l_scr[:] = l_new
        acc_scr[:] = acc_new
        m_scr[:] = m_new

    if active is None:
        _fold()
    else:
        pl.when(active)(_fold)

    @pl.when(ts == s_tiles - 1)
    def _finish():
        # l == 0 cannot happen (even fully-masked rows accumulate
        # p == 1 per position); the guard keeps hypothetical S == 0
        # grids finite.
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(
            o_ref.dtype
        )


def _flash_kernel(
    mask_ref,  # [1, TILE_T, TILE_S] int8 (1 = attend); extras lead
    q_ref, k_ref, v_ref,
    o_ref, m_scr, l_scr, acc_scr,
    *, groups: int, scale: float, s_tiles: int,
):
    pen = (mask_ref[0].astype(jnp.float32) - 1.0) * 1e30
    _softmax_fold(
        q_ref, k_ref, v_ref, pen, o_ref, m_scr, l_scr, acc_scr,
        groups=groups, scale=scale, s_tiles=s_tiles,
    )


def _flash_ragged_kernel(
    c0_ref,  # SMEM i32[1]: global position of the first query row
    len_ref,  # SMEM i32[B]: per-row valid sequence lengths (whole vector:
    #           Mosaic rank-1 blocks must equal the array or tile to 128,
    #           so a (1,)-block per batch row only lowers at B == 1 —
    #           indexed in-kernel by program_id instead)
    q_ref, k_ref, v_ref,
    o_ref, m_scr, l_scr, acc_scr,
    *, groups: int, scale: float, s_tiles: int, tile_t: int, tile_s: int,
    n_kv: int,
):
    """The engine's prefill mask — attend cache slots <= own global
    position AND < the row's valid length — from iotas on two scalars
    instead of a shipped [B, T, S] int8 tensor."""
    row_len = len_ref[pl.program_id(0) // n_kv]
    tq = pl.program_id(1)
    ts = pl.program_id(2)
    q_pos = (
        c0_ref[0] + tq * tile_t
        + jax.lax.broadcasted_iota(jnp.int32, (tile_t, tile_s), 0)
    )
    s_pos = ts * tile_s + jax.lax.broadcasted_iota(
        jnp.int32, (tile_t, tile_s), 1
    )
    attend = (s_pos <= q_pos) & (s_pos < row_len)
    pen = jnp.where(attend, 0.0, -1e30)  # i1 never changes rank
    _softmax_fold(
        q_ref, k_ref, v_ref, pen, o_ref, m_scr, l_scr, acc_scr,
        groups=groups, scale=scale, s_tiles=s_tiles,
        active=_ragged_tile_active(
            c0_ref[0], row_len, tq, ts, tile_t, tile_s
        ),
    )


def _ragged_tile_active(c0, row_len, tq, ts, tile_t, tile_s):
    """Whether this (tq, ts) tile can contain any unmasked slot under
    the causal+length mask. Tile 0 of the S sweep always runs — it owns
    the scratch init, and keeping every tile of a row_len == 0 row
    active preserves that row's defined output (see _softmax_fold)."""
    s_start = ts * tile_s
    q_max = c0 + (tq + 1) * tile_t - 1
    return (
        (ts == 0)
        | (row_len == 0)
        | ((s_start <= q_max) & (s_start < row_len))
    )


def _run_flash(
    kern,
    extra_arrays: tuple,
    extra_specs: list,
    q: jax.Array,  # [B, T, n_heads, D]
    k: jax.Array,  # [B, S, n_kv, D]
    v: jax.Array,
    tile_t: int,
    tile_s: int,
    interpret: bool,
    name: str,
) -> jax.Array:
    """Shared host plumbing: GQA row fold, tile validation, pallas_call,
    and the inverse fold. ``extra_arrays``/``extra_specs`` prepend the
    kernel's mask source (int8 tensor or SMEM scalars). ``tile_t`` and
    ``tile_s`` are the caller's _prefill_tiles result — the kernel partial
    and the mask BlockSpec were built from the same pair."""
    B, T, n_heads, D = q.shape
    S, n_kv = k.shape[1], k.shape[2]
    G = n_heads // n_kv
    if T % tile_t or S % tile_s:
        raise ValueError(
            f"{name} needs T divisible by {tile_t} and S by {tile_s}; "
            f"got T={T} S={S} (use attention_auto for fallback)"
        )
    t_tiles, s_tiles = T // tile_t, S // tile_s

    # fold (B, n_kv) into the grid axis and (T, G) into MXU rows
    qf = q.reshape(B, T, n_kv, G, D).transpose(0, 2, 1, 3, 4)
    qf = qf.reshape(B * n_kv, T * G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * n_kv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * n_kv, S, D)

    out = pl.pallas_call(
        functools.partial(
            kern, groups=G, scale=1.0 / float(D) ** 0.5, s_tiles=s_tiles
        ),
        grid=(B * n_kv, t_tiles, s_tiles),
        in_specs=extra_specs + [
            pl.BlockSpec(
                (1, tile_t * G, D), lambda bh, tq, ts: (bh, tq, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, tile_s, D), lambda bh, tq, ts: (bh, ts, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, tile_s, D), lambda bh, tq, ts: (bh, ts, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, tile_t * G, D), lambda bh, tq, ts: (bh, tq, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((B * n_kv, T * G, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((tile_t * G, 1), jnp.float32),
            pltpu.VMEM((tile_t * G, 1), jnp.float32),
            pltpu.VMEM((tile_t * G, D), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(*extra_arrays, qf, kf, vf)
    out = out.reshape(B, n_kv, T, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, T, n_heads, D)


def flash_attention(
    q: jax.Array,  # [B, T, n_heads, D]
    k: jax.Array,  # [B, S, n_kv, D]
    v: jax.Array,  # [B, S, n_kv, D]
    mask: jax.Array,  # bool[B, T, S] True = attend
    *,
    tile_t: int = TILE_T,
    tile_s: int = TILE_S,
    interpret: bool = False,
) -> jax.Array:
    """Exact attention, streamed; requires T % tile_t == S % tile_s == 0.

    Callers wanting automatic fallback for unaligned shapes use
    ``attention_auto``.
    """
    n_kv = k.shape[2]
    tt, ts_ = _prefill_tiles(q, k, tile_t, tile_s)
    return _run_flash(
        _flash_kernel,
        (mask.astype(jnp.int8),),
        [
            pl.BlockSpec(
                (1, tt, ts_),
                lambda bh, tq, ts, n_kv=n_kv: (bh // n_kv, tq, ts),
                memory_space=pltpu.VMEM,
            ),
        ],
        q, k, v, tt, ts_, interpret, "flash_attention",
    )


def flash_attention_ragged(
    q: jax.Array,  # [B, T, n_heads, D]
    k: jax.Array,  # [B, S, n_kv, D]
    v: jax.Array,  # [B, S, n_kv, D]
    q_offset: jax.Array,  # i32 scalar: global position of q[:, 0]
    row_lens: jax.Array,  # i32[B] valid sequence length per row
    *,
    tile_t: int = TILE_T,
    tile_s: int = TILE_S,
    interpret: bool = False,
) -> jax.Array:
    """flash_attention specialized to the chunked-prefill mask
    ``(s <= q_offset + t) & (s < row_lens[b])``, computed in-kernel from
    scalars — nothing [T, S]-sized exists anywhere, in HBM or out."""
    n_kv = k.shape[2]
    tt, ts_ = _prefill_tiles(q, k, tile_t, tile_s)
    lens = jnp.asarray(row_lens, jnp.int32)
    kern = functools.partial(
        _flash_ragged_kernel, tile_t=tt, tile_s=ts_, n_kv=n_kv
    )
    return _run_flash(
        kern,
        (
            jnp.asarray(q_offset, jnp.int32).reshape(1),
            lens,
        ),
        [
            pl.BlockSpec(
                (1,), lambda bh, tq, ts: (0,), memory_space=pltpu.SMEM
            ),
            pl.BlockSpec(
                lens.shape, lambda bh, tq, ts: (0,),
                memory_space=pltpu.SMEM,
            ),
        ],
        q, k, v, tt, ts_, interpret, "flash_attention_ragged",
    )


# --- batched decode attention (T == 1, per-row live lengths) ---------------


def _decode_attn_kernel(
    len_ref,  # scalar-prefetch i32[B]: per-row live lengths
    q_ref,  # [1, G, D] — the row's single query, groups as MXU rows
    k_ref,  # [1, tile_s, D]
    v_ref,  # [1, tile_s, D]
    o_ref,  # [1, G, D] out
    m_scr,  # f32[G, 1]
    l_scr,  # f32[G, 1]
    acc_scr,  # f32[G, D]
    *,
    groups: int,
    scale: float,
    s_tiles: int,
    tile_s: int,
    n_kv: int,
):
    """One decode step's attention for one (batch row, kv head): sweep
    the row's live S tiles with the shared fold. The grid is 2D
    (B*n_kv, s_tiles) — T == 1 makes the q-tile axis pointless — and
    the per-row length lives in the scalar-prefetch operand so the k/v
    BlockSpec index_map can clamp DMAs past the live length (see
    decode_attention). Tiles past the length are also compute-skipped;
    both are bit-identical no-ops (see _softmax_fold's active note:
    row_len == 0 rows keep every tile live to preserve their defined
    uniform-average output)."""
    row_len = len_ref[pl.program_id(0) // n_kv]
    ts = pl.program_id(1)  # innermost: S sweep with resident scratch

    @pl.when(ts == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when((ts == 0) | (row_len == 0) | (ts * tile_s < row_len))
    def _fold():
        s_pos = ts * tile_s + jax.lax.broadcasted_iota(
            jnp.int32, (1, tile_s), 1
        )
        pen = jnp.where(s_pos < row_len, 0.0, -1e30)
        m_new, l_new, acc_new = _fold_tile_math(
            q_ref[0], k_ref[0], v_ref[0], pen,
            m_scr[:], l_scr[:], acc_scr[:],
            groups=groups, scale=scale,
        )
        l_scr[:] = l_new
        acc_scr[:] = acc_new
        m_scr[:] = m_new

    @pl.when(ts == s_tiles - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(
            o_ref.dtype
        )


def decode_attention(
    q: jax.Array,  # [B, 1, n_heads, D] — one new token per row
    k: jax.Array,  # [B, S, n_kv, D] padded KV cache
    v: jax.Array,  # [B, S, n_kv, D]
    lengths: jax.Array,  # i32[B]: live entries per row (offset + 1)
    *,
    tile_s: int = TILE_S,
    interpret: bool = False,
) -> jax.Array:
    """Batched ragged decode attention: each row attends to its own
    first ``lengths[b]`` cache slots. HBM traffic is the point — the
    lengths ride in as a scalar-prefetch operand, so the k/v index_map
    below clamps the block index past each row's live length and
    Pallas elides the repeated-block DMAs: a row that is 1k tokens
    into a 128k cache reads ~1k positions, not 128k. The dense path
    this replaces reads the full padded cache every step for every
    row. Twin: decode_attention_jnp (bit-identical, parity-tested)."""
    B, T, n_heads, D = q.shape
    if T != 1:
        raise ValueError(f"decode_attention is T == 1 only; got T={T}")
    S, n_kv = k.shape[1], k.shape[2]
    G = n_heads // n_kv
    tile_s = min(tile_s, S)
    if S % tile_s:
        raise ValueError(
            f"decode_attention needs S divisible by {tile_s}; got S={S} "
            "(use decode_attention_auto for fallback)"
        )
    s_tiles = S // tile_s

    qf = q.reshape(B, n_kv, G, D).reshape(B * n_kv, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * n_kv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * n_kv, S, D)
    lens = jnp.asarray(lengths, jnp.int32)

    def _kv_map(bh, ts, lens_ref, n_kv=n_kv, tile_s=tile_s):
        # Clamp the S block index to the row's last live tile: Pallas
        # skips the DMA when consecutive steps name the same block, so
        # dead tiles cost nothing. row_len == 0 rows must NOT clamp —
        # their (defined) output is the uniform average over the real
        # cache contents, so they read every true tile.
        rl = lens_ref[bh // n_kv]
        live_last = jnp.maximum(rl - 1, 0) // tile_s
        return (bh, jnp.where(rl == 0, ts, jnp.minimum(ts, live_last)), 0)

    q_spec = pl.BlockSpec(
        (1, G, D), lambda bh, ts, lens_ref: (bh, 0, 0),
        memory_space=pltpu.VMEM,
    )
    kv_spec = pl.BlockSpec((1, tile_s, D), _kv_map, memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(
            _decode_attn_kernel, groups=G, scale=1.0 / float(D) ** 0.5,
            s_tiles=s_tiles, tile_s=tile_s, n_kv=n_kv,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * n_kv, s_tiles),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * n_kv, G, D), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lens, qf, kf, vf)
    return out.reshape(B, 1, n_heads, D)


def decode_attention_jnp(
    q: jax.Array,  # [B, 1, n_heads, D]
    k: jax.Array,  # [B, S, n_kv, D]
    v: jax.Array,
    lengths: jax.Array,  # i32[B]
    *,
    tile_s: int = TILE_S,
) -> jax.Array:
    """The decode kernel's jnp twin: the SAME _fold_tile_math, iterated
    over the B*n_kv grid axis with lax.map and over the S tiles with
    lax.scan, with the same penalty construction. Sequential per-row
    execution (not vmap) is deliberate: it keeps every dot_general the
    exact per-instance shape the interpreted kernel runs, so XLA:CPU
    picks the same lowering and kernel-vs-twin parity is exact
    (np.array_equal), per the repo invariant — a vmapped batched dot
    accumulates differently at G == 1. The twin runs every tile
    densely; the kernel's skipped tiles contribute exactly 0 (p
    underflows against a finite running max), so skipping never shows
    up in the bits."""
    B, T, n_heads, D = q.shape
    if T != 1:
        raise ValueError(f"decode_attention_jnp is T == 1 only; got T={T}")
    S, n_kv = k.shape[1], k.shape[2]
    G = n_heads // n_kv
    tile_s = min(tile_s, S)
    if S % tile_s:
        raise ValueError(f"S={S} must divide by tile_s={tile_s}")
    s_tiles = S // tile_s
    BH = B * n_kv
    scale = 1.0 / float(D) ** 0.5

    qf = q.reshape(B, n_kv, G, D).reshape(BH, G, D)
    kf = k.transpose(0, 2, 1, 3).reshape(BH, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(BH, S, D)
    row_len = jnp.repeat(jnp.asarray(lengths, jnp.int32), n_kv)  # [BH]

    def _row(args):
        qr, kr, vr, rl = args  # [G, D], [S, D], [S, D], i32

        def step(carry, ts):
            m, l, acc = carry
            k_t = jax.lax.dynamic_slice_in_dim(kr, ts * tile_s, tile_s, 0)
            v_t = jax.lax.dynamic_slice_in_dim(vr, ts * tile_s, tile_s, 0)
            s_pos = ts * tile_s + jax.lax.broadcasted_iota(
                jnp.int32, (1, tile_s), 1
            )
            pen = jnp.where(s_pos < rl, 0.0, -1e30)
            return _fold_tile_math(
                qr, k_t, v_t, pen, m, l, acc, groups=G, scale=scale
            ), None

        init = (
            jnp.full((G, 1), -1e30, jnp.float32),
            jnp.zeros((G, 1), jnp.float32),
            jnp.zeros((G, D), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            step, init, jnp.arange(s_tiles, dtype=jnp.int32)
        )
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    out = jax.lax.map(_row, (qf, kf, vf, row_len))  # [BH, G, D]
    return out.reshape(B, 1, n_heads, D)


def decode_flash_available(S: int, D: int) -> bool:
    """Shapes the decode kernel handles on the current default backend
    — same conservative contract as flash_available (a wrong True is a
    trace-time Mosaic error), minus the T constraints (T is always 1
    here, folded into the G rows)."""
    return (
        jax.default_backend() == "tpu"
        and S % min(TILE_S, S) == 0
        and S % 128 == 0
        and S >= 128
        and D % 64 == 0
    )


def decode_attention_auto(q, k, v, lengths, mask, gspmd=False):
    """Decode-step attention router: the length-clamped Pallas kernel
    when shapes/backend allow, dense jnp over ``mask`` otherwise. The
    flash branch never reads ``mask`` — XLA dead-code-eliminates its
    construction (the chunked_prefill contract). ``lengths`` and
    ``mask`` must describe the same live set (mask[b] true exactly on
    slots < lengths[b]) or the two branches diverge.

    ``gspmd=True`` pins the dense branch: a caller tracing under a
    sharded jit needs every op partitionable, and a Pallas kernel is a
    custom call GSPMD cannot split over heads — it would replicate (or
    fail to lower), same constraint forward_tensor_parallel documents
    for the prefill kernel."""
    if (not gspmd and q.shape[1] == 1
            and decode_flash_available(k.shape[1], q.shape[3])):
        return decode_attention(q, k, v, lengths)
    return dense_attention(q, k, v, mask)


# --- block-table (paged) decode attention ----------------------------------
#
# The serving engine's KV lives in a shared pool stored HEAD-MAJOR,
# [num_blocks, n_kv, block_size, D] (kv_blocks' page layout); each batch
# row owns an i32[max_blocks] table naming its blocks in sequence order.
# The decode kernel below runs one grid step a batch row and walks only
# the row's live pages: a page of every KV head is one contiguous
# [n_kv, block_size, D] slab of the pool, copied where it lies (the pool
# is the pallas_call's operand as stored: nothing of the pool's shape is
# copied in front of a call), and rows sharing a prefix read the same
# physical blocks. No reference counterpart — the reference hands paging
# to the vLLM subprocess (vllm.go:93-112); vLLM's PagedAttention (Kwon
# et al. 2023) is the design source.
#
# Table contract: EVERY entry of every row — including entries past the
# row's live blocks — must be a valid pool index (the host pads with the
# reserved null block 0). The kernel never reads an entry past a row's
# live pages (except for rows shorter than the window, which walk the
# whole table), but the twin gathers them unconditionally.


def _pages_to_walk(row_len, n_q, block_size, n_blocks, xp=jnp):
    """Pages the block kernel walks for a row of ``row_len`` live
    positions under an ``n_q``-query window: the live pages, or the
    whole table for rows shorter than the window (the kernel's dense
    fallback, see _decode_blocks_kernel). Elementwise over arrays, so
    the host counts with ``xp=np`` what the kernel walks with jnp."""
    live = xp.minimum((row_len + block_size - 1) // block_size, n_blocks)
    return xp.where(row_len < n_q, n_blocks, live)


def decode_blocks_pages(lengths, n_q: int, block_size: int,
                        n_blocks: int) -> int:
    """Pages one decode_attention_blocks call walks, summed over the
    batch's rows, from the ``lengths`` it is handed: host arithmetic
    (no device work), the kubeinfer_engine_attn_pages_total counter's
    unit."""
    lens = np.asarray(lengths, np.int64)
    return int(_pages_to_walk(lens, n_q, block_size, n_blocks, xp=np).sum())


def _decode_blocks_kernel(
    tbl_ref,  # scalar-prefetch i32[B, max_blocks]: per-row block tables
    len_ref,  # scalar-prefetch i32[B]: per-row live lengths
    q_ref,  # [1, n_kv, T*G, D] — the row's T queries, (t, group) rows
    k_hbm,  # [num_blocks, n_kv, block_size, D] pool, left in HBM
    v_hbm,  # [num_blocks, n_kv, block_size, D]
    o_ref,  # [1, n_kv, T*G, D] out
    k_buf,  # [2, n_kv, block_size, D]: two pages in flight
    v_buf,  # [2, n_kv, block_size, D]
    sem,  # DMA semaphores [2 sides, 2 buffers]
    base_ref,  # SMEM i32[1]: pages fetched by earlier rows
    m_scr,  # f32[n_kv, T*G, 1]
    l_scr,  # f32[n_kv, T*G, 1]
    acc_scr,  # f32[n_kv, T*G, D]
    *,
    groups: int,
    scale: float,
    n_blocks: int,
    block_size: int,
    n_kv: int,
    n_q: int,
):
    """One grid step a batch row: fold the row's live pages, in
    ascending order, for every KV head with the shared fold, the
    penalty derived from the LOGICAL position i * block_size + j. The
    walk's length comes from the scalar-prefetched row length
    (_pages_to_walk), so the kernel's cost follows the pages a step
    must read, not B * n_kv * max_blocks grid steps. Pages past the
    live length are not walked: folded, they would contribute exactly
    0 (the twin folds them; see _softmax_fold's active note), so the
    output is the twin's bit for bit.

    Pages are double-buffered across rows, not only within one: the
    grid runs in order on one core, so the copy of the next page —
    the row's own, or the first of the next row's — starts before the
    current page is folded, and buffer (base + i) % 2 holds the row's
    i-th page, ``base`` counting the pages earlier rows fetched.

    ``n_q`` > 1 is the speculative verify window: query t of a row sits
    at logical position row_len - n_q + t (the window's tokens are the
    cache's LAST n_q positions, scattered by the caller before the
    read), so the causal mask within the window is the only new math —
    pen row t admits s_pos <= row_len - n_q + t, which at n_q == 1
    reduces exactly to the decode rule s_pos < row_len. The live page
    set is unchanged (the last query attends precisely s_pos <
    row_len) — EXCEPT rows with row_len < n_q, whose leading queries
    are fully masked: their uniform-over-junk output depends on every
    page the twin folds, so they walk the whole (null-padded) table
    (identical at n_q == 1 to the row_len == 0 rule; the engine never
    emits such rows, since a dispatch sets lengths = offset + T, but
    the twin contract must hold on the whole operand domain)."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    row_len = len_ref[b]
    n_walk = _pages_to_walk(row_len, n_q, block_size, n_blocks)

    def copies(row, i, buf):
        blk = tbl_ref[row, i]
        return (
            pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[buf],
                                  sem.at[0, buf]),
            pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[buf],
                                  sem.at[1, buf]),
        )

    @pl.when(b == 0)
    def _prologue():
        base_ref[0] = 0
        for c in copies(0, 0, 0):
            c.start()

    base = base_ref[0]
    m_scr[:] = jnp.full_like(m_scr, -1e30)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    def page(i, carry):
        buf = jax.lax.rem(base + i, 2)
        nxt = 1 - buf

        @pl.when(i + 1 < n_walk)
        def _own_next():
            for c in copies(b, i + 1, nxt):
                c.start()

        @pl.when((i + 1 == n_walk) & (b + 1 < n_rows))
        def _next_row():
            for c in copies(b + 1, 0, nxt):
                c.start()

        for c in copies(b, i, buf):
            c.wait()
        s_pos = i * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (n_q, block_size), 1
        )
        q_pos = row_len - n_q + jax.lax.broadcasted_iota(
            jnp.int32, (n_q, block_size), 0
        )
        pen = jnp.where(s_pos <= q_pos, 0.0, -1e30)
        for h in range(n_kv):
            m_new, l_new, acc_new = _fold_tile_math(
                q_ref[0, h], k_buf[buf, h], v_buf[buf, h], pen,
                m_scr[h], l_scr[h], acc_scr[h],
                groups=groups, scale=scale,
            )
            l_scr[h] = l_new
            acc_scr[h] = acc_new
            m_scr[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_walk, page, 0)
    base_ref[0] = base + n_walk
    for h in range(n_kv):
        o_ref[0, h] = (acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)).astype(
            o_ref.dtype
        )


# jitted so that a step program lowers the kernel once, not once a layer:
# Pallas lowers every call site of a pallas_call anew (as quant_matmul)
@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention_blocks(
    q: jax.Array,  # [B, T, n_heads, D] — the row's last T tokens
    k_pool: jax.Array,  # [num_blocks, n_kv, block_size, D] shared pool
    v_pool: jax.Array,  # [num_blocks, n_kv, block_size, D]
    block_tables: jax.Array,  # i32[B, max_blocks]: pool indices, seq order
    lengths: jax.Array,  # i32[B]: live entries per row (offset + T)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode attention: row b's logical cache position p lives in
    pool block block_tables[b, p // block_size] at slot p % block_size.
    Both scalar operands prefetch; each grid step is one row, which
    copies only its live pages (every KV head of a page in one DMA)
    through the table, so the call's cost follows the live pages, not
    the table's width. T > 1 is the speculative verify window: query t
    attends cache positions <= lengths[b] - T + t (the window occupies
    the row's last T live positions, already scattered into the pool by
    the caller), folded causally inside the kernel's penalty — the T
    axis rides the MXU row dim next to the GQA groups, so the page walk
    is the T == 1 kernel's unchanged. Twin: decode_attention_blocks_jnp
    (bit-identical, parity-tested in tests/test_flash_attention.py)."""
    B, T, n_heads, D = q.shape
    block_size, n_kv, _ = page_dims(k_pool)
    max_blocks = block_tables.shape[1]
    G = n_heads // n_kv

    # (t, group) flatten with t OUTER within a head: _fold_tile_math
    # reshapes rows as (tq, groups, sk) + pen[:, None, :], so pen row t
    # must cover the contiguous run of G MXU rows belonging to query t.
    qf = q.reshape(B, T, n_kv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, n_kv, T * G, D
    )
    tbl = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)

    q_spec = pl.BlockSpec(
        (1, n_kv, T * G, D), lambda b, tbl_ref, lens_ref: (b, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(
            _decode_blocks_kernel, groups=G, scale=1.0 / float(D) ** 0.5,
            n_blocks=max_blocks, block_size=block_size, n_kv=n_kv,
            n_q=T,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[q_spec, pool_spec, pool_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((2, n_kv, block_size, D), k_pool.dtype),
                pltpu.VMEM((2, n_kv, block_size, D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((n_kv, T * G, 1), jnp.float32),
                pltpu.VMEM((n_kv, T * G, 1), jnp.float32),
                pltpu.VMEM((n_kv, T * G, D), jnp.float32),
            ],
        ),
        # the rows run in order on one core: a row's first page is
        # fetched by the row before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        out_shape=jax.ShapeDtypeStruct((B, n_kv, T * G, D), q.dtype),
        interpret=interpret,
        name="decode_attention_blocks",
    )(tbl, lens, qf, k_pool, v_pool)
    return out.reshape(B, n_kv, T, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, T, n_heads, D
    )


def decode_attention_blocks_jnp(
    q: jax.Array,  # [B, T, n_heads, D]
    k_pool: jax.Array,  # [num_blocks, n_kv, block_size, D]
    v_pool: jax.Array,
    block_tables: jax.Array,  # i32[B, max_blocks]
    lengths: jax.Array,  # i32[B]
) -> jax.Array:
    """The block kernel's jnp twin: the SAME _fold_tile_math walked
    per-(row, head) with lax.map and per-block with lax.scan, gathering
    each tile through the row's table as the kernel's page copies do,
    but over the whole table — pages past the live length, which the
    kernel never walks, fold exactly 0 (see decode_attention_jnp's
    note). T > 1 mirrors the kernel's in-window
    causal penalty (query t at logical position rl - T + t). Because a
    gathered block holds the same values as the linear cache's
    corresponding tile, this twin is also bitwise equal to
    decode_attention_jnp(tile_s=block_size) on the gathered cache —
    parity-tested both ways."""
    B, T, n_heads, D = q.shape
    block_size, n_kv, _ = page_dims(k_pool)
    max_blocks = block_tables.shape[1]
    G = n_heads // n_kv
    BH = B * n_kv
    scale = 1.0 / float(D) ** 0.5

    # Same (t, group) row order as the kernel's qf flatten.
    qf = q.reshape(B, T, n_kv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        BH, T * G, D
    )
    tbl = jnp.asarray(block_tables, jnp.int32)
    row_tbl = jnp.repeat(tbl, n_kv, axis=0)  # [BH, max_blocks]
    row_head = jnp.tile(jnp.arange(n_kv, dtype=jnp.int32), B)  # [BH]
    row_len = jnp.repeat(jnp.asarray(lengths, jnp.int32), n_kv)  # [BH]

    def _row(args):
        qr, trow, h, rl = args  # [T*G, D], i32[max_blocks], i32, i32

        def step(carry, ts):
            m, l, acc = carry
            k_t = k_pool[trow[ts], h]  # [bs, D]: the kernel's tile
            v_t = v_pool[trow[ts], h]
            s_pos = ts * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (T, block_size), 1
            )
            q_pos = rl - T + jax.lax.broadcasted_iota(
                jnp.int32, (T, block_size), 0
            )
            pen = jnp.where(s_pos <= q_pos, 0.0, -1e30)
            return _fold_tile_math(
                qr, k_t, v_t, pen, m, l, acc, groups=G, scale=scale
            ), None

        init = (
            jnp.full((T * G, 1), -1e30, jnp.float32),
            jnp.zeros((T * G, 1), jnp.float32),
            jnp.zeros((T * G, D), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            step, init, jnp.arange(max_blocks, dtype=jnp.int32)
        )
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    out = jax.lax.map(_row, (qf, row_tbl, row_head, row_len))
    return out.reshape(B, n_kv, T, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, T, n_heads, D
    )


def gather_block_kv(pool: jax.Array, block_tables: jax.Array) -> jax.Array:
    """[num_blocks, n_kv, bs, D] pool -> [B, max_blocks * bs, n_kv, D]
    per-row linear view through the tables — the dense-fallback (and
    warm-prefill) materialization of what the block kernel reads
    in-place. One XLA gather and a transpose of the GATHERED pages (the
    cost follows the rows, never the pool); rows sharing blocks
    duplicate them here, which is exactly the copy the paged kernel
    exists to avoid."""
    bs, n_kv, D = page_dims(pool)
    B, M = block_tables.shape
    return pages_to_rows(pool[block_tables]).reshape(B, M * bs, n_kv, D)


def decode_blocks_available(block_size: int, D: int) -> bool:
    """Shapes the block kernel handles on the current default backend —
    decode_flash_available's contract with S replaced by the pool's
    block_size (each tile is one block, so the block itself must be
    lane-aligned). Small-block test configs route to the gather+dense
    fallback."""
    return (
        jax.default_backend() == "tpu"
        and block_size % 128 == 0
        and block_size >= 128
        and D % 64 == 0
    )


def decode_attention_blocks_auto(q, k_pool, v_pool, block_tables, lengths,
                                 mask, gspmd=False):
    """Paged decode-step router: the block-table Pallas kernel when
    shapes/backend allow, gather-through-the-table + dense jnp over
    ``mask`` otherwise. The flash branch never reads ``mask`` (XLA
    dead-code-eliminates its construction); ``lengths`` and ``mask``
    must describe the same live set, per decode_attention_auto.

    ``gspmd=True`` pins the gather+dense branch (the sharded engine's
    route): the table gather indexes the pool's REPLICATED num_blocks
    axis, so with the pool sharded along n_kv each device gathers its
    own heads' slice of the named blocks through the same host i32
    tables, and the dense einsum partitions over heads — whereas the
    block kernel is a custom call GSPMD cannot split (see
    decode_attention_auto).

    Any T >= 1 routes to the kernel: T > 1 is the speculative verify
    window, whose in-window causal rule the kernel derives from
    ``lengths`` alone — ``mask`` must equal that rule
    (mask[b, t, s] = s <= lengths[b] - T + t) for the branches to
    agree."""
    if (not gspmd) and decode_blocks_available(
        page_dims(k_pool)[0], q.shape[3]
    ):
        return decode_attention_blocks(
            q, k_pool, v_pool, block_tables, lengths
        )
    return dense_attention(
        q,
        gather_block_kv(k_pool, block_tables),
        gather_block_kv(v_pool, block_tables),
        mask,
    )


# --- int8 (quantized pool) block-table decode attention --------------------
#
# kv_dtype="int8" splits each pool side into three tensors: int8 pages
# [num_blocks, n_kv, bs, D], f32 scales [num_blocks, n_kv] (symmetric
# per-block-per-head, kv_blocks.quantize_blocks), and a per-slot bf16
# TAIL [n_slots, 2, n_kv, bs, D] (two pages a slot, the pool's layout)
# holding the row's current partial
# block plus the one a verify window can spill into (n_emit <= k+1 <
# block_size bounds a window to ONE boundary crossing). Dequantization
# happens HERE, next to the table gather — committed blocks never
# round-trip to bf16 in HBM — while tiles at or past the row's tail
# base (lengths - T) // bs read the bf16 tail verbatim, so the partial
# block is bit-exact until the stepper commits it
# (stepper._commit_full_tails). Scales ride the scalar-prefetch SMEM
# path as f32 (one scalar per (bh, ts) grid step). They must NOT travel
# as i32 bits: Mosaic's tpu.bitcast takes vectors only, so a scalar
# bitcast back to f32 passes interpret mode and is refused on the chip.


def _dequant_tile(kq, scale, tail, use_tail, out_dtype):
    """One tile's effective K (or V): dequantized int8 page, or the
    bf16 tail verbatim when ``use_tail``. Shared verbatim by the q8
    kernel and its jnp twin — the bit-identity contract runs through
    this function exactly as the fold runs through _fold_tile_math."""
    deq = kq.astype(jnp.float32) * scale
    return jnp.where(use_tail, tail.astype(jnp.float32), deq).astype(
        out_dtype
    )


def _decode_blocks_q8_kernel(
    tbl_ref,  # scalar-prefetch i32[B, max_blocks]
    len_ref,  # scalar-prefetch i32[B]
    tb_ref,  # scalar-prefetch i32[B]: first tail-resident block per row
    ks_ref,  # scalar-prefetch f32[B*n_kv, max_blocks]: K scale per tile
    vs_ref,  # scalar-prefetch f32[B*n_kv, max_blocks]
    q_ref,  # [1, T*G, D]
    k_ref,  # [1, 1, block_size, D] int8 pool tile
    v_ref,  # [1, 1, block_size, D] int8
    kt_ref,  # [1, 1, 1, block_size, D] bf16 tail tile
    vt_ref,  # [1, 1, 1, block_size, D]
    o_ref,  # [1, T*G, D] out
    m_scr,
    l_scr,
    acc_scr,
    *,
    groups: int,
    scale: float,
    n_blocks: int,
    block_size: int,
    n_kv: int,
    n_q: int,
):
    """_decode_blocks_kernel with the dequant-or-tail select spliced in
    front of the fold; init/skip/penalty/finish are carried over
    unchanged (the quantized pool changes tile VALUES, never the walk).
    Dead tiles still fold exactly 0 whatever junk they dequantize to —
    int8 * finite scale is always finite — so the clamp-elision story
    survives quantization untouched."""
    del tbl_ref  # consumed by the BlockSpec index_maps, not the body
    bh = pl.program_id(0)
    row_len = len_ref[bh // n_kv]
    ts = pl.program_id(1)

    @pl.when(ts == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when((ts == 0) | (row_len < n_q) | (ts * block_size < row_len))
    def _fold():
        use_tail = ts >= tb_ref[bh // n_kv]
        k_eff = _dequant_tile(
            k_ref[0, 0], ks_ref[bh, ts], kt_ref[0, 0, 0], use_tail,
            o_ref.dtype,
        )
        v_eff = _dequant_tile(
            v_ref[0, 0], vs_ref[bh, ts], vt_ref[0, 0, 0], use_tail,
            o_ref.dtype,
        )
        s_pos = ts * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (n_q, block_size), 1
        )
        q_pos = row_len - n_q + jax.lax.broadcasted_iota(
            jnp.int32, (n_q, block_size), 0
        )
        pen = jnp.where(s_pos <= q_pos, 0.0, -1e30)
        m_new, l_new, acc_new = _fold_tile_math(
            q_ref[0], k_eff, v_eff, pen,
            m_scr[:], l_scr[:], acc_scr[:],
            groups=groups, scale=scale,
        )
        l_scr[:] = l_new
        acc_scr[:] = acc_new
        m_scr[:] = m_new

    @pl.when(ts == n_blocks - 1)
    def _finish():
        o_ref[0] = (acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)).astype(
            o_ref.dtype
        )


def decode_attention_blocks_q8(
    q: jax.Array,  # [B, T, n_heads, D]
    k_pool: jax.Array,  # int8[num_blocks, n_kv, block_size, D]
    v_pool: jax.Array,  # int8
    k_scales: jax.Array,  # f32[num_blocks, n_kv]
    v_scales: jax.Array,  # f32[num_blocks, n_kv]
    k_tail: jax.Array,  # [B, 2, n_kv, block_size, D] bf16 partial blocks
    v_tail: jax.Array,
    block_tables: jax.Array,  # i32[B, max_blocks]
    lengths: jax.Array,  # i32[B] live entries per row (offset + T)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Paged decode attention over the quantized pool. Tile walk, DMA
    clamp, and penalty are decode_attention_blocks'; the new operands
    are the two scale rows (gathered through the table at trace time —
    ONE f32 per folded tile — and prefetched to SMEM) and
    the per-row tails, whose BlockSpec resolves tile ts to tail slot
    clip(ts - tail_base, 0, 1). tail_base is derived from ``lengths``
    (the window START block (lengths - T) // bs), not passed, so the
    kernel and every caller agree on it by construction. Twin:
    decode_attention_blocks_q8_jnp (bit-identical — parity-tested in
    tests/test_kv_quant.py)."""
    B, T, n_heads, D = q.shape
    block_size, n_kv, _ = page_dims(k_pool)
    max_blocks = block_tables.shape[1]
    G = n_heads // n_kv

    qf = q.reshape(B, T, n_kv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B * n_kv, T * G, D
    )
    tbl = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    tb = jnp.maximum(lens - T, 0) // block_size  # i32[B]
    # [B, max_blocks, n_kv] gather -> one scale per (row-head, tile)
    ksb = k_scales[tbl].transpose(0, 2, 1).reshape(B * n_kv, max_blocks)
    vsb = v_scales[tbl].transpose(0, 2, 1).reshape(B * n_kv, max_blocks)

    def _kv_map(bh, ts, tbl_ref, lens_ref, tb_ref, ks_ref, vs_ref,
                n_kv=n_kv, bs=block_size, nq=T):
        # decode_attention_blocks' clamp, verbatim (the scale gather
        # above uses the UNclamped table — dead tiles never fold, so
        # the pair only has to agree on folded tiles, where the clamp
        # is the identity)
        b = bh // n_kv
        rl = lens_ref[b]
        live_last = jnp.maximum(rl - 1, 0) // bs
        step = jnp.where(rl < nq, ts, jnp.minimum(ts, live_last))
        return (tbl_ref[b, step], bh % n_kv, 0, 0)

    def _tail_map(bh, ts, tbl_ref, lens_ref, tb_ref, ks_ref, vs_ref,
                  n_kv=n_kv):
        b = bh // n_kv
        return (b, jnp.clip(ts - tb_ref[b], 0, 1), bh % n_kv, 0, 0)

    q_spec = pl.BlockSpec(
        (1, T * G, D),
        lambda bh, ts, tbl_ref, lens_ref, tb_ref, ks_ref, vs_ref: (
            bh, 0, 0
        ),
        memory_space=pltpu.VMEM,
    )
    kv_spec = pl.BlockSpec(
        (1, 1, block_size, D), _kv_map, memory_space=pltpu.VMEM
    )
    tail_spec = pl.BlockSpec(
        (1, 1, 1, block_size, D), _tail_map, memory_space=pltpu.VMEM
    )
    out = pl.pallas_call(
        functools.partial(
            _decode_blocks_q8_kernel, groups=G,
            scale=1.0 / float(D) ** 0.5,
            n_blocks=max_blocks, block_size=block_size, n_kv=n_kv,
            n_q=T,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B * n_kv, max_blocks),
            in_specs=[q_spec, kv_spec, kv_spec, tail_spec, tail_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((T * G, 1), jnp.float32),
                pltpu.VMEM((T * G, 1), jnp.float32),
                pltpu.VMEM((T * G, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B * n_kv, T * G, D), q.dtype),
        interpret=interpret,
        name="decode_attention_blocks_q8",
    )(tbl, lens, tb, ksb, vsb, qf, k_pool, v_pool, k_tail, v_tail)
    return out.reshape(B, n_kv, T, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, T, n_heads, D
    )


def decode_attention_blocks_q8_jnp(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    k_scales: jax.Array,
    v_scales: jax.Array,
    k_tail: jax.Array,
    v_tail: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
) -> jax.Array:
    """The q8 kernel's jnp twin: decode_attention_blocks_jnp's walk
    with _dequant_tile spliced in front of the fold, mirroring the
    kernel op for op (same clip-to-tail-slot, same cast order). Gathers
    the UNclamped table like the bf16 twin — dead tiles fold exactly 0
    on both sides whatever they dequantize to."""
    B, T, n_heads, D = q.shape
    block_size, n_kv, _ = page_dims(k_pool)
    max_blocks = block_tables.shape[1]
    G = n_heads // n_kv
    BH = B * n_kv
    scale = 1.0 / float(D) ** 0.5

    qf = q.reshape(B, T, n_kv, G, D).transpose(0, 2, 1, 3, 4).reshape(
        BH, T * G, D
    )
    tbl = jnp.asarray(block_tables, jnp.int32)
    lens = jnp.asarray(lengths, jnp.int32)
    tb = jnp.maximum(lens - T, 0) // block_size
    ksb = k_scales[tbl].transpose(0, 2, 1).reshape(BH, max_blocks)
    vsb = v_scales[tbl].transpose(0, 2, 1).reshape(BH, max_blocks)
    row_tbl = jnp.repeat(tbl, n_kv, axis=0)
    row_head = jnp.tile(jnp.arange(n_kv, dtype=jnp.int32), B)
    row_len = jnp.repeat(lens, n_kv)
    row_tb = jnp.repeat(tb, n_kv)
    row_b = jnp.repeat(jnp.arange(B, dtype=jnp.int32), n_kv)

    def _row(args):
        qr, trow, h, rl, tbase, b, ks_row, vs_row = args

        def step(carry, ts):
            m, l, acc = carry
            use_tail = ts >= tbase
            rel = jnp.clip(ts - tbase, 0, 1)
            k_eff = _dequant_tile(
                k_pool[trow[ts], h], ks_row[ts], k_tail[b, rel, h],
                use_tail,
                q.dtype,
            )
            v_eff = _dequant_tile(
                v_pool[trow[ts], h], vs_row[ts], v_tail[b, rel, h],
                use_tail,
                q.dtype,
            )
            s_pos = ts * block_size + jax.lax.broadcasted_iota(
                jnp.int32, (T, block_size), 1
            )
            q_pos = rl - T + jax.lax.broadcasted_iota(
                jnp.int32, (T, block_size), 0
            )
            pen = jnp.where(s_pos <= q_pos, 0.0, -1e30)
            return _fold_tile_math(
                qr, k_eff, v_eff, pen, m, l, acc, groups=G, scale=scale
            ), None

        init = (
            jnp.full((T * G, 1), -1e30, jnp.float32),
            jnp.zeros((T * G, 1), jnp.float32),
            jnp.zeros((T * G, D), jnp.float32),
        )
        (m, l, acc), _ = jax.lax.scan(
            step, init, jnp.arange(max_blocks, dtype=jnp.int32)
        )
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    out = jax.lax.map(
        _row, (qf, row_tbl, row_head, row_len, row_tb, row_b, ksb, vsb)
    )
    return out.reshape(B, n_kv, T, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, T, n_heads, D
    )


def dequant_gather_block_kv(pool, scales, tail, block_tables, tail_base):
    """gather_block_kv for the quantized pool: dequantize the gathered
    pages (bitwise kv_blocks.dequantize_blocks' math) and overlay the
    two tail-resident tiles verbatim, returning the [B, max_blocks*bs,
    n_kv, D] linear view in the tail's (compute) dtype. The dense
    fallback AND the GSPMD route: every op here partitions over n_kv
    (pages axis 1, scales axis 1, tail axis 2), the gathers index only
    replicated axes."""
    bs, n_kv, D = page_dims(pool)
    B, M = block_tables.shape
    deq = dequantize_blocks(
        pool[block_tables], scales[block_tables]
    )  # [B, M, n_kv, bs, D] f32
    rel = jnp.arange(M, dtype=jnp.int32)[None, :] - tail_base[:, None]
    use_tail = (rel >= 0) & (rel < 2)
    tg = tail[jnp.arange(B)[:, None], jnp.clip(rel, 0, 1)]
    out = jnp.where(
        use_tail[:, :, None, None, None], tg.astype(jnp.float32), deq
    )
    return pages_to_rows(out.astype(tail.dtype)).reshape(
        B, M * bs, n_kv, D
    )


def decode_attention_blocks_q8_auto(
    q, k_pool, v_pool, k_scales, v_scales, k_tail, v_tail,
    block_tables, lengths, mask, gspmd=False,
):
    """Quantized-pool twin of decode_attention_blocks_auto: the q8
    Pallas kernel when shapes/backend allow, dequantize-gather + dense
    jnp over ``mask`` otherwise (and always under ``gspmd`` — same
    custom-call constraint). Same lengths/mask live-set contract; the
    tail base both branches derive is (lengths - T) // block_size."""
    T = q.shape[1]
    block_size = page_dims(k_pool)[0]
    if (not gspmd) and decode_blocks_available(block_size, q.shape[3]):
        return decode_attention_blocks_q8(
            q, k_pool, v_pool, k_scales, v_scales, k_tail, v_tail,
            block_tables, lengths,
        )
    tb = jnp.maximum(jnp.asarray(lengths, jnp.int32) - T, 0) // block_size
    return dense_attention(
        q,
        dequant_gather_block_kv(
            k_pool, k_scales, k_tail, block_tables, tb
        ),
        dequant_gather_block_kv(
            v_pool, v_scales, v_tail, block_tables, tb
        ),
        mask,
    )


# --- backward (recompute-based custom_vjp over the ragged kernel) ----------


def _ragged_pen(c0, row_len, tq, ts, tile_t, tile_s):
    """The ragged causal penalty tile, shared by the lse-forward and both
    backward kernels — identical mask derivation is what makes the
    recomputed probabilities match the forward bit-for-bit."""
    q_pos = (
        c0 + tq * tile_t
        + jax.lax.broadcasted_iota(jnp.int32, (tile_t, tile_s), 0)
    )
    s_pos = ts * tile_s + jax.lax.broadcasted_iota(
        jnp.int32, (tile_t, tile_s), 1
    )
    attend = (s_pos <= q_pos) & (s_pos < row_len)
    return jnp.where(attend, 0.0, -1e30)


def _flash_ragged_lse_kernel(
    c0_ref, len_ref,  # len_ref: SMEM i32[B], indexed in-kernel
    q_ref, k_ref, v_ref,
    o_ref,
    lse_ref,  # [1, TILE_T * G, 1] out: per-row logsumexp (m + log l)
    m_scr, l_scr, acc_scr,
    *, groups: int, scale: float, s_tiles: int, tile_t: int, tile_s: int,
    n_kv: int,
):
    """The ragged forward, additionally emitting the logsumexp the
    backward's probability recompute needs. Identical o math to
    _flash_ragged_kernel (same _softmax_fold) — custom_vjp requires the
    fwd path to reproduce the primal's output exactly."""
    tq = pl.program_id(1)
    ts = pl.program_id(2)
    row_len = len_ref[pl.program_id(0) // n_kv]
    pen = _ragged_pen(c0_ref[0], row_len, tq, ts, tile_t, tile_s)
    _softmax_fold(
        q_ref, k_ref, v_ref, pen, o_ref, m_scr, l_scr, acc_scr,
        groups=groups, scale=scale, s_tiles=s_tiles,
        active=_ragged_tile_active(
            c0_ref[0], row_len, tq, ts, tile_t, tile_s
        ),
    )

    @pl.when(ts == s_tiles - 1)
    def _emit_lse():
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _recompute_p(q, k, pen, lse_col, row_len, groups, scale):
    """[TqG, Sk] softmax probabilities from (q, k, L): exp(qk*scale +
    pen - L). Exact — L is the forward's converged logsumexp. A fully
    masked row (row_len == 0) is degenerate: s and L both saturate at
    -1e30 in f32 so exp(s - L) would be 1 per slot, not 0 — gate to 0 so
    dq/dk/dv for such rows vanish like the dense path's."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    tq, sk = pen.shape
    s = (s.reshape(tq, groups, sk) + pen[:, None, :]).reshape(
        tq * groups, sk
    )
    return jnp.where(row_len > 0, jnp.exp(s - lse_col), 0.0)


def _flash_bwd_dq_kernel(
    c0_ref, len_ref,
    q_ref, k_ref, v_ref, do_ref,  # [1, TqG, D] / [1, Sk, D] blocks
    lse_ref,  # [1, TqG]
    drow_ref,  # [1, TqG] rowsum(dO * O)
    dq_ref,  # [1, TqG, D] out
    dq_scr,  # f32[TqG, D] scratch
    *, groups: int, scale: float, s_tiles: int, tile_t: int, tile_s: int,
    n_kv: int,
):
    tq = pl.program_id(1)
    ts = pl.program_id(2)  # innermost: S sweep, dq resident

    @pl.when(ts == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    row_len = len_ref[pl.program_id(0) // n_kv]

    # Fully-masked tiles contribute exactly 0 (p underflows to 0 against
    # the row-global lse; row_len == 0 rows are gated to p == 0 inside
    # _recompute_p), so skipping them is bit-identical — same causal
    # upper-triangle VPU saving as the forward's _ragged_tile_active.
    @pl.when(
        (ts * tile_s <= c0_ref[0] + (tq + 1) * tile_t - 1)
        & (ts * tile_s < row_len)
    )
    def _accum():
        pen = _ragged_pen(c0_ref[0], row_len, tq, ts, tile_t, tile_s)
        p = _recompute_p(
            q_ref[0], k_ref[0], pen, lse_ref[0], row_len, groups, scale
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [TqG, Sk]
        ds = p * (dp - drow_ref[0])
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(ts == s_tiles - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    c0_ref, len_ref,
    q_ref, k_ref, v_ref, do_ref,
    lse_ref, drow_ref,
    dk_ref, dv_ref,  # [1, Sk, D] out
    dk_scr, dv_scr,  # f32[Sk, D] scratch
    *, groups: int, scale: float, t_tiles: int, tile_t: int, tile_s: int,
    n_kv: int,
):
    ts = pl.program_id(1)
    tq = pl.program_id(2)  # innermost: T sweep, dk/dv resident

    @pl.when(tq == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    row_len = len_ref[pl.program_id(0) // n_kv]

    # same provably-zero-tile skip as the dq kernel (grid here is
    # (bn, ts, tq), so the guard reads the swapped program ids)
    @pl.when(
        (ts * tile_s <= c0_ref[0] + (tq + 1) * tile_t - 1)
        & (ts * tile_s < row_len)
    )
    def _accum():
        pen = _ragged_pen(c0_ref[0], row_len, tq, ts, tile_t, tile_s)
        p = _recompute_p(
            q_ref[0], k_ref[0], pen, lse_ref[0], row_len, groups, scale
        )
        # dv += p^T dO; the folded (t, g) rows make the GQA group
        # reduction implicit in the row contraction
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - drow_ref[0])
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(tq == t_tiles - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _check_diff_tiles(T, S, tile_t, tile_s):
    if T % tile_t or S % tile_s:
        raise ValueError(
            f"flash_attention_causal_diff needs T divisible by {tile_t} "
            f"and S by {tile_s}; got T={T} S={S} (use the dense path for "
            "unaligned shapes)"
        )


def _fold_qlike(x, n_kv):
    """[B, T, n_heads, D] -> [B*n_kv, T*G, D] (the kernels' row fold)."""
    B, T, n_heads, D = x.shape
    G = n_heads // n_kv
    return (
        x.reshape(B, T, n_kv, G, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B * n_kv, T * G, D)
    )


def _unfold_qlike(x, B, n_kv, T, G, D):
    return (
        x.reshape(B, n_kv, T, G, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, T, n_kv * G, D)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def flash_attention_causal_diff(interpret, q, k, v, q_offset, row_lens):
    """Differentiable ragged-causal flash attention.

    Primal = flash_attention_ragged (bit-identical); under jax.grad the
    fwd re-runs with the lse output and the bwd runs the recompute
    kernels. ``interpret`` is a nondiff static for CPU parity tests.
    """
    # tile sizes resolved at CALL time from the module globals — the
    # vjp fwd below reads the same globals, so primal and fwd always
    # tile (and therefore accumulate) identically, even under tests
    # that monkeypatch TILE_T/TILE_S
    return flash_attention_ragged(
        q, k, v, q_offset, row_lens,
        tile_t=TILE_T, tile_s=TILE_S, interpret=interpret,
    )


def _diff_fwd(interpret, q, k, v, q_offset, row_lens):
    B, T, n_heads, D = q.shape
    S, n_kv = k.shape[1], k.shape[2]
    G = n_heads // n_kv
    tile_t, tile_s = _prefill_tiles(q, k, TILE_T, TILE_S)
    _check_diff_tiles(T, S, tile_t, tile_s)
    t_tiles, s_tiles = T // tile_t, S // tile_s
    qf = _fold_qlike(q, n_kv)
    kf = k.transpose(0, 2, 1, 3).reshape(B * n_kv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * n_kv, S, D)
    c0 = jnp.asarray(q_offset, jnp.int32).reshape(1)
    lens = jnp.asarray(row_lens, jnp.int32)
    kern = functools.partial(
        _flash_ragged_lse_kernel, groups=G, scale=1.0 / float(D) ** 0.5,
        s_tiles=s_tiles, tile_t=tile_t, tile_s=tile_s, n_kv=n_kv,
    )
    smem1 = pl.BlockSpec(
        (1,), lambda bh, tq, ts: (0,), memory_space=pltpu.SMEM
    )
    smem_b = pl.BlockSpec(
        (B,), lambda bh, tq, ts: (0,), memory_space=pltpu.SMEM
    )
    qspec = pl.BlockSpec(
        (1, tile_t * G, D), lambda bh, tq, ts: (bh, tq, 0),
        memory_space=pltpu.VMEM,
    )
    kspec = pl.BlockSpec(
        (1, tile_s, D), lambda bh, tq, ts: (bh, ts, 0),
        memory_space=pltpu.VMEM,
    )
    out, lse = pl.pallas_call(
        kern,
        grid=(B * n_kv, t_tiles, s_tiles),
        in_specs=[smem1, smem_b, qspec, kspec, kspec],
        out_specs=[
            qspec,
            pl.BlockSpec(
                (1, tile_t * G, 1), lambda bh, tq, ts: (bh, tq, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * n_kv, T * G, D), q.dtype),
            jax.ShapeDtypeStruct((B * n_kv, T * G, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_t * G, 1), jnp.float32),
            pltpu.VMEM((tile_t * G, 1), jnp.float32),
            pltpu.VMEM((tile_t * G, D), jnp.float32),
        ],
        interpret=interpret,
    )(c0, lens, qf, kf, vf)
    o = _unfold_qlike(out, B, n_kv, T, G, D)
    return o, (q, k, v, c0, lens, out, lse)


def _diff_bwd(interpret, res, do):
    q, k, v, c0, lens, of, lse = res
    B, T, n_heads, D = q.shape
    S, n_kv = k.shape[1], k.shape[2]
    G = n_heads // n_kv
    tile_t, tile_s = _prefill_tiles(q, k, TILE_T, TILE_S)
    t_tiles, s_tiles = T // tile_t, S // tile_s
    scale = 1.0 / float(D) ** 0.5
    qf = _fold_qlike(q, n_kv)
    kf = k.transpose(0, 2, 1, 3).reshape(B * n_kv, S, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * n_kv, S, D)
    dof = _fold_qlike(do, n_kv)
    # rowsum(dO * O): cheap fused XLA reduce, shared by both kernels
    drow = jnp.sum(
        dof.astype(jnp.float32) * of.astype(jnp.float32), axis=2,
        keepdims=True,
    )  # [B*n_kv, T*G, 1]

    smem1 = pl.BlockSpec(
        (1,), lambda bh, a, b: (0,), memory_space=pltpu.SMEM
    )
    smem_b = pl.BlockSpec(
        (B,), lambda bh, a, b: (0,), memory_space=pltpu.SMEM
    )

    # dq: grid (bh, tq, ts), S innermost
    q_at_tq = pl.BlockSpec(
        (1, tile_t * G, D), lambda bh, tq, ts: (bh, tq, 0),
        memory_space=pltpu.VMEM,
    )
    kv_at_ts = pl.BlockSpec(
        (1, tile_s, D), lambda bh, tq, ts: (bh, ts, 0),
        memory_space=pltpu.VMEM,
    )
    row_at_tq = pl.BlockSpec(
        (1, tile_t * G, 1), lambda bh, tq, ts: (bh, tq, 0),
        memory_space=pltpu.VMEM,
    )
    dqf = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, groups=G, scale=scale, s_tiles=s_tiles,
            tile_t=tile_t, tile_s=tile_s, n_kv=n_kv,
        ),
        grid=(B * n_kv, t_tiles, s_tiles),
        in_specs=[smem1, smem_b, q_at_tq, kv_at_ts, kv_at_ts, q_at_tq,
                  row_at_tq, row_at_tq],
        out_specs=q_at_tq,
        out_shape=jax.ShapeDtypeStruct((B * n_kv, T * G, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((tile_t * G, D), jnp.float32)],
        interpret=interpret,
    )(c0, lens, qf, kf, vf, dof, lse, drow)

    # dk/dv: grid (bh, ts, tq), T innermost
    q_at_tq2 = pl.BlockSpec(
        (1, tile_t * G, D), lambda bh, ts, tq: (bh, tq, 0),
        memory_space=pltpu.VMEM,
    )
    kv_at_ts2 = pl.BlockSpec(
        (1, tile_s, D), lambda bh, ts, tq: (bh, ts, 0),
        memory_space=pltpu.VMEM,
    )
    row_at_tq2 = pl.BlockSpec(
        (1, tile_t * G, 1), lambda bh, ts, tq: (bh, tq, 0),
        memory_space=pltpu.VMEM,
    )
    dkf, dvf = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, groups=G, scale=scale, t_tiles=t_tiles,
            tile_t=tile_t, tile_s=tile_s, n_kv=n_kv,
        ),
        grid=(B * n_kv, s_tiles, t_tiles),
        in_specs=[smem1, smem_b, q_at_tq2, kv_at_ts2, kv_at_ts2,
                  q_at_tq2, row_at_tq2, row_at_tq2],
        out_specs=[kv_at_ts2, kv_at_ts2],
        out_shape=[
            jax.ShapeDtypeStruct((B * n_kv, S, D), k.dtype),
            jax.ShapeDtypeStruct((B * n_kv, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_s, D), jnp.float32),
            pltpu.VMEM((tile_s, D), jnp.float32),
        ],
        interpret=interpret,
    )(c0, lens, qf, kf, vf, dof, lse, drow)

    dq = _unfold_qlike(dqf, B, n_kv, T, G, D)
    dk = dkf.reshape(B, n_kv, S, D).transpose(0, 2, 1, 3)
    dv = dvf.reshape(B, n_kv, S, D).transpose(0, 2, 1, 3)
    import numpy as _np

    f0 = jax.dtypes.float0
    return (
        dq, dk, dv,
        _np.zeros(jnp.shape(jnp.asarray(0, jnp.int32)), f0),
        _np.zeros(res[4].shape, f0),
    )


flash_attention_causal_diff.defvjp(_diff_fwd, _diff_bwd)


def flash_available(T: int, S: int, D: int) -> bool:
    """Shapes the kernels handle on the current default backend.

    Deliberately conservative: a wrong True here is a Mosaic compile
    error at trace time (there is no catchable fallback once the outer
    jit lowers), so the guard admits only shapes of the class actually
    exercised on hardware — sublane-aligned T, lane-aligned S tiles, and
    the production head dims (64/128/256). Tiny test models (D=16) route
    to the dense path.
    """
    return (
        jax.default_backend() == "tpu"
        and T % 8 == 0
        and T % min(TILE_T, T) == 0
        and S % min(TILE_S, S) == 0
        and S % 128 == 0
        and T >= 8
        and S >= 128
        and D % 64 == 0
    )


def attention_auto(q, k, v, mask):
    """model.attention signature; Pallas kernel when shapes/backend
    allow, dense jnp otherwise. Drop-in for ``forward(attn_fn=...)``."""
    if flash_available(q.shape[1], k.shape[1], q.shape[3]):
        return flash_attention(q, k, v, mask)
    return dense_attention(q, k, v, mask)


def causal_attention_auto(q, k, v, mask):
    """Plain causal self-attention (T == S) with the mask derived
    in-kernel — model.forward's no-cache path binds this so training
    and full-sequence prefill never ship a [B, T, T] tensor to the
    kernel. ``mask`` is the caller's dense-fallback mask: the flash
    branch never reads it and XLA dead-code-eliminates its
    construction (the same contract as engine.chunked_prefill's flash
    branch). Differentiable: the flash branch routes through the
    custom_vjp wrapper, so this binding works under jax.grad (training
    at long context no longer needs the dense path's [T, T] scores)."""
    B, T = q.shape[0], q.shape[1]
    S, D = k.shape[1], q.shape[3]
    if T == S and flash_available(T, S, D):
        return flash_attention_causal_diff(
            False, q, k, v, 0, jnp.full((B,), S, jnp.int32)
        )
    return dense_attention(q, k, v, mask)
