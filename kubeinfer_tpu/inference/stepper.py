"""The one decode stepper: every serving path's token loop lives here.

Three decode loops used to coexist — ``Engine.generate``'s dense-cache
``lax.scan``, ``SPEngine``'s copy of the same call, and
``ContinuousEngine``'s per-token ``_decode_step`` dispatch — divergent
in everything but intent (ROADMAP item 3). This module collapses them:

- :func:`step_forward` is the single-token forward both loops share —
  the dense-cache route (per-row ``[B, S, ...]`` caches) and the paged
  route (shared block pool + ``i32[B, max_blocks]`` tables) differ only
  in which attention reader the trace binds, so the jnp twin and the
  block-table Pallas kernel are reached per-step exactly as before.
- :func:`decode_scan` is the fused fixed-horizon loop the per-request
  and sequence-parallel engines jit (prefill hands it dense caches).
- :func:`decode_window` is the continuous batcher's fused K-step window:
  ONE jitted dispatch runs K steps as a ``lax.scan`` over the donated
  :class:`SlotState` and returns the ``[n_slots, K]`` token matrix.
  K is static — the scheduler picks it from a small bucket set, one
  compiled shape each — so the per-dispatch floor (scheduler pass,
  jit call, readback sync) is paid once per K tokens instead of once
  per token.

Bit-identity across horizons is by construction, not luck: sampling
keys are position-folded (``sample_rows`` folds ``offset + 1``; admit
folds ``prompt_len``), so a fused window draws exactly the noise the
same steps would draw dispatched one at a time — the parity tests pin
K∈{1,2,4,8} against single-step streams, greedy and sampled.

Reference divergence: the reference operator never owns a decode loop —
it delegates stepping wholesale to the vLLM subprocess
(internal/agent/vllm.go:93-112) and multi-step scheduling is vLLM's
internal affair. Our engine owns its schedule, so the window, its
horizon policy, and the host/device overlap are built natively here.
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from kubeinfer_tpu.inference.config import ModelConfig
from kubeinfer_tpu.inference.engine import (
    apply_repetition_penalty,
    filter_logits,
    gumbel_pick,
    gumbel_sample,
    record_seen,
    seen_from_prompt,
)
from kubeinfer_tpu.inference.flash_attention import (
    decode_attention_auto,
    decode_attention_blocks_auto,
    decode_attention_blocks_q8_auto,
)
from kubeinfer_tpu.inference.gdn import init_gdn_state
from kubeinfer_tpu.inference.kv_blocks import (
    page_dims,
    pool_shape,
    quantize_blocks,
)
from kubeinfer_tpu.inference.model import Params, forward
from kubeinfer_tpu.inference.moe import STATS as MOE_STATS

__all__ = [
    "SlotState", "init_slot_state", "layer_caches", "split_layer_caches",
    "add_moe_stats", "sample_rows", "step_forward",
    "decode_body", "decode_window", "decode_scan", "WINDOW_BUCKETS",
    "DraftState", "init_draft_state", "spec_accept", "verify_window",
]

# Static decode-window horizons: one compiled shape each, so the
# scheduler can retune K per pass without ever paying a fresh compile.
# Powers of two keep the set tiny while spanning the useful range — by
# K=8 the dispatch floor is already amortized below the solve time.
WINDOW_BUCKETS = (1, 2, 4, 8)


# --- device state ----------------------------------------------------------


@dataclass
class SlotState:
    """All device-resident decode state (fixed shapes).

    The KV pool is SHARED across slots and stored HEAD-MAJOR (the
    layout the block kernels read in place; kv_blocks' page layout is
    the one place that spells the axis order): row b's logical cache
    position p lives in ``caches_k[l][tables[b, p // bs], :, p % bs]``.
    Block 0 is
    the reserved null block (kv_blocks.NULL_BLOCK): dead table entries
    and retired rows point there, so every gather/scatter index is
    always valid without data-dependent control flow under jit.

    ``kv_dtype="int8"`` (trace-static: ``caches_k[0].dtype``) adds the
    quantized-pool companions: per-(block, head) dequant scales and the
    per-slot bf16 TAIL [B, 2, n_kv, bs, D] (two pages a slot, in the
    pool's layout) — slot 0 is the row's
    current partial block (logical block offset // bs), slot 1 the one
    a verify window may spill into. Decode scatters land in the tail
    (model.decoder_layer), attention overlays it past the committed
    blocks (flash_attention q8 readers), and the window boundary
    quantizes just-filled slot-0 blocks into the pool
    (:func:`_commit_full_tails`). In bf16 mode all four are EMPTY
    lists — valid pytrees that keep every trace byte-identical to the
    pre-quantization engine.

    What a layer caches follows its kind, and this class with
    :func:`init_slot_state`, :func:`layer_caches` and
    :func:`split_layer_caches` is the one place that says so. A
    full-attention layer owns one entry of ``caches_k``/``caches_v``
    (and of the int8 companions): pages, through the slot's table. A
    linear-attention layer (gdn.py) owns one entry of ``gdn_state`` and
    ``gdn_conv``: a fixed-size recurrent state per slot and the
    convolution's last inputs, no pages; an admit starts them from zero
    and rows that are not decoding leave them untouched. Models without
    such layers have none of these leaves, and models without routed
    experts no ``moe_stats``, so their programs are what they were."""

    caches_k: list[jax.Array]  # per full layer [num_blocks, n_kv, bs, D]
    caches_v: list[jax.Array]
    tables: jax.Array  # i32[B, max_blocks] pool indices, seq order
    last_token: jax.Array  # i32[B]
    offset: jax.Array  # i32[B] next cache position (= current length)
    active: jax.Array  # bool[B]
    temperature: jax.Array  # f32[B]; <=0 = greedy
    top_k: jax.Array  # i32[B]; <1 = disabled
    top_p: jax.Array  # f32[B]; >=1 = disabled
    rep_penalty: jax.Array  # f32[B]; 1.0 = disabled
    seen: jax.Array  # bool[B, V] ids in prompt or generated so far
    rng: jax.Array  # u32[B, 2] per-slot PRNG key data
    scales_k: list[jax.Array]  # int8: L x f32[num_blocks, n_kv]; else []
    scales_v: list[jax.Array]
    tails_k: list[jax.Array]  # int8: L x [B, 2, n_kv, bs, D]; else []
    tails_v: list[jax.Array]
    # per linear-attention layer; [] for models that have none
    gdn_state: list[jax.Array] = dataclasses.field(default_factory=list)
    gdn_conv: list[jax.Array] = dataclasses.field(default_factory=list)
    # routed experts: [u32[len(moe.STATS)]] summed over every MoE call
    # so far (wraps; the host reads differences), [] for dense models
    moe_stats: list[jax.Array] = dataclasses.field(default_factory=list)


jax.tree_util.register_dataclass(
    SlotState,
    data_fields=["caches_k", "caches_v", "tables", "last_token", "offset",
                 "active", "temperature", "top_k", "top_p", "rep_penalty",
                 "seen", "rng", "scales_k", "scales_v", "tails_k",
                 "tails_v", "gdn_state", "gdn_conv", "moe_stats"],
    meta_fields=[],
)


def init_slot_state(cfg: ModelConfig, n_slots: int, cache_len: int,
                    dtype, num_blocks: int, block_size: int,
                    kv_dtype: str = "bf16") -> SlotState:
    """``kv_dtype="bf16"`` stores pool pages in the compute ``dtype``
    (the historical layout — the name is the CLI axis, not the literal
    array dtype, so f32 test engines stay f32); ``"int8"`` stores int8
    pages + f32 scales and allocates the per-slot bf16 tails."""
    L = len(cfg.full_attention_layers)  # the layers that hold pages
    gdn = [init_gdn_state(cfg, n_slots, dtype)
           for i in range(cfg.num_hidden_layers) if cfg.layer_is_linear(i)]
    shape = pool_shape(num_blocks, block_size, cfg.num_key_value_heads,
                       cfg.head_dim)
    if kv_dtype == "int8":
        page_dt = jnp.int8
        sshape = (num_blocks, cfg.num_key_value_heads)
        tshape = (n_slots, *pool_shape(2, block_size,
                                       cfg.num_key_value_heads,
                                       cfg.head_dim))
        scales_k = [jnp.ones(sshape, jnp.float32) for _ in range(L)]
        scales_v = [jnp.ones(sshape, jnp.float32) for _ in range(L)]
        tails_k = [jnp.zeros(tshape, dtype) for _ in range(L)]
        tails_v = [jnp.zeros(tshape, dtype) for _ in range(L)]
    elif kv_dtype == "bf16":
        page_dt = dtype
        scales_k, scales_v, tails_k, tails_v = [], [], [], []
    else:
        raise ValueError(
            f"kv_dtype must be 'bf16' or 'int8', got {kv_dtype!r}"
        )
    return SlotState(
        caches_k=[jnp.zeros(shape, page_dt) for _ in range(L)],
        caches_v=[jnp.zeros(shape, page_dt) for _ in range(L)],
        scales_k=scales_k,
        scales_v=scales_v,
        tails_k=tails_k,
        tails_v=tails_v,
        tables=jnp.zeros((n_slots, cache_len // block_size), jnp.int32),
        last_token=jnp.zeros((n_slots,), jnp.int32),
        offset=jnp.zeros((n_slots,), jnp.int32),
        active=jnp.zeros((n_slots,), bool),
        temperature=jnp.zeros((n_slots,), jnp.float32),
        top_k=jnp.zeros((n_slots,), jnp.int32),
        top_p=jnp.ones((n_slots,), jnp.float32),
        rep_penalty=jnp.ones((n_slots,), jnp.float32),
        # [n_slots, V] bool lives for the engine's lifetime and the
        # keep-mask select threads through every decode step even when
        # no request sets repetition_penalty (advisor r2: megabytes at
        # production vocab x slot counts, not gigabytes — acceptable; if
        # slot counts grow, allocate lazily / gate the select on
        # any-penalty-enabled)
        seen=jnp.zeros((n_slots, cfg.vocab_size), bool),
        rng=jnp.zeros((n_slots, 2), jnp.uint32),
        gdn_state=[s for s, _ in gdn],
        gdn_conv=[c for _, c in gdn],
        moe_stats=[jnp.zeros((len(MOE_STATS),), jnp.uint32)]
        if cfg.num_local_experts > 0 else [],
    )


def layer_caches(state: SlotState, cfg: ModelConfig, full=None,
                 linear=None) -> list:
    """One cache entry per layer for forward(), each of its layer's
    kind: by default the pool's pages (:func:`_zip_kv`) and the slots'
    (state, convolution tail) pairs; the admit paths pass one row's
    views instead."""
    full = iter(_zip_kv(state) if full is None else full)
    linear = iter(zip(state.gdn_state, state.gdn_conv)
                  if linear is None else linear)
    return [next(linear) if cfg.layer_is_linear(i) else next(full)
            for i in range(cfg.num_hidden_layers)]


def split_layer_caches(cfg: ModelConfig, caches: list):
    """forward()'s updated entries back into (full layers' entries,
    linear layers' (state, tail) pairs)."""
    linear = [c for i, c in enumerate(caches) if cfg.layer_is_linear(i)]
    full = [c for i, c in enumerate(caches) if not cfg.layer_is_linear(i)]
    return full, linear


def add_moe_stats(state: SlotState, stats: list) -> dict:
    """The SlotState field with this program's MoE calls added."""
    if not stats:
        return {}
    return {"moe_stats": [state.moe_stats[0] + sum(stats)]}


def sample_rows(
    logits: jax.Array,  # f32[B, V]
    temperature: jax.Array,  # f32[B]
    top_k: jax.Array,  # i32[B]
    top_p: jax.Array,  # f32[B]
    rep_penalty: jax.Array,  # f32[B]
    seen: jax.Array,  # bool[B, V]
    rng: jax.Array,  # u32[B, 2]
    counter: jax.Array,  # i32[B] — folded in so each step draws fresh noise
) -> jax.Array:
    logits = apply_repetition_penalty(logits, seen, rep_penalty)

    # filter at BATCH level so filter_logits' lax.cond fast-paths engage
    # (inside the vmap a batched predicate would lower to select and pay
    # the full-vocab nucleus sort on every step even with filters off);
    # only the per-row gumbel pick is vmapped
    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]

    def pick_sampled(_):
        filtered = filter_logits(scaled, top_k, top_p)

        def pick_one(row_logits, row_filtered, key_data, ctr, temp):
            key = jax.random.fold_in(
                jax.random.wrap_key_data(key_data, impl="threefry2x32"),
                ctr,
            )
            return gumbel_pick(row_logits, row_filtered, key, temp)

        return jax.vmap(pick_one)(
            logits, filtered, rng, counter, temperature
        )

    def pick_greedy(_):
        # exactly gumbel_pick's temperature <= 0 branch: argmax of the
        # RAW (post-penalty) logits, so an all-greedy batch draws
        # bit-identical tokens to the sampled path's per-row select
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # all-greedy fast path: the per-row threefry fold + full-vocab
    # gumbel noise is the dominant per-draw cost (not the argmax), and
    # a verify window draws 2k+1 times per dispatch — skipping the RNG
    # when no row samples is what keeps speculation ahead of plain
    # decode on dispatch-bound hosts
    return jax.lax.cond(
        jnp.any(temperature > 0), pick_sampled, pick_greedy, None
    )


# --- the shared single-token forward ---------------------------------------


def step_forward(
    params: Params,
    cfg: ModelConfig,
    tok: jax.Array,  # i32[B] each row's last token
    offset: jax.Array,  # i32[B] each row's next cache position
    kv_caches,  # per-layer (k, v): dense [B, S, ...] or paged pool
    cache_len: int,  # logical per-row cache width S
    block_tables: jax.Array | None = None,  # i32[B, max_blocks] = paged
    sharded: bool = False,  # caller jits under a tp-sharded EngineLayout
    active: jax.Array | None = None,  # bool[B] rows that are decoding
    moe_stats: list | None = None,
):
    """One decode token's forward pass for a length-ragged batch;
    returns (logits f32[B, V], updated kv_caches).

    The dense and paged routes share everything but the attention
    reader: both scatter the step's K/V at each row's own offset
    (decoder_layer picks the table-indirect scatter when
    ``block_tables`` is given) and attend to positions ``< offset + 1``.
    On TPU the decode kernels DMA only each row's live tiles (the
    lengths operand == the mask's live set); the bool mask remains the
    dense fallback operand.

    ``sharded`` pins the attention routers' GSPMD-partitionable branch
    (flash_attention: Pallas custom calls cannot be split over heads) —
    the rest of the trace is einsums and the table scatter/gather,
    which partition over the pool's n_kv axis as-is."""
    B = tok.shape[0]
    mask = (jnp.arange(cache_len)[None, None, :]
            < (offset + 1)[:, None, None])
    mask = jnp.broadcast_to(mask, (B, 1, cache_len))
    if block_tables is None:
        def attn_fn(q, k, v, m):
            return decode_attention_auto(
                q, k, v, offset + 1, m, gspmd=sharded
            )
    else:
        # int8 pool: cache entries are (pages, scales, tail) triples
        # (trace-static pytree structure), routed to the dequant-in-
        # kernel readers; decoder_layer scattered the step's K/V into
        # the tail, never the quantized pages
        quantized = any(isinstance(c[0], tuple) for c in kv_caches)

        def attn_fn(q, kc, vc, m):
            if quantized:
                kp, ks, ktl = kc
                vp, vs, vtl = vc
                return decode_attention_blocks_q8_auto(
                    q, kp, vp, ks, vs, ktl, vtl, block_tables,
                    offset + 1, m, gspmd=sharded,
                )
            return decode_attention_blocks_auto(
                q, kc, vc, block_tables, offset + 1, m, gspmd=sharded
            )
    logits, kv_caches = forward(
        params, tok[:, None], cfg,
        positions=offset[:, None],
        attn_mask=mask,
        kv_caches=kv_caches,
        cache_offset=offset,
        block_tables=block_tables,
        attn_fn=attn_fn,
        wq_gspmd=sharded,
        valid_len=None if active is None else active.astype(jnp.int32),
        moe_stats=moe_stats,
    )
    return logits[:, 0], kv_caches


# --- the continuous batcher's fused window ---------------------------------


def _zip_kv(state: SlotState):
    """Per-layer cache entries for forward(): (k, v) pairs in bf16
    mode, ((pages, scales, tail), ...) triples in int8 mode. The
    branch is trace-static (pool dtype), so each kv_dtype compiles its
    own program — exactly the one-shape-per-(K, layout, kv_dtype)
    contract."""
    if state.caches_k and state.caches_k[0].dtype == jnp.int8:
        return [
            ((pk, sk, tk), (pv, sv, tv))
            for pk, sk, tk, pv, sv, tv in zip(
                state.caches_k, state.scales_k, state.tails_k,
                state.caches_v, state.scales_v, state.tails_v,
            )
        ]
    return list(zip(state.caches_k, state.caches_v))


def _commit_full_tails(pools, scales, tails, tables, old_off, new_off,
                       keep, block_size):
    """Quantize-on-commit: rows whose window moved ``offset`` across a
    block boundary have just FILLED tail slot 0 — quantize it
    (kv_blocks.quantize_blocks) into the row's pool block + scale row
    and shift the tail down a block (slot 0 <- slot 1, slot 1 <-
    zeros). At most one boundary per window by construction: n_emit <=
    k+1 <= WINDOW_BUCKETS[-1]+1 < block_size. Non-crossed rows scatter
    their block's CURRENT value back at its own index, so duplicate
    indices (inactive rows all naming null block 0) write identical
    values — deterministic, the same discipline as the null-block
    decode scatter. Returns (pools, scales, tails) lists."""
    B = old_off.shape[0]
    M = tables.shape[1]
    rows = jnp.arange(B)
    crossed = keep & (new_off // block_size > old_off // block_size)
    blk = tables[rows, jnp.clip(old_off // block_size, 0, M - 1)]
    out_p, out_s, out_t = [], [], []
    for pool, sc, tail in zip(pools, scales, tails):
        qv, sv = quantize_blocks(tail[:, 0])
        out_p.append(pool.at[blk].set(
            jnp.where(crossed[:, None, None, None], qv, pool[blk])
        ))
        out_s.append(sc.at[blk].set(
            jnp.where(crossed[:, None], sv, sc[blk])
        ))
        shifted = jnp.concatenate(
            [tail[:, 1:], jnp.zeros_like(tail[:, :1])], axis=1
        )
        out_t.append(jnp.where(
            crossed[:, None, None, None, None], shifted, tail
        ))
    return out_p, out_s, out_t


def decode_body(
    params: Params, state: SlotState, cfg: ModelConfig,
    sharded: bool = False,
) -> tuple[SlotState, jax.Array]:
    """One token for every active slot (greedy, or per-slot temperature
    sampling keyed by the slot PRNG + offset); returns (state, tokens).

    Inactive slots still flow through the math (static shapes) but their
    cache/offset/token state is preserved unchanged. This is the scan
    body of :func:`decode_window` — kept un-jitted so the window's K
    steps trace into one program."""
    block_size = page_dims(state.caches_k[0])[0]
    S = state.tables.shape[1] * block_size  # logical per-row cache width
    quantized = state.caches_k[0].dtype == jnp.int8
    stats: list = []
    # recurrent layers and routed experts must know which rows decode
    # (a row mid-prefill holds state this step may not touch); the
    # other families' trace takes no such operand
    rows = state.active if (cfg.recurrent or cfg.num_local_experts) \
        else None
    logits, caches = step_forward(
        params, cfg, state.last_token, state.offset,
        layer_caches(state, cfg), S,
        block_tables=state.tables, sharded=sharded,
        active=rows, moe_stats=stats,
    )
    caches, linear = split_layer_caches(cfg, caches)
    # counter offset+1: admit folds prompt_len (== first decode offset),
    # so folding the bare offset here would reuse the admit-time gumbel
    # draw and systematically double the first sampled token
    nxt = sample_rows(
        logits, state.temperature, state.top_k, state.top_p,
        state.rep_penalty, state.seen, state.rng, state.offset + 1,
    )

    keep = state.active
    new_off = jnp.where(keep, state.offset + 1, state.offset)
    if quantized:
        # the step's K/V landed in the tails; pages/scales passed
        # through forward untouched, and the boundary commit below
        # quantizes any tail block this token just filled
        tails_k = [c[0][2] for c in caches]
        tails_v = [c[1][2] for c in caches]
        pk, sk, tk = _commit_full_tails(
            state.caches_k, state.scales_k, tails_k, state.tables,
            state.offset, new_off, keep, block_size,
        )
        pv, sv, tv = _commit_full_tails(
            state.caches_v, state.scales_v, tails_v, state.tables,
            state.offset, new_off, keep, block_size,
        )
        kv_fields = dict(caches_k=pk, caches_v=pv, scales_k=sk,
                         scales_v=sv, tails_k=tk, tails_v=tv)
    else:
        kv_fields = dict(
            # no keep-masking on the pool: a retired slot's table row
            # is all-null (see batching._maybe_retire), so an inactive
            # row's scatter lands in the sacrificial block 0 and the
            # pool is taken as-is (a per-row where over a SHARED pool
            # would be wrong anyway — rows no longer own disjoint
            # stripes)
            caches_k=[c[0] for c in caches],
            caches_v=[c[1] for c in caches],
        )
    # dataclasses.replace carries unchanged fields automatically — a
    # full-constructor copy here silently reset any SlotState field
    # added later (this diff had to hand-thread top_k/top_p through two
    # such copies before the conversion)
    new_state = dataclasses.replace(
        state,
        last_token=jnp.where(keep, nxt, state.last_token),
        offset=new_off,
        # record_seen self-gates on any-penalty-enabled; masking by
        # keep afterwards preserves inactive slots
        seen=jnp.where(
            keep[:, None],
            record_seen(state.seen, nxt, state.rep_penalty),
            state.seen,
        ),
        **kv_fields,
        gdn_state=[c[0] for c in linear],
        gdn_conv=[c[1] for c in linear],
        **add_moe_stats(state, stats),
    )
    return new_state, jnp.where(keep, nxt, -1)


@functools.partial(
    jax.jit, static_argnames=("cfg", "k", "sharded"), donate_argnums=(1,)
)
def decode_window(
    params: Params, state: SlotState, cfg: ModelConfig, k: int,
    sharded: bool = False,
) -> tuple[SlotState, jax.Array]:
    """K fused decode steps in ONE dispatch; returns (state, i32[B, K]).

    The scan threads the donated SlotState through K copies of
    :func:`decode_body`, so each step's sampling sees exactly the state
    a lone dispatch would have seen — token streams are bit-identical
    to K single-step dispatches (the keys are position-folded, not
    stream-split). ``active`` never changes mid-window (retirement is
    host work): a row whose EOS lands mid-window keeps stepping and
    keeps scattering into its own refcounted blocks — positions nobody
    will ever read, since the host masks the tail tokens on readback
    and the horizon clamp keeps every write inside the row's allocated
    block span. -1 marks inactive rows' tokens, exactly as at K=1.

    Under a sharded EngineLayout the ENGINE passes ``sharded=True`` and
    a SlotState whose leaves are placed (pool along n_kv, rest
    replicated): jit keys the executable on those input shardings, so
    the donated scan carry keeps its placement across windows and the
    compiled-shape set stays one per (K-bucket, layout) — the same
    donation discipline as tp=1, with GSPMD's psums inside the scan
    body. The static flag only pins the attention routers' dense
    branch; at tp=1 its False default leaves the trace byte-identical
    to the pre-layout engine."""

    def step(st, _):
        return decode_body(params, st, cfg, sharded)

    state, toks = jax.lax.scan(step, state, None, length=k)
    # scan stacks on the leading (time) axis; callers want [slot, step]
    return state, jnp.swapaxes(toks, 0, 1)


# --- speculative verify window ---------------------------------------------


@dataclass
class DraftState:
    """Device-resident draft-model state for the verify window, one row
    per slot. The draft keeps DENSE per-row caches (``[n_slots, Ld,
    n_kv_d, D_d]``): it is orders of magnitude smaller than the target,
    so paging it would buy nothing and would couple its block accounting
    to the pool's. Invariant at rest (target offset ``o``): committed
    draft KV covers positions ``0 .. o-2``; positions ``o-1`` and ``o``
    are rewritten by each window's repair forward from ``prev`` and the
    slot's ``last_token``, so stale KV from rejected proposals is never
    attended (every position the propose scan reads was either
    committed, repaired this window, or written earlier in the same
    scan)."""

    caches_k: list[jax.Array]  # L_d x [n_slots, Ld, n_kv_d, D_d]
    caches_v: list[jax.Array]
    prev: jax.Array  # i32[n_slots] token at target position offset - 1


jax.tree_util.register_dataclass(
    DraftState,
    data_fields=["caches_k", "caches_v", "prev"],
    meta_fields=[],
)


def init_draft_state(dcfg: ModelConfig, n_slots: int, cache_len: int,
                     dtype) -> DraftState:
    # Ld == cache_len suffices: the propose scan's deepest write is
    # position o + k - 1, and the engine only dispatches verify for
    # rows with prompt + max_new + k <= cache_len (spec_ok), which
    # bounds o + k - 1 <= cache_len - 2.
    shape = (n_slots, cache_len, dcfg.num_key_value_heads, dcfg.head_dim)
    return DraftState(
        caches_k=[jnp.zeros(shape, dtype)
                  for _ in range(dcfg.num_hidden_layers)],
        caches_v=[jnp.zeros(shape, dtype)
                  for _ in range(dcfg.num_hidden_layers)],
        prev=jnp.zeros((n_slots,), jnp.int32),
    )


def spec_accept(drafts: jax.Array, target_toks: jax.Array) -> jax.Array:
    """THE acceptance rule — the only implementation in the repo
    (speculative.py routes here too). ``drafts`` i32[B, k] are the
    proposals; ``target_toks`` i32[B, k+1] are the target model's own
    samples at the same positions under the same position-folded noise.
    Draft i survives iff it equals the target's sample AND every
    earlier draft survived (cumprod); the target's sample after the
    last survivor is always emitted, so n_emit = m + 1 in [1, k+1].

    Exact-match acceptance (not rejection sampling) is what buys token
    identity: the emitted row IS the target's sample stream, so the
    output distribution equals the non-speculative engine's by
    construction — correlated draft/target noise only moves the
    acceptance RATE, never the output law."""
    k = drafts.shape[1]
    agree = (drafts == target_toks[:, :k]).astype(jnp.int32)
    m = jnp.cumprod(agree, axis=1).sum(axis=1)
    return m + 1


@functools.partial(
    jax.jit, static_argnames=("cfg", "dcfg", "k", "sharded"),
    donate_argnums=(1, 3),
)
def verify_window(
    params: Params, state: SlotState,
    dparams: Params, dstate: DraftState,
    cfg: ModelConfig, dcfg: ModelConfig, k: int,
    sharded: bool = False,
) -> tuple[SlotState, DraftState, jax.Array]:
    """Speculative twin of :func:`decode_window`: ONE dispatch proposes
    k draft tokens per live row, scores all k+1 window positions
    through the block-table attention path ([k+1, G, D] queries — the
    kernel's verify generalization), and emits each row's accepted
    prefix. Returns (state, dstate, toks i32[B, k+1]) where row b's
    first n_emit entries are its emitted tokens and the rest are -1
    (the same negative-token skip convention the host drain already
    applies to decode_window's output).

    Token identity with the plain engine is by construction, not by
    tuning: target tokens are drawn by the SAME :func:`sample_rows`
    with the SAME position-folded counters the single-step path folds
    (position p -> counter p), the seen-set evolves only along the
    emitted (alive) prefix, and ``rng`` is never mutated — so the
    emitted stream bitwise-equals what decode_window would have
    produced, window boundaries and acceptance rate notwithstanding.

    Rollback is free on the device side: rejected positions' KV stays
    in the row's own refcounted blocks but past the new offset, where
    the next window's scatter-before-attend overwrites it (positions
    ``o' .. o'+k`` cover the junk span because n_emit <= k+1). The
    HOST must still never publish those positions (radix inserts stay
    behind the committed offset — batching's ``toks[:-1]`` rule).

    ``active`` gates everything: inactive rows propose/verify into the
    null block 0 (static shapes), their n_emit is 0, and their
    last_token/offset/seen/prev are preserved unchanged.

    Reference divergence: vLLM keeps draft scheduling inside the
    subprocess (internal/agent/vllm.go:93-112); here the verify window
    is a first-class engine dispatch so it composes with the paged
    pool, preemption, and the sharded layout."""
    B = state.last_token.shape[0]
    block_size = page_dims(state.caches_k[0])[0]
    S = state.tables.shape[1] * block_size
    # a 0-layer (bigram) draft carries no KV at all — Ld then only
    # shapes the repair mask, which no layer reads; S keeps the shape
    # well-formed without a dedicated branch downstream
    Ld = S
    if dcfg.num_hidden_layers > 0:
        Ld = dstate.caches_k[0].shape[1]
    o = state.offset
    T = k + 1

    # --- draft propose -----------------------------------------------------
    # Repair forward: rewrite draft KV at positions o-1 (prev) and o
    # (last_token). This is what makes preemption/rollback cheap — the
    # draft cache never needs host fixup because the only positions a
    # fresh window depends on beyond the committed prefix are rebuilt
    # here from host-verified tokens. dlogits[:, 1] predicts position
    # o+1, the first proposal.
    rep_tok = jnp.stack([dstate.prev, state.last_token], axis=1)
    rep_pos = jnp.stack([o - 1, o], axis=1)
    rep_mask = (jnp.arange(Ld)[None, None, :] <= rep_pos[:, :, None])
    dcaches = list(zip(dstate.caches_k, dstate.caches_v))
    # attn_fn=None -> dense attention: the draft's caches are dense
    # per-row, and the model is small enough that a kernel would be
    # dispatch-bound anyway.
    dlogits, dcaches = forward(
        dparams, rep_tok, dcfg,
        positions=rep_pos, attn_mask=rep_mask,
        kv_caches=dcaches, cache_offset=o - 1,
    )
    dseen = state.seen
    d1 = sample_rows(
        dlogits[:, 1], state.temperature, state.top_k, state.top_p,
        state.rep_penalty, dseen, state.rng, o + 1,
    )
    dseen = record_seen(dseen, d1, state.rep_penalty)

    if k > 1:
        def dstep(carry, i):
            caches_i, tok, seen_i = carry
            lg, caches_i = step_forward(
                dparams, dcfg, tok, o + i, caches_i, Ld, sharded=sharded,
            )
            nxt = sample_rows(
                lg, state.temperature, state.top_k, state.top_p,
                state.rep_penalty, seen_i, state.rng, o + i + 1,
            )
            seen_i = record_seen(seen_i, nxt, state.rep_penalty)
            return (caches_i, nxt, seen_i), nxt

        (dcaches, _, _), rest = jax.lax.scan(
            dstep, (dcaches, d1, dseen),
            jnp.arange(1, k, dtype=jnp.int32),
        )
        drafts = jnp.concatenate(
            [d1[:, None], jnp.swapaxes(rest, 0, 1)], axis=1
        )
    else:
        drafts = d1[:, None]

    # --- fused verify ------------------------------------------------------
    # The window's T tokens [last, d_1 .. d_k] occupy logical positions
    # o .. o+k: decoder_layer scatters their KV into the row's blocks
    # FIRST, then the T-query kernel attends s <= o + t per query —
    # exactly the mask below, per decode_attention_blocks_auto's
    # contract (lengths == o + T).
    window = jnp.concatenate([state.last_token[:, None], drafts], axis=1)
    positions = o[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    mask = jnp.arange(S)[None, None, :] <= positions[:, :, None]
    lengths = o + T
    quantized = state.caches_k[0].dtype == jnp.int8

    if quantized:
        # q8 router derives tail_base = (lengths - T) // block_size
        # == o // block_size, the block the tail slots were pinned to
        # at window start — exactly where decoder_layer lands the
        # window's scatter (rel in {0, 1}, one crossing max per window
        # since T <= k + 1 < block_size)
        def attn_fn(q, kc, vc, m):
            kp, ks, ktl = kc
            vp, vs, vtl = vc
            return decode_attention_blocks_q8_auto(
                q, kp, vp, ks, vs, ktl, vtl, state.tables, lengths, m,
                gspmd=sharded,
            )
    else:
        def attn_fn(q, kp, vp, m):
            return decode_attention_blocks_auto(
                q, kp, vp, state.tables, lengths, m, gspmd=sharded
            )

    logits, caches = forward(
        params, window, cfg,
        positions=positions, attn_mask=mask,
        kv_caches=_zip_kv(state),
        cache_offset=o, block_tables=state.tables, attn_fn=attn_fn,
        wq_gspmd=sharded,
    )

    # --- acceptance --------------------------------------------------------
    # logits[:, i] predicts position o+1+i; sample it with counter
    # o+1+i — the identical draw the single-step engine would make at
    # that position. The scan threads the seen-set along the ALIVE
    # prefix only: a row's seen must reflect exactly its emitted
    # tokens, and sampling depends on seen, so acceptance and sampling
    # have to interleave sequentially (this is VPU-cheap next to the
    # fused forward above).
    drafts_pad = jnp.concatenate(
        [drafts, jnp.zeros((B, 1), jnp.int32)], axis=1
    )
    xs = (
        jnp.swapaxes(logits, 0, 1),
        jnp.swapaxes(drafts_pad, 0, 1),
        jnp.arange(T, dtype=jnp.int32),
    )

    def astep(carry, xs_i):
        seen_i, alive = carry
        lg, d_next, i = xs_i
        t = sample_rows(
            lg, state.temperature, state.top_k, state.top_p,
            state.rep_penalty, seen_i, state.rng, o + 1 + i,
        )
        seen_i = jnp.where(
            alive[:, None],
            record_seen(seen_i, t, state.rep_penalty),
            seen_i,
        )
        alive = alive & (i < k) & (d_next == t)
        return (seen_i, alive), t

    (seen_f, _), t_seq = jax.lax.scan(astep, (state.seen, state.active), xs)
    target = jnp.swapaxes(t_seq, 0, 1)  # [B, T]
    n_emit = jnp.where(state.active, spec_accept(drafts, target), 0)

    # --- boundary state ----------------------------------------------------
    # Token at the new offset o' = o + n_emit is target[n_emit-1]; the
    # one at o'-1 (the next repair window's `prev`) is target[n_emit-2]
    # when two or more tokens were emitted, else the old last_token.
    rows = jnp.arange(B)
    last_new = target[rows, jnp.clip(n_emit - 1, 0, k)]
    prev_new = jnp.where(
        n_emit >= 2, target[rows, jnp.clip(n_emit - 2, 0, k)],
        state.last_token,
    )
    keep = state.active
    new_off = jnp.where(keep, o + n_emit, o)
    if quantized:
        tails_k = [c[0][2] for c in caches]
        tails_v = [c[1][2] for c in caches]
        pk, sk, tk = _commit_full_tails(
            state.caches_k, state.scales_k, tails_k, state.tables,
            o, new_off, keep, block_size,
        )
        pv, sv, tv = _commit_full_tails(
            state.caches_v, state.scales_v, tails_v, state.tables,
            o, new_off, keep, block_size,
        )
        kv_fields = dict(caches_k=pk, caches_v=pv, scales_k=sk,
                         scales_v=sv, tails_k=tk, tails_v=tv)
    else:
        kv_fields = dict(
            caches_k=[c[0] for c in caches],
            caches_v=[c[1] for c in caches],
        )
    new_state = dataclasses.replace(
        state,
        last_token=jnp.where(keep, last_new, state.last_token),
        offset=new_off,
        seen=seen_f,  # already alive-masked in-scan; alive_0 = active
        **kv_fields,
    )
    new_dstate = dataclasses.replace(
        dstate,
        caches_k=[c[0] for c in dcaches],
        caches_v=[c[1] for c in dcaches],
        prev=jnp.where(keep, prev_new, dstate.prev),
    )
    toks = jnp.where(
        jnp.arange(T, dtype=jnp.int32)[None, :] < n_emit[:, None],
        target, -1,
    )
    return new_state, new_dstate, toks


# --- the per-request / sequence-parallel fused loop ------------------------


def decode_scan(
    params: Params,
    cfg: ModelConfig,
    caches,  # per-layer (k, v) with the prompt's KV already written
    next_logits: jax.Array,  # f32[B, V] logits at each row's last prompt pos
    prompt: jax.Array,  # i32[B, T_bucket] (repetition-penalty seed state)
    prompt_len: jax.Array,  # i32[B]; rows may be length-ragged
    max_new: int,
    cache_len: int,
    eos_id: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array,
    top_p: jax.Array,
    rep_penalty: jax.Array,
    rng_key: jax.Array,
):
    """The decode loop shared by every prefill strategy (chunked single-
    device, sequence-parallel ring — sp_engine.py): sample from
    ``next_logits``, then scan single-token steps against the caches.
    Callers jit.

    Key-schedule note: this loop pre-splits a per-call PRNG key, the
    slot path folds per-position counters — the two streams are
    intentionally different (a generate() is one key universe, a slot
    survives many requests), which is why cross-engine parity tests
    compare greedy streams only."""
    B = prompt.shape[0]

    def sample(logits, key, seen):
        logits = apply_repetition_penalty(logits, seen, rep_penalty)
        return gumbel_sample(logits, key, temperature, top_k, top_p)

    seen = seen_from_prompt(prompt, prompt_len, cfg.vocab_size)
    k0, krest = jax.random.split(rng_key)
    first = sample(next_logits, k0, seen)
    seen = record_seen(seen, first, rep_penalty)

    def step(carry, key):
        caches, tok, offset, done, seen = carry
        # per-row offsets: each row writes its token at its OWN cache
        # position (batched scatter in decoder_layer) and attends to
        # its own live prefix — one dispatch decodes a length-ragged
        # batch (step_forward builds the identical mask/attention the
        # paged window uses, minus the table indirection)
        logits, caches = step_forward(
            params, cfg, tok, offset, caches, cache_len,
        )
        nxt = sample(logits, key, seen)
        seen = record_seen(seen, nxt, rep_penalty)
        newly_done = (nxt == eos_id) & (eos_id >= 0)
        nxt = jnp.where(done, eos_id, nxt)
        done = done | newly_done
        return (caches, nxt, offset + 1, done, seen), nxt

    done0 = (first == eos_id) & (eos_id >= 0)
    if max_new > 1:
        keys = jax.random.split(krest, max_new - 1)
        (_, _, _, done, _), rest = jax.lax.scan(
            step,
            (caches, first, prompt_len, done0, seen),
            keys,
            length=max_new - 1,
        )
        toks = jnp.concatenate(
            [first[:, None], rest.swapaxes(0, 1)], axis=1
        )
    else:
        toks = first[:, None]
    # generated length = tokens up to and including first EOS
    is_eos = (toks == eos_id) & (eos_id >= 0)
    first_eos = jnp.where(
        is_eos.any(axis=1), is_eos.argmax(axis=1) + 1, max_new
    )
    return toks, first_eos.astype(jnp.int32)
