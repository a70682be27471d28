"""Continuous batching: requests join/leave a shared decode batch.

The reference delegates serving entirely to vLLM, whose headline
scheduler feature is continuous batching; this is the TPU-native
equivalent, built from static shapes:

- A fixed pool of B decode **slots**. KV lives in a shared PAGED pool:
  per-layer [num_blocks, n_kv, block_size, D] tensors (head-major
  pages: kv_blocks' page layout, read in place by the decode kernel)
  plus a static i32[B, max_blocks] block table per slot (vLLM's
  PagedAttention, Kwon et al. 2023). The dense prefill and the KV wire
  are token-major; pages become rows only for the blocks one table
  names (``_row_caches``, ``_export_pages``), never for a pool. All
  device state lives in one ``SlotState`` pytree that never changes
  shape; every allocation/refcount/free decision is host-side
  (kv_blocks.py), between device steps.
- ``stepper.decode_window`` advances EVERY active slot K tokens in ONE
  jitted call — compiled once per horizon bucket (K ∈ {1, 2, 4, 8}),
  so the per-dispatch floor is paid once per K tokens. Each fused step's
  K/V land via one batched scatter through the block tables; attention
  reads the pool through the same tables
  (flash_attention.decode_attention_blocks_auto). The scheduler picks K
  per pass (``_pick_horizon``) and overlaps its own bookkeeping with
  the in-flight window (``_plan_admissions``), syncing tokens only at
  the window boundary.
- New requests **prefill into a free slot** (compiled once per SUFFIX
  bucket) while other slots keep decoding. A host-side radix cache
  (kv_blocks.RadixCache, SGLang's RadixAttention idea) matches the
  longest full-block prompt prefix already in the pool: matched blocks
  join the slot's table by refcount bump and prefill starts at the
  matched offset, so a warm system prompt pays only its novel suffix.
  The partial tail block is never shared — it is recomputed into a
  fresh block (copy-on-write by construction).

The scheduler loop itself (admit → step → emit/retire) is plain Python
in the serving thread: decisions are O(slots) host work between device
steps, exactly the split the task brief prescribes (control flow on
host, math under jit).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import logging
import queue
import threading
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from kubeinfer_tpu.inference.config import ModelConfig
from kubeinfer_tpu.inference.engine import _bucket, record_seen
from kubeinfer_tpu.inference.kv_blocks import (
    BlockPool,
    RadixCache,
    dequantize_blocks,
    page_dims,
    pages_to_rows,
    pool_shape,
    prefix_fingerprints,
    quantize_blocks,
    rows_to_pages,
)
from kubeinfer_tpu.analysis.racecheck import guard, make_lock
from kubeinfer_tpu.inference.model import Params, forward
from kubeinfer_tpu.inference.moe import STATS as MOE_STATS
from kubeinfer_tpu.observability import tracing
from kubeinfer_tpu.observability.flightrecorder import FlightRecorder
from kubeinfer_tpu.observability.slo import SLOMonitor, SLOObjective
from kubeinfer_tpu.observability.stepprof import (
    StepProfiler,
    annotate,
    profiling,
)
from kubeinfer_tpu.inference.sharding import EngineLayout
from kubeinfer_tpu.inference.weight_quant import (
    params_weight_dtype,
    quantize_params,
)
from kubeinfer_tpu.inference.stepper import (
    DraftState,
    SlotState,
    WINDOW_BUCKETS,
    add_moe_stats,
    decode_window,
    init_draft_state,
    init_slot_state,
    layer_caches,
    sample_rows,
    split_layer_caches,
    verify_window,
)

log = logging.getLogger(__name__)

# spans are recorded retroactively from the request timeline below, so
# the scheduler never holds a live span across passes (docs/OBSERVABILITY.md)
_TRACER = tracing.get_tracer("engine")

# per-token instant events on the decode span are capped so a single
# long completion cannot dominate the span ring's memory
_MAX_TOKEN_EVENTS = 128

# pool block width (tokens). 128 keeps each block lane-aligned so the
# block-table Pallas kernel's tiles are MXU-shaped
# (flash_attention.decode_blocks_available); engines whose cache_len is
# smaller clamp down and take the gather+dense fallback.
DEFAULT_BLOCK_SIZE = 128

# --- device state ----------------------------------------------------------
# SlotState and the fused decode window live in stepper.py (ROADMAP
# item 3's unification: one stepper serves the per-request engine, the
# sequence-parallel engine, and this batcher); the admit/prefill-chunk
# dispatches below stay here — they are paged-pool plumbing the other
# engines never touch.


def _row_recurrent(state: SlotState, slot, start) -> list:
    """One slot's (state, convolution tail) of every linear-attention
    layer, as 1-row batches: what the slot holds when the prefill
    continues (``start > 0``: an earlier chunk left it), zeros when a
    request begins."""
    def row(x):
        return jnp.where(start > 0, x[slot], jnp.zeros_like(x[0]))[None]

    return [(row(s), row(c))
            for s, c in zip(state.gdn_state, state.gdn_conv)]


def _put_row_recurrent(state: SlotState, slot, linear: list) -> dict:
    """The SlotState fields with one slot's recurrent rows replaced."""
    if not linear:
        return {}
    return dict(
        gdn_state=[s.at[slot].set(n[0][0])
                   for s, n in zip(state.gdn_state, linear)],
        gdn_conv=[c.at[slot].set(n[1][0])
                  for c, n in zip(state.gdn_conv, linear)],
    )


def _row_caches(state: SlotState, table_row) -> list:
    """One row's dense (k, v) view ``[1, S, n_kv, D]`` of every
    full-attention layer, gathered through ``table_row``: the named
    pages only are fetched and turned token-major for the dense
    forward (the cost follows the row, never the pool). A quantized
    pool's committed blocks arrive dequantized (shared-prefix KV is
    approximate: that IS the int8 contract); the window a prefill then
    recomputes is bf16, and requantizing a block whose values came from
    dequantization is exact (the amax element always quantizes to ±127,
    so the recovered scale round-trips)."""
    bs, n_kv, D = page_dims(state.caches_k[0])
    S = table_row.shape[0] * bs

    def view(pool, scales=None):
        pages = pool[table_row]
        if scales is not None:
            pages = dequantize_blocks(
                pages, scales[table_row], state.tails_k[0].dtype)
        return pages_to_rows(pages).reshape(1, S, n_kv, D)

    if state.caches_k[0].dtype == jnp.int8:
        return [(view(ck, sk), view(cv, sv)) for ck, sk, cv, sv in zip(
            state.caches_k, state.scales_k,
            state.caches_v, state.scales_v)]
    return [(view(ck), view(cv))
            for ck, cv in zip(state.caches_k, state.caches_v)]


def _view_pages(view, M: int):
    """A row's dense view ``[1, S, n_kv, D]`` back as its ``M`` pages."""
    _, S, n_kv, D = view.shape
    return rows_to_pages(view.reshape(M, S // M, n_kv, D))


@functools.partial(
    jax.jit, static_argnames=("cfg", "wq_gspmd"), donate_argnums=(1,)
)
def _admit_slot(
    params: Params,
    state: SlotState,
    suffix: jax.Array,  # i32[1, T_bucket] prompt tokens from ``start`` on
    suffix_len: jax.Array,  # i32[] live tokens in ``suffix``
    start: jax.Array,  # i32[] matched-prefix length (0 = cold admit)
    prompt_len: jax.Array,  # i32[] full prompt length (= start + suffix_len)
    cfg: ModelConfig,
    slot: jax.Array,  # i32[] — traced, or admission compiles per slot
    table_row: jax.Array,  # i32[max_blocks] this slot's block table
    own_mask: jax.Array,  # bool[max_blocks] True = freshly allocated block
    temperature: jax.Array,  # f32[]
    top_k: jax.Array,  # i32[]
    top_p: jax.Array,  # f32[]
    rep_penalty: jax.Array,  # f32[]
    key_data: jax.Array,  # u32[2] per-request PRNG key data
    seen_row: jax.Array,  # bool[1, V] host-computed full-prompt id set
    wq_gspmd: bool = False,  # static: dense dequant route under GSPMD
) -> SlotState:
    """Prefill one request's novel suffix into the pool blocks of
    ``table_row`` (compiled per SUFFIX bucket — a warm admit of a long
    prompt compiles and runs the short-suffix trace).

    Shape of the trick: gather the row's logical cache view through the
    table (shared prefix blocks arrive with their KV already computed),
    run the dense prefill over the suffix window at ``cache_offset=
    start`` with RoPE positions ``start + arange(T)``, then scatter the
    updated view back — but ONLY into blocks this admit owns
    (``own_mask``): shared blocks are never rewritten (copy-on-write),
    and the null padding past the row's last block is left alone so
    duplicate scatter indices all carry the block's current value
    (deterministic by construction). Masked positions of the gathered
    view contribute exactly 0 to attention, so a cold admit here is
    bit-identical to the pre-paging dense prefill."""
    T = suffix.shape[1]
    bs = page_dims(state.caches_k[0])[0]
    M = table_row.shape[0]
    S = M * bs  # logical per-row width == engine cache_len
    q_pos = start + jnp.arange(T)
    cache_pos = jnp.arange(S)
    # causal over logical positions, limited to the real prompt: key
    # slots past prompt_len (pad tail and decode room) are masked; the
    # shared-prefix slots < start are always visible
    mask = (
        (cache_pos[None, None, :] <= q_pos[None, :, None])
        & (cache_pos[None, None, :] < prompt_len)
    )
    quantized = state.caches_k[0].dtype == jnp.int8
    caches = _row_caches(state, table_row)
    stats: list = []
    logits, caches = forward(
        params, suffix, cfg, positions=q_pos[None, :], attn_mask=mask,
        kv_caches=layer_caches(state, cfg, caches,
                               _row_recurrent(state, slot, start)),
        cache_offset=start, wq_gspmd=wq_gspmd,
        # the bucket's padding must not reach a recurrent state or an
        # expert
        valid_len=suffix_len[None], moe_stats=stats,
    )
    caches, linear = split_layer_caches(cfg, caches)

    last = jnp.clip(suffix_len - 1, 0, T - 1)
    first = sample_rows(
        logits[:, last], temperature[None], top_k[None], top_p[None],
        rep_penalty[None], seen_row, key_data[None], prompt_len[None],
    )[0]
    seen_row = record_seen(seen_row, first[None], rep_penalty[None])

    own = own_mask[:, None, None, None]

    def put(pool, view):
        return pool.at[table_row].set(
            jnp.where(own, _view_pages(view, M), pool[table_row])
        )

    if quantized:
        # quantize-on-commit: only owned FULL blocks (< prompt_len //
        # bs) enter the pool; the partial tail block stays bf16 in the
        # slot's tail pair until a decode window fills it
        # (stepper._commit_full_tails) — a partial block never
        # round-trips through int8
        tb = prompt_len // bs
        own_q = own_mask & (jnp.arange(M) < tb)

        def putq(pool, scales, view):
            qv, sv = quantize_blocks(_view_pages(view, M))
            pool = pool.at[table_row].set(
                jnp.where(own_q[:, None, None, None], qv,
                          pool[table_row])
            )
            scales = scales.at[table_row].set(
                jnp.where(own_q[:, None], sv, scales[table_row])
            )
            return pool, scales

        def tail_pair(tails, view):
            blocks = _view_pages(view, M)
            # slot 0 = the current partial block tb (clipped gather:
            # tb == M only for prefill-only full rows, which never
            # decode); slot 1 = zeroed spill room
            t0 = blocks[jnp.clip(tb, 0, M - 1)]
            return tails.at[slot].set(
                jnp.stack([t0, jnp.zeros_like(t0)])
            )

        qk = [putq(b, s, c[0]) for b, s, c in zip(
            state.caches_k, state.scales_k, caches)]
        qv_ = [putq(b, s, c[1]) for b, s, c in zip(
            state.caches_v, state.scales_v, caches)]
        kv_fields = dict(
            caches_k=[p for p, _ in qk],
            scales_k=[s for _, s in qk],
            caches_v=[p for p, _ in qv_],
            scales_v=[s for _, s in qv_],
            tails_k=[tail_pair(t, c[0]) for t, c in zip(
                state.tails_k, caches)],
            tails_v=[tail_pair(t, c[1]) for t, c in zip(
                state.tails_v, caches)],
        )
    else:
        kv_fields = dict(
            caches_k=[
                put(b, c[0]) for b, c in zip(state.caches_k, caches)
            ],
            caches_v=[
                put(b, c[1]) for b, c in zip(state.caches_v, caches)
            ],
        )

    return dataclasses.replace(
        state,
        **kv_fields,
        **_put_row_recurrent(state, slot, linear),
        **add_moe_stats(state, stats),
        tables=state.tables.at[slot].set(table_row),
        last_token=state.last_token.at[slot].set(first),
        offset=state.offset.at[slot].set(prompt_len),
        active=state.active.at[slot].set(True),
        temperature=state.temperature.at[slot].set(temperature),
        top_k=state.top_k.at[slot].set(top_k),
        top_p=state.top_p.at[slot].set(top_p),
        rep_penalty=state.rep_penalty.at[slot].set(rep_penalty),
        seen=jax.lax.dynamic_update_slice(
            state.seen, seen_row, (slot, 0)
        ),
        rng=state.rng.at[slot].set(key_data),
    )


@functools.partial(
    jax.jit, static_argnames=("cfg", "wq_gspmd"), donate_argnums=(1,)
)
def _prefill_chunk(
    params: Params,
    state: SlotState,
    window: jax.Array,  # i32[1, C] prompt tokens [pos, pos + C)
    pos: jax.Array,  # i32[] chunk start position in the logical row
    cfg: ModelConfig,
    table_row: jax.Array,  # i32[max_blocks] this slot's block table
    own_mask: jax.Array,  # bool[max_blocks] True = freshly allocated block
    wq_gspmd: bool = False,  # static: dense dequant route under GSPMD
    slot: jax.Array | None = None,  # i32[]: models with recurrent layers
) -> SlotState:
    """Commit ONE fixed-size prefill chunk's KV into the pool — no
    sampling, no slot-state installation (``_admit_slot`` finishes the
    tail and flips the slot live in one dispatch, so the row is never
    half-visible to the decode batch: its table stays all-null and
    ``active`` stays False until the final chunk).

    Same gather/scatter shape as ``_admit_slot``: the row's logical view
    through ``table_row`` (earlier chunks' KV arrives committed), dense
    forward over the window at ``cache_offset=pos``, own-masked write
    back (shared radix-prefix blocks are never rewritten). The window is
    always entirely inside the prompt, so the plain causal mask over
    logical positions is exactly ``_admit_slot``'s prompt-limited mask
    restricted to these queries — chunked and whole-suffix prefill
    commit bit-identical KV. ``return_hidden=True`` skips the lm-head
    matmul: intermediate chunks sample nothing, so the vocab projection
    is paid once per prompt (in the final ``_admit_slot``), not once per
    chunk. Compiled once per chunk width C (a fixed multiple of
    block_size), never per prompt length."""
    T = window.shape[1]
    M = table_row.shape[0]
    S = M * page_dims(state.caches_k[0])[0]
    q_pos = pos + jnp.arange(T)
    cache_pos = jnp.arange(S)
    mask = cache_pos[None, None, :] <= q_pos[None, :, None]
    quantized = state.caches_k[0].dtype == jnp.int8
    caches = _row_caches(state, table_row)
    stats: list = []
    _, caches = forward(
        params, window, cfg, positions=q_pos[None, :], attn_mask=mask,
        kv_caches=layer_caches(state, cfg, caches,
                               _row_recurrent(state, slot, pos)),
        cache_offset=pos, return_hidden=True,
        wq_gspmd=wq_gspmd, moe_stats=stats,
    )
    caches, linear = split_layer_caches(cfg, caches)
    # the slot is not live yet, but it already holds its own recurrent
    # rows: decode windows leave an inactive row's untouched
    extra = {**_put_row_recurrent(state, slot, linear),
             **add_moe_stats(state, stats)}

    own = own_mask[:, None, None, None]

    def put(pool, view):
        return pool.at[table_row].set(
            jnp.where(own, _view_pages(view, M), pool[table_row])
        )

    if quantized:
        # intermediate chunks are block-aligned and entirely inside the
        # prompt, so every owned block the window covered is FULL —
        # quantize all owned blocks (blocks past the chunk hold junk
        # that later chunks and the finalizing _admit_slot rewrite;
        # already-committed earlier-chunk blocks requantize exactly,
        # see _admit_slot)
        def putq(pool, scales, view):
            qv, sv = quantize_blocks(_view_pages(view, M))
            pool = pool.at[table_row].set(
                jnp.where(own, qv, pool[table_row])
            )
            scales = scales.at[table_row].set(
                jnp.where(own_mask[:, None], sv, scales[table_row])
            )
            return pool, scales

        qk = [putq(b, s, c[0]) for b, s, c in zip(
            state.caches_k, state.scales_k, caches)]
        qv_ = [putq(b, s, c[1]) for b, s, c in zip(
            state.caches_v, state.scales_v, caches)]
        return dataclasses.replace(
            state,
            caches_k=[p for p, _ in qk],
            scales_k=[s for _, s in qk],
            caches_v=[p for p, _ in qv_],
            scales_v=[s for _, s in qv_],
            **extra,
        )

    return dataclasses.replace(
        state,
        caches_k=[put(b, c[0]) for b, c in zip(state.caches_k, caches)],
        caches_v=[put(b, c[1]) for b, c in zip(state.caches_v, caches)],
        **extra,
    )


@functools.partial(jax.jit, static_argnames=("dcfg",), donate_argnums=(1,))
def _admit_draft(
    dparams: Params,
    dstate: DraftState,
    window: jax.Array,  # i32[1, T_bucket] FULL effective prompt, padded
    prompt_len: jax.Array,  # i32[] live tokens in ``window``
    dcfg: ModelConfig,
    slot: jax.Array,  # i32[]
) -> DraftState:
    """Prefill the DRAFT model over one slot's effective prompt and
    install the row (compiled per full-prompt bucket — the draft has no
    radix reuse, so unlike ``_admit_slot`` the whole prompt recomputes;
    the draft is small enough that this never dominates an admit).

    The forward runs against throwaway 1-row caches and the result is
    scattered into the slot's stripe of the dense draft cache. Padded
    tail positions (>= prompt_len) carry junk KV, which is safe by the
    DraftState invariant: verify_window's repair forward rewrites
    positions offset-1 and offset before any read, and the propose scan
    writes each deeper position before attending it — junk is never
    upstream of a kept token. ``prev`` is the prompt's last token
    (position prompt_len - 1): the target's ``last_token`` after admit
    is the freshly sampled token at position prompt_len, one past it.

    A 0-layer (bigram) draft — embed/norm/lm_head only, the degenerate
    end of the draft spectrum, cf. prompt-lookup/n-gram drafting — has
    no KV to prefill: its logits depend only on the previous token, so
    installing the row is just setting ``prev``."""
    T = window.shape[1]
    if dcfg.num_hidden_layers == 0:
        return dataclasses.replace(
            dstate,
            prev=dstate.prev.at[slot].set(window[0, prompt_len - 1]),
        )
    n_kv = dstate.caches_k[0].shape[2]
    D = dstate.caches_k[0].shape[3]
    caches = [
        (
            jnp.zeros((1, T, n_kv, D), dstate.caches_k[0].dtype),
            jnp.zeros((1, T, n_kv, D), dstate.caches_v[0].dtype),
        )
        for _ in range(dcfg.num_hidden_layers)
    ]
    pos = jnp.arange(T)
    mask = (pos[None, None, :] <= pos[None, :, None])
    _, caches = forward(
        dparams, window, dcfg, attn_mask=mask,
        kv_caches=caches, cache_offset=0, return_hidden=True,
    )

    def put(pool, view):
        return jax.lax.dynamic_update_slice(
            pool, view, (slot, 0, 0, 0)
        )

    return dataclasses.replace(
        dstate,
        caches_k=[put(b, c[0]) for b, c in zip(dstate.caches_k, caches)],
        caches_v=[put(b, c[1]) for b, c in zip(dstate.caches_v, caches)],
        prev=dstate.prev.at[slot].set(window[0, prompt_len - 1]),
    )


@functools.partial(jax.jit, donate_argnums=(0,))
def _import_blocks(
    state: SlotState,
    table_row: jax.Array,  # i32[max_blocks] freshly allocated block ids
    own_mask: jax.Array,  # bool[max_blocks] True = real imported page
    pages_k: jax.Array,  # [L, max_blocks, n_kv, bs, D], zero-padded
    pages_v: jax.Array,
    scales_k: jax.Array,  # f32[L, max_blocks, n_kv]; all-ones for bf16
    scales_v: jax.Array,
) -> SlotState:
    """Scatter fetched KV pages into the pool (disaggregated prefill:
    a prefill replica computed them, wire.py carried them, the host
    staged them — kubeinfer_tpu/disagg/). Same own-mask discipline as
    ``_admit_slot``'s put: padding entries point at the null block with
    ``own=False``, so every duplicate scatter index carries the block's
    current value (deterministic by construction), and the pages tensor
    is always padded to ``max_blocks`` — ONE compiled shape per engine
    config, never one per prefix length. No slot state is touched: the
    import only materializes pool blocks; the request that wants them
    admits through the ordinary warm path afterwards, which is what
    makes a remote prefix token-identical to a radix hit."""
    own = own_mask[:, None, None, None]

    def put(pool, pages):
        return pool.at[table_row].set(
            jnp.where(own, pages, pool[table_row])
        )

    def put_s(scales, pages):
        return scales.at[table_row].set(
            jnp.where(own_mask[:, None], pages, scales[table_row])
        )

    # quantized pools also land the per-block scales (the exporter
    # captured committed int8 pages, so no requantization happens on
    # either side of the wire); the bf16 pytree has no scale leaves and
    # the operands are simply unused
    scale_fields = {}
    if state.scales_k:  # lint: allow[jit-traced-branch] branches on pytree STRUCTURE (empty list under bf16), not a traced value — both trace shapes are legal and cached separately
        scale_fields = dict(
            scales_k=[
                put_s(s, scales_k[i])
                for i, s in enumerate(state.scales_k)
            ],
            scales_v=[
                put_s(s, scales_v[i])
                for i, s in enumerate(state.scales_v)
            ],
        )
    return dataclasses.replace(
        state,
        caches_k=[
            put(b, pages_k[i]) for i, b in enumerate(state.caches_k)
        ],
        caches_v=[
            put(b, pages_v[i]) for i, b in enumerate(state.caches_v)
        ],
        **scale_fields,
    )


# --- host-side scheduler ---------------------------------------------------

_RECURRENT_WIRE = (
    "this model has linear-attention layers whose recurrent state the "
    "KV wire does not carry: live migration and disaggregated prefill "
    "are refused for it (blocks alone cannot resume a request)"
)


class EngineDrainingError(RuntimeError):
    """submit() refused because the engine is draining. Its own type
    (not ValueError) so the server can answer 503 — the request is
    valid, THIS replica just won't take it — and the router can treat
    the refusal as 'mark draining, route elsewhere' rather than a
    client error to relay."""


class EngineOverloadedError(RuntimeError):
    """submit() shed because the waiting-work depth reached
    ``queue_depth_limit`` (ROADMAP item 5's graceful load-shedding:
    refuse at the door instead of queue collapse). Distinct from
    EngineDrainingError because the remedy differs — a drained replica
    never recovers for new work, an overloaded one does, so the server
    answers 503 WITH Retry-After and the router treats it as transient
    pressure, not evacuation."""

    def __init__(self, msg: str, retry_after_s: float = 1.0) -> None:
        super().__init__(msg)
        self.retry_after_s = retry_after_s


@dataclass(frozen=True)
class PreemptionPolicy:
    """SLO-aware preemption knobs (vLLM preempts by full recompute; the
    radix trie makes park-and-readmit nearly free here, so the policy
    can afford to fire on queue-wait pressure alone).

    A waiter triggers preemption only when ALL of: its wait exceeds
    ``threshold_s``, the engine-private queue_wait SLO burn rate has
    reached ``burn_limit`` (burn 1.0 = spending error budget exactly at
    the sustainable rate), at least ``cooldown_steps`` decode steps ran
    since the last preemption, and some victim has decoded at least
    ``min_progress`` tokens since its own (re)admission. The last two
    are the anti-livelock levers: every park is preceded by guaranteed
    forward progress, so an oversubscribed engine round-robins rather
    than thrashes."""

    threshold_s: float = 0.5
    objective: float = 0.9  # good fraction target for the private SLO
    burn_limit: float = 1.0
    cooldown_steps: int = 4
    min_progress: int = 2

    @classmethod
    def parse(cls, spec: str) -> "PreemptionPolicy":
        """``THRESHOLD_S[:BURN_LIMIT]`` — the --preemption-slo CLI
        syntax, e.g. ``0.5`` or ``0.5:2.0``."""
        parts = spec.split(":")
        if len(parts) > 2:
            raise ValueError(
                f"preemption spec {spec!r} is not THRESHOLD_S[:BURN_LIMIT]"
            )
        kw: dict = {"threshold_s": float(parts[0])}
        if len(parts) == 2:
            kw["burn_limit"] = float(parts[1])
        return cls(**kw)


# process-wide request-id stream: every flight-recorder lifecycle emit
# carries ``req=<rid>`` (the canonical detail key the protocol spec in
# analysis/protocol.py requires), so a /debug/flightrecorder dump keys
# each request's chain unambiguously even across engine restarts
_REQ_IDS = itertools.count()


@dataclass
class _Request:
    prompt: list[int]
    max_new: int
    eos_id: int
    rid: int = field(default_factory=lambda: next(_REQ_IDS))
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    rep_penalty: float = 1.0
    seed: int = 0
    out_tokens: list[int] = field(default_factory=list)
    done: threading.Event = field(default_factory=threading.Event)
    cancelled: threading.Event = field(default_factory=threading.Event)
    # set instead of a normal completion when the engine shut down
    # mid-flight — truncated output must not look like success
    failed: str = ""
    # request timeline, tracing-clock seconds: the scheduler writes
    # these at the admit/first-token/retire transitions and the server
    # reads them AFTER done is set (the Event is the happens-before
    # edge), deriving the queue-wait/TTFT/TPOT histograms without a
    # second timing source. trace_parent anchors the retroactive
    # engine spans to the caller's trace (or a fresh one).
    trace_parent: "tracing.SpanContext | None" = None
    t_submit: float = 0.0
    t_admit: float = 0.0
    # t_admit - t_submit split at the first decode/verify window
    # boundary at or after t_submit (ContinuousEngine._split_wait): the
    # wait for the window in flight, and the rest (slots, pool, another
    # request's admit). They sum to the queue wait.
    wait_window_s: float = 0.0
    wait_backlog_s: float = 0.0
    t_first: float = 0.0
    t_done: float = 0.0
    token_times: list[float] = field(default_factory=list)
    # preemption bookkeeping: t_parked restarts the request's place in
    # the longest-pending-first admission order (a just-parked victim
    # goes to the back of the line — the anti-livelock invariant);
    # tokens_at_admit anchors the min_progress victim guard to the
    # CURRENT residency, not lifetime output
    t_parked: float = 0.0
    preemptions: int = 0
    tokens_at_admit: int = 0
    # True once any token_times entry was interpolated from a fused
    # window bracket rather than observed per step — the decode span
    # carries it as ``kubeinfer.interpolated`` so trace readers don't
    # mistake the evenly spaced events for per-step measurements
    # (docs/OBSERVABILITY.md)
    interpolated: bool = False
    # per-request speculative accounting (verify-window path): accepted
    # draft tokens and windows that rolled at least one draft back —
    # carried onto the engine.decode span at retirement
    spec_accepted: int = 0
    spec_rollbacks: int = 0
    # disaggregated prefill (disagg/): export_kv asks the scheduler to
    # capture this request's committed full-block pages at finalize
    # time — the ONLY thread where reading _state is safe (jit donation
    # deletes the buffers HTTP threads would race). kv_export is the
    # captured dict (pages_k/pages_v/fingerprints/block_size), read by
    # the server after done is set (the Event is the happens-before
    # edge, same contract as the timeline fields above).
    export_kv: bool = False
    kv_export: dict | None = None
    # live-session migration (drain): set INSTEAD of a normal
    # completion when the engine handed this session off — carries the
    # generation-so-far plus how many committed blocks were streamed to
    # the export cache, so the router can re-route with a resume body.
    # Read by the server after done is set (same happens-before
    # contract as kv_export above). A migrated request is neither
    # finished nor failed: its out_tokens are a PREFIX of the final
    # answer, which the resuming replica completes token-identically.
    migrated: dict | None = None

    @property
    def pending_since(self) -> float:
        return self.t_parked or self.t_submit

    def cancel(self) -> None:
        """Abandon the request: the scheduler drops it before admission
        or retires its slot at the next step, instead of decoding tokens
        nobody will read."""
        self.cancelled.set()


@dataclass
class _PrefillTask:
    """One in-progress chunked prefill: the slot is reserved (its
    ``_slot_req`` entry set, blocks held) but the row stays inactive —
    the decode batch keeps stepping other slots between chunks.
    ``tokens`` is the EFFECTIVE prompt (original prompt + any tokens
    generated before a preemption), frozen at plan time; ``pos`` is the
    next logical position to prefill (starts at the radix-matched
    offset, advances one chunk per scheduler pass)."""

    req: _Request
    slot: int
    table_row: np.ndarray  # i32[max_blocks]
    own_mask: np.ndarray  # bool[max_blocks]
    reuse: int  # radix-matched full blocks
    total: int  # blocks held by the slot (prompt + decode horizon)
    pos: int
    tokens: list[int]
    resumed: bool
    # the plan reserved verify slack (spec_k extra positions), so the
    # finalize also prefills the slot's draft-cache row
    spec_ok: bool = False


@dataclass
class _ImportTask:
    """One staged KV import (disaggregated prefill): an HTTP thread
    fetched and verified the pages (disagg/client.py), the scheduler
    thread scatters them — it is the only ``_state`` writer, so the
    handoff is a queue + Event rather than a lock around device state.
    ``tokens`` covers exactly the imported full blocks (n * block_size
    tokens); ``pages_k``/``pages_v`` are ``[L, n, bs, n_kv, D]``."""

    tokens: list[int]
    pages_k: np.ndarray
    pages_v: np.ndarray
    # int8 wire (kubeinfer-kvwire/2): per-block-per-head dequant scales
    # [L, n, n_kv] f32; None on the bf16 wire
    scales_k: np.ndarray | None = None
    scales_v: np.ndarray | None = None
    # chunked import (kubeinfer-kvwire/3, live migration): the pages
    # cover blocks [start_block, start_block + n) and ``tokens`` the
    # whole prefix through the chunk's end — the scatter stacks on a
    # radix-matched [0, start_block) prefix, so a chunk can never land
    # on the wrong base
    start_block: int = 0
    done: threading.Event = field(default_factory=threading.Event)
    imported: int = 0
    reason: str | None = None


class ContinuousEngine:
    """Slot-scheduled generation: submit() from any thread; a single
    scheduler thread admits requests into free slots and steps the
    shared decode batch.

    Cold-compile stall (ADVICE r5): the first prefill of each prompt
    bucket (and the first decode/verify window of each horizon) pays
    its full jit compile ON the scheduler thread — potentially tens of
    seconds on which EVERY in-flight slot request also stalls (no
    decode steps run while the scheduler is inside the compile).
    Deployments that care should issue a throwaway generate per bucket
    before serving traffic; the per-shape compile caches are
    process-global, so one warmup covers all later requests of that
    shape.
    """

    def __init__(self, params: Params, cfg: ModelConfig,
                 n_slots: int = 8, cache_len: int = 1024,
                 block_size: int | None = None,
                 num_blocks: int | None = None,
                 prefill_chunk_blocks: int = 0,
                 preemption: PreemptionPolicy | None = None,
                 max_window: int = 8,
                 layout: EngineLayout | None = None,
                 spec_draft: tuple[Params, ModelConfig] | None = None,
                 spec_k: int = 4,
                 kv_dtype: str = "bf16",
                 weight_dtype: str = "bf16",
                 queue_depth_limit: int = 0,
                 migration_chunk_blocks: int = 4,
                 flight_capacity: int = 512,
                 replica_name: str | None = None) -> None:
        # device layout (sharding.EngineLayout): tp=1 (the default) is
        # meshless and every placement below is the identity — the
        # engine is byte-for-byte the single-device engine. Under tp>1
        # the layout places params (Megatron specs) and the slot state
        # (pool along n_kv, rest replicated); the jits themselves are
        # unchanged and GSPMD partitions from the input shardings.
        self.layout = layout if layout is not None else EngineLayout()
        self.layout.check_model(cfg)
        self._sharded = self.layout.sharded
        # models with recurrent layers: what cannot follow the state
        # yet is refused here, by name, never served from pages alone
        self._recurrent = cfg.recurrent
        cfg.check_serving(
            weight_dtype=weight_dtype, kv_dtype=kv_dtype,
            tp=self.layout.tp,
            speculation=spec_draft is not None,
        )
        # weight precision axis (ISSUE 20), kv_dtype's load-time
        # mirror: "int8" accepts either pre-quantized params (the
        # load-time path — weights.params_from_state_dict /
        # model.init_params, where the bf16 copy never reached the
        # device) or plain params to quantize here; "bf16" with a
        # quantized tree is a hard error rather than a silent
        # dequantize, because the caller's capacity math would be wrong
        if weight_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"weight_dtype must be 'bf16' or 'int8', got "
                f"{weight_dtype!r}"
            )
        held = params_weight_dtype(params)
        if weight_dtype == "int8" and held == "bf16":
            params = quantize_params(params)
        elif weight_dtype == "bf16" and held == "int8":
            raise ValueError(
                "weight_dtype='bf16' but params are weight-quantized "
                "(dequantize_params first, or pass weight_dtype='int8')"
            )
        self.weight_dtype = weight_dtype
        self.params = self.layout.shard_params(params, cfg)
        # static param footprint for the kubeinfer_model_param_bytes
        # gauge: int8 pages + f32 scale planes under weight quant,
        # global across the mesh (shape metadata only — no host sync)
        self.model_param_bytes = int(sum(
            x.nbytes for x in jax.tree.leaves(self.params)
        ))
        self.cfg = cfg
        self.n_slots = n_slots
        self.cache_len = cache_len
        # paged KV: block width defaults to the kernel-aligned size,
        # clamped for small test caches (which then take the
        # gather+dense fallback path)
        self.block_size = block_size if block_size is not None else min(
            DEFAULT_BLOCK_SIZE, cache_len
        )
        if cache_len % self.block_size:
            raise ValueError(
                f"cache_len {cache_len} must be a multiple of block_size "
                f"{self.block_size}"
            )
        self.max_blocks = cache_len // self.block_size
        if num_blocks is None:
            # 2x slot capacity (+ the reserved null block): the surplus
            # is what the radix cache retains between requests — with
            # exactly slot capacity every admit would evict the prefix
            # it hopes to reuse. A model with recurrent layers reuses
            # no prefix, so it retains none.
            num_blocks = 1 + (1 if self._recurrent else 2) \
                * n_slots * self.max_blocks
        if num_blocks < 1 + n_slots * self.max_blocks:
            # below this floor a full-length request could find the pool
            # permanently short even after evicting the whole trie (its
            # blocks pinned by other slots) — the holdover would starve
            raise ValueError(
                f"num_blocks {num_blocks} < 1 + n_slots * max_blocks "
                f"({1 + n_slots * self.max_blocks}): a request could "
                "never admit"
            )
        self._pool = BlockPool(num_blocks, self.block_size)
        self._radix = RadixCache(self._pool)
        # chunked prefill: intermediate chunks are exactly this many
        # tokens (k full blocks — ONE compiled shape), the tail rides
        # the existing _admit_slot bucket traces. 0 disables, restoring
        # the single-dispatch admit.
        if prefill_chunk_blocks < 0:
            raise ValueError(
                f"prefill_chunk_blocks must be >= 0, got "
                f"{prefill_chunk_blocks}"
            )
        self.chunk_tokens = prefill_chunk_blocks * self.block_size
        # fused decode windows: horizons are drawn from the static
        # bucket set clipped to max_window (one compiled shape per
        # bucket — stepper.WINDOW_BUCKETS). max_window=1 restores the
        # one-dispatch-per-token loop exactly.
        if max_window < 1:
            raise ValueError(f"max_window must be >= 1, got {max_window}")
        self.max_window = max_window
        self._window_buckets = tuple(
            b for b in WINDOW_BUCKETS if b <= max_window
        )
        self.windows_total = 0  # telemetry: fused decode dispatches
        # admissions PLANNED while a decode window is in flight
        # (host-side radix match + block alloc only — no device work):
        # (req, slot, kv_plan, effective tokens), admitted at the next
        # window boundary by _admit_pending. Mutated under _lock; swept
        # by _fail_inflight like every other handoff field.
        self._staged: list[tuple[_Request, int, tuple, list[int]]] = []
        # SLO-aware preemption: the engine owns a PRIVATE monitor (the
        # server's SLOMonitor aggregates every route; feeding the
        # scheduler from it would double-count queue_wait and couple
        # admission policy to scrape configuration). Observations land
        # at admit time plus a live head-wait probe in _maybe_preempt,
        # so a wedged engine with no admits still sees its burn rise.
        self.preemption = preemption
        self._slo: SLOMonitor | None = None
        if preemption is not None:
            self._slo = SLOMonitor(
                objectives=(SLOObjective(
                    "queue_wait", preemption.threshold_s,
                    preemption.objective,
                ),),
                windows=(30.0, 300.0),
                name="batching.SLOMonitor._lock",
            )
        # chunked prefills in flight (at most one chunk dispatched per
        # scheduler pass, FIFO) and preempted requests awaiting readmit
        self._prefills: list[_PrefillTask] = []
        self._parked: list[_Request] = []
        # staged KV imports (disaggregated prefill, disagg/): appended
        # by HTTP threads under _lock, serviced one per scheduler pass
        # by _step_import, swept by _fail_inflight like every other
        # handoff field
        self._imports: list[_ImportTask] = []
        self.imports_total = 0  # telemetry: serviced KV imports
        self.imported_blocks_total = 0  # telemetry: blocks scattered in
        # live-session migration (drain): while _draining, submit()
        # refuses, pending populations complete as migrated, and live
        # slots stream their committed blocks out through
        # migration_sink one chunk per scheduler pass (decode keeps
        # running between chunks), then park-and-migrate the tail.
        # _draining is read locklessly on hot paths (same torn-read
        # tolerance as stats_summary — a racing submit lands in the
        # queue and the next drain sweep migrates it).
        if migration_chunk_blocks < 1:
            raise ValueError(
                f"migration_chunk_blocks must be >= 1, got "
                f"{migration_chunk_blocks}"
            )
        self.migration_chunk_blocks = migration_chunk_blocks
        self._draining = False
        self._drained = threading.Event()
        # injectable export hook, set by the serving layer: called on
        # the scheduler thread OFF _lock with one chunk dict
        # (start_block, pages, fingerprints slice, scales for int8) —
        # the server encodes wire v3 and parks it in its KVExportCache
        self.migration_sink = None
        # per-slot count of committed blocks already streamed out
        self._migrate_cursor: dict[int, int] = {}
        self.migrated_total = 0  # telemetry: sessions handed off
        self.migration_chunks_total = 0  # telemetry: chunks streamed
        self.migration_blocks_total = 0  # telemetry: blocks streamed
        # cooldown ticks on decode steps; start past the gate so the
        # first pressure spike can preempt immediately
        self._steps_since_preempt = 1 << 30
        self.preempted_total = 0  # telemetry: rows parked
        self.resumed_total = 0  # telemetry: parked rows readmitted
        self.chunks_total = 0  # telemetry: intermediate chunk dispatches
        # prompt tokens of admitted requests by what happened to them:
        # run through _prefill_chunk/_admit_slot, taken from the radix
        # cache (reuse * block_size), or bucket padding (T - suffix)
        self.prefill_tokens = {"computed": 0, "cached": 0, "padded": 0}
        # admissions whose prefix lookup was refused, by reason: a
        # model with recurrent layers cannot resume from blocks alone
        self.prefix_refused = {"recurrent_state": 0}
        # routed experts: the device-side counters (moe.STATS), as
        # Python ints; _moe_seen is the device's wrapping u32 at the
        # last read
        self.moe_counts = dict.fromkeys(MOE_STATS, 0)
        self._moe_seen: np.ndarray | None = None
        # step_t of recent decode/verify windows, for _split_wait; 4096
        # windows is minutes of decoding at any step time seen so far
        self._boundaries: collections.deque[float] = collections.deque(
            maxlen=4096
        )
        # step-level observability (docs/OBSERVABILITY.md): one record
        # per device dispatch, plus the scheduler-decision flight ring.
        # The kv_stats callback reads the pool's own locked counters and
        # runs OUTSIDE the profiler lock, so no cycle joins the
        # engine -> radix -> pool order.
        self.profiler = StepProfiler(
            n_slots=n_slots,
            kv_stats=lambda: (self._pool.used_blocks,
                              self._pool.free_blocks),
            name="batching.StepProfiler._lock",
        )
        if flight_capacity < 1:
            raise ValueError(
                f"flight_capacity must be >= 1, got {flight_capacity}"
            )
        self.flight = FlightRecorder(
            capacity=flight_capacity,
            name="batching.FlightRecorder._lock",
        )
        # fleet identity on this engine's spans (engine.queue_wait /
        # prefill / decode): every in-process replica records into the
        # module-global RECORDER, so without a replica attr a merged
        # fleet trace cannot say WHICH engine served a hop. None (the
        # default) adds no attr — single-engine traces stay unchanged.
        self.replica_name = replica_name
        # host copy of each slot's owned block ids (shared + fresh), in
        # table order — what retire returns to the pool
        self._slot_blocks: list[list[int]] = [[] for _ in range(n_slots)]
        # arrival-order heads popped from the queue but not yet
        # placeable (no free slot); served before the queue. A deque
        # (oldest first) rather than a single slot: preemption
        # interleaves parked readmits with fresh arrivals, so two
        # unplaced requests can be in hand at once.
        self._holdover: "collections.deque[_Request]" = collections.deque()
        # Speculative decoding rides the paged batch itself: a draft
        # model proposes spec_k tokens per live row and ONE fused
        # stepper.verify_window dispatch scores/accepts them. The
        # window serves every slot request, warm or resumed, with or
        # without repetition penalty, and composes with preemption and
        # tensor parallelism.
        self.spec_draft = spec_draft
        self.spec_k = spec_k
        self._dparams: Params | None = None
        self._dcfg: ModelConfig | None = None
        self._dstate: DraftState | None = None
        # per-slot: the admit plan reserved verify slack and the draft
        # row was prefilled. Verify dispatches only when ALL live
        # decoding rows are spec-capable (one fused window covers every
        # slot); a single tight-on-cache row degrades the pass to
        # decode_window, never to wrong output.
        self._slot_spec_ok = [False] * n_slots
        # monotonic verify-path counters (scheduler_stats -> /metrics
        # delta): proposed draft tokens, host-accepted draft tokens,
        # windows that rolled at least one draft back
        self.spec_draft_tokens = 0
        self.spec_accepted_tokens = 0
        self.spec_rollbacks = 0
        if spec_draft is not None:
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            dparams, dcfg = spec_draft
            if dcfg.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft/target vocabulary mismatch: "
                    f"{dcfg.vocab_size} vs {cfg.vocab_size}"
                )
            if spec_k + 1 > cache_len:
                raise ValueError(
                    f"spec_k {spec_k} leaves no room in cache_len "
                    f"{cache_len}"
                )
            # draft params/state replicate under tp: the draft is tiny,
            # and replication keeps it free of head-divisibility
            # constraints the target's Megatron specs impose
            if self._sharded:
                rep = self.layout.replicated()
                dparams = jax.tree.map(
                    lambda x: jax.device_put(x, rep), dparams
                )
            self._dparams, self._dcfg = dparams, dcfg
            dstate = init_draft_state(
                dcfg, n_slots, cache_len, params["norm"].dtype
            )
            if self._sharded:
                rep = self.layout.replicated()
                dstate = jax.tree.map(
                    lambda x: jax.device_put(x, rep), dstate
                )
            self._dstate = dstate
        # paged-pool precision axis (ISSUE 15): int8 pages + per-block
        # scales double the effective pool capacity; the stepper, the
        # attention routers, and the wire all branch statically on the
        # pool dtype, so the bf16 engine's traces stay byte-identical
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(
                f"kv_dtype must be 'bf16' or 'int8', got {kv_dtype!r}"
            )
        self.kv_dtype = kv_dtype
        # host telemetry: logical KV blocks quantize-committed into the
        # pool (admit full blocks + decode/verify tail commits; imports
        # arrive pre-quantized and are not re-counted). Monotonic —
        # the server deltas it into a Prometheus counter.
        self.quant_blocks_total = 0
        self._state = self.layout.shard_state(init_slot_state(
            cfg, n_slots, cache_len, params["norm"].dtype,
            num_blocks, self.block_size, kv_dtype=kv_dtype,
        ))
        # static pool footprint for the kubeinfer_kv_pool_bytes gauge:
        # pages + scales + tails, global across the mesh (shape
        # metadata only — no host sync)
        st = self._state
        self.kv_pool_bytes = int(sum(
            x.nbytes for x in (
                *st.caches_k, *st.caches_v, *st.scales_k,
                *st.scales_v, *st.tails_k, *st.tails_v,
            )
        ))
        # what the linear-attention layers hold instead of pages
        self.recurrent_state_bytes = int(sum(
            x.nbytes for x in (*st.gdn_state, *st.gdn_conv)
        ))
        # load-shedding door (ROADMAP item 5): 0 = unbounded (the
        # pre-shedding behavior); > 0 sheds submits once waiting work
        # (queue + holdover + parked) reaches the limit
        self.queue_depth_limit = int(queue_depth_limit)
        self._queue: "queue.Queue[_Request]" = queue.Queue()
        self._slot_req: list[_Request | None] = [None] * n_slots
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        # guards _slot_req and request result mutation between the
        # scheduler loop and stop()'s cleanup (the join below can time
        # out behind a long jit compile, leaving both threads live)
        self._lock = make_lock("batching.ContinuousEngine._lock")
        guard(self)

    # -- public API -------------------------------------------------------

    def fits(self, prompt_len: int, max_new_tokens: int) -> bool:
        """Can this request ride a slot? (callers fall back to the
        per-request engine when not — e.g. contexts beyond slot width)"""
        return (
            prompt_len > 0
            and prompt_len + max_new_tokens <= self.cache_len
            and _bucket(prompt_len) <= self.cache_len
        )

    def submit(self, prompt: list[int], max_new_tokens: int = 32,
               eos_id: int = -1, temperature: float = 0.0,
               seed: int = 0, top_k: int = 0,
               top_p: float = 1.0,
               repetition_penalty: float = 1.0,
               export_kv: bool = False,
               resume_tokens: list[int] | None = None) -> _Request:
        """``resume_tokens`` is the migration resume path: tokens a
        SOURCE replica already generated for this request. They
        pre-populate ``out_tokens``, so admission takes the readmit
        route (effective prompt = prompt + resume_tokens, remaining
        budget = max_new - len(resume_tokens)) and — by the
        position-folded key schedule that makes park/readmit exact —
        every later sample draws the identical noise an uninterrupted
        run would have at that position. ``max_new_tokens`` stays the
        ORIGINAL total budget, exactly as a parked request keeps its
        own; the returned out_tokens therefore contains resume_tokens
        as a prefix of the full answer."""
        if self._draining:
            # lockless read, same torn-read tolerance as stats_summary:
            # a submit racing the flag flip lands in the queue and the
            # next _step_drain sweep migrates it — refused here only as
            # a fast path so the router marks this replica early
            raise EngineDrainingError("engine is draining")
        if not prompt:
            raise ValueError("empty prompt")
        if not self.fits(len(prompt), max_new_tokens):
            # includes the bucket check: admission pads the prompt to a
            # bucket, and a bucket wider than the cache cannot prefill —
            # accepting it here would return a silent empty completion
            raise ValueError(
                f"request (prompt {len(prompt)} + new {max_new_tokens}, "
                f"prefill bucket {_bucket(len(prompt))}) exceeds slot "
                f"capacity ({self.cache_len})"
            )
        rt = [int(t) for t in (resume_tokens or [])]
        if rt:
            if len(rt) >= max_new_tokens:
                # a fully (or over-) generated resume has nothing left
                # to decode; admitting it would sample past the budget
                raise ValueError(
                    f"resume_tokens ({len(rt)}) must leave budget "
                    f"(max_new {max_new_tokens})"
                )
            if _bucket(len(prompt) + len(rt)) > self.cache_len:
                # the readmit's effective prompt pads to a bucket just
                # like a cold admit; same silent-empty-completion guard
                # as fits() applies to the widened prompt
                raise ValueError(
                    f"resume bucket {_bucket(len(prompt) + len(rt))} "
                    f"exceeds slot capacity ({self.cache_len})"
                )
        if export_kv and self._recurrent:
            raise ValueError(_RECURRENT_WIRE)
        if self.queue_depth_limit:
            # same lockless depth read as stats_summary (torn by at
            # most 1); >= so limit=1 means "shed whenever anything is
            # already waiting"
            depth = (self._queue.qsize() + len(self._holdover)
                     + len(self._parked))
            if depth >= self.queue_depth_limit:
                # ledger the refusal as submit -> backpressure -> fail
                # (the SPEC's queued self-loop, then the terminal) so
                # flight post-mortems see WHY the request never reached
                # a slot, then refuse with a retry hint instead of
                # joining a queue already past the replica's drain rate
                req = _Request(prompt, max_new_tokens, eos_id,
                               temperature=temperature, top_k=top_k,
                               top_p=top_p, rep_penalty=repetition_penalty,
                               seed=seed)
                req.t_submit = tracing.now()
                req.failed = "shed"
                self._note("submit", req=req.rid,
                           prompt_tokens=len(prompt),
                           max_new=max_new_tokens)
                self._note("backpressure", req=req.rid,
                           reason="queue_depth_limit", depth=depth,
                           limit=self.queue_depth_limit)
                self._note("fail", req=req.rid, reason="shed")
                req.done.set()
                raise EngineOverloadedError(
                    f"queue depth {depth} >= queue_depth_limit "
                    f"{self.queue_depth_limit}"
                )
        req = _Request(prompt, max_new_tokens, eos_id,
                       temperature=temperature, top_k=top_k, top_p=top_p,
                       rep_penalty=repetition_penalty, seed=seed,
                       export_kv=export_kv)
        if rt:
            # the admit path detects a resume by out_tokens being
            # non-empty (exactly how a parked readmit looks); no
            # token_times for these — they were timed on the source
            req.out_tokens = rt
        # capture the submitter's trace context here (scheduler runs on
        # its own thread, where the thread-local stack is empty); no
        # inbound context still gets a per-request trace anchor
        ctx = tracing.current_context()
        req.trace_parent = ctx if ctx is not None else \
            tracing.new_root_context()
        req.t_submit = tracing.now()
        # note BEFORE the queue publish: once the request is visible the
        # scheduler thread can admit it, and an admit event with a lower
        # ring seq than its own submit would be an illegal transition to
        # the protocol oracle (and a lie to any post-mortem reader)
        self._note("submit", req=req.rid, prompt_tokens=len(prompt),
                   max_new=max_new_tokens)
        self._queue.put(req)
        return req

    def serve(self, prompt: list[int], max_new_tokens: int = 32,
              eos_id: int = -1, temperature: float = 0.0,
              seed: int = 0, top_k: int = 0, top_p: float = 1.0,
              repetition_penalty: float = 1.0,
              timeout: float = 300.0,
              export_kv: bool = False,
              resume_tokens: list[int] | None = None) -> _Request:
        """submit() + wait, returning the completed request object so
        callers (the HTTP server's latency-breakdown histograms) can
        read the timeline fields alongside the tokens. A request that
        completes by MIGRATION (this replica drained mid-generation)
        returns normally with ``req.migrated`` set — the caller decides
        whether to re-route with the partial out_tokens."""
        req = self.submit(prompt, max_new_tokens, eos_id,
                          temperature=temperature, seed=seed,
                          top_k=top_k, top_p=top_p,
                          repetition_penalty=repetition_penalty,
                          export_kv=export_kv,
                          resume_tokens=resume_tokens)
        if not req.done.wait(timeout):
            req.cancel()  # free the slot; tokens would go unread
            raise TimeoutError("generation timed out")
        if req.failed:
            raise RuntimeError(req.failed)
        return req

    def generate(self, prompt: list[int], max_new_tokens: int = 32,
                 eos_id: int = -1, temperature: float = 0.0,
                 seed: int = 0, top_k: int = 0, top_p: float = 1.0,
                 repetition_penalty: float = 1.0,
                 timeout: float = 300.0) -> list[int]:
        return self.serve(
            prompt, max_new_tokens, eos_id, temperature=temperature,
            seed=seed, top_k=top_k, top_p=top_p,
            repetition_penalty=repetition_penalty, timeout=timeout,
        ).out_tokens

    @property
    def draining(self) -> bool:
        return self._draining

    def drain(self) -> None:
        """Flip the engine into drain mode: submit() starts refusing,
        and the scheduler loop replaces admission/preemption with
        ``_step_drain`` — pending populations complete as migrated
        immediately, live slots stream their committed KV out through
        ``migration_sink`` one chunk per pass (decode keeps running
        between chunks — the stream chases the decode head), and each
        caught-up slot parks-for-migrate. Idempotent; ``undrain()``
        reverses it (the rebalance caller drains, hands sessions off,
        then rejoins the fleet)."""
        if self._recurrent:
            raise ValueError(_RECURRENT_WIRE)
        with self._lock:
            if self._draining:
                return
            self._draining = True
            self._drained.clear()
            # note under the lock: the scheduler observes _draining via
            # this same lock, so the drain_start event's ring seq is
            # guaranteed to precede every migrate* emit of the window —
            # the protocol oracle's drain guard depends on that order
            self._note("drain_start")

    def undrain(self) -> None:
        """Resume admissions after a drain (rebalance / cancelled
        scale-down). Sessions already migrated are gone — a bounced-back
        request re-enters through submit(resume_tokens=...) and lands
        warm on the blocks ``_migrate_slot`` parked in the trie."""
        with self._lock:
            if not self._draining:
                return
            self._draining = False
            self._drained.clear()
            # under the lock for the same seq-order guarantee as
            # drain_start: no migrate* emit may land after this event
            self._note("drain_end")

    def wait_drained(self, timeout_s: float = 30.0) -> bool:
        """Block until every live session has reached a terminal state
        (done, failed, cancelled, or migrated) and the arrival queue is
        empty. Only meaningful while draining."""
        return self._drained.wait(timeout_s)

    def kv_cache_stats(self) -> dict:
        """Point-in-time paged-KV accounting for /metrics: pool
        occupancy plus the radix cache's monotonic hit/miss/eviction
        counters (the server turns the latter into Prometheus counters
        by delta at scrape time). Callable from any thread — the pool
        and trie take their own locks."""
        stats = self._radix.stats()
        stats["blocks_in_use"] = self._pool.used_blocks
        stats["blocks_free"] = self._pool.free_blocks
        stats["pool_bytes"] = self.kv_pool_bytes
        stats["quant_blocks"] = self.quant_blocks_total
        return stats

    def cache_summary(self) -> dict:
        """Capped radix-summary advertisement (fingerprints + version)
        for the fleet router — served at the inference server's
        ``/cache/summary`` and embedded in stats_summary for the
        heartbeat path. Callable from any thread; the trie takes its
        own lock."""
        return self._radix.summary()

    def import_prefix(self, tokens: list[int], pages_k: np.ndarray,
                      pages_v: np.ndarray,
                      timeout_s: float = 10.0,
                      scales_k: np.ndarray | None = None,
                      scales_v: np.ndarray | None = None,
                      kv_dtype: str = "bf16",
                      start_block: int = 0) -> tuple[int, str | None]:
        """Land a remotely prefilled prefix in the local pool + radix
        cache (disaggregated prefill, disagg/). Callable from any
        thread: the scatter is staged for the scheduler thread — the
        only ``_state`` writer — and this call waits for it. Returns
        ``(blocks_imported, reason)``; reason is None on success, else
        a low-cardinality fallback label. Never raises: every failure
        here just means the request prefills locally (token-identical
        by the determinism contract).

        ``tokens`` must cover exactly the imported full blocks and
        ``pages_k``/``pages_v`` be ``[L, n, block_size, n_kv, D]`` in
        the cache dtype — the caller (disagg.client) has already
        verified the fingerprint chain, so a shape mismatch here means
        a mis-configured fleet, not corruption.

        ``start_block`` supports CHUNKED imports (wire v3, live-session
        migration): the pages cover blocks ``[start_block, start_block
        + n)`` of ``tokens``, and the first ``start_block`` blocks must
        already be in the radix cache (landed by the previous chunks) —
        a chunk whose base prefix was evicted between chunks fails with
        ``missing_prefix`` rather than caching a chain with a hole."""
        if self._recurrent:
            raise ValueError(_RECURRENT_WIRE)
        if start_block < 0:
            return 0, "shape_mismatch"
        if kv_dtype != self.kv_dtype:
            # cross-dtype pages are structurally unusable (an int8 page
            # without its scales, or bf16 pages a quantized pool would
            # have to requantize blind) — reject before staging so the
            # caller counts a low-cardinality fallback and prefills
            # locally
            return 0, "kv_dtype_mismatch"
        if pages_k.ndim != 5 or pages_k.shape != pages_v.shape:
            return 0, "shape_mismatch"
        n = int(pages_k.shape[1])
        if n == 0 or start_block + n > self.max_blocks or \
                len(tokens) != (start_block + n) * self.block_size:
            return 0, "shape_mismatch"
        if kv_dtype == "int8":
            want_s = (pages_k.shape[0], n, pages_k.shape[3])
            if (
                scales_k is None or scales_v is None
                or tuple(scales_k.shape) != want_s
                or tuple(scales_v.shape) != want_s
            ):
                return 0, "shape_mismatch"
        if self._stop.is_set() or self._thread is None:
            return 0, "stopped"
        task = _ImportTask(list(tokens), pages_k, pages_v,
                           scales_k=scales_k, scales_v=scales_v,
                           start_block=start_block)
        with self._lock:
            self._imports.append(task)
        self._note("import_staged", blocks=n)
        if not task.done.wait(timeout_s):
            # the scheduler may still service the task later — that
            # only warms the trie; the caller stops waiting and
            # prefills locally
            return 0, "timeout"
        return task.imported, task.reason

    def _export_pages(self, idx: jax.Array):
        """The pool blocks ``idx`` names, as the wire carries them:
        ``(pages_k, pages_v)`` host arrays ``[L, n, block_size, n_kv,
        D]``, token-major whatever the stored layout (the edge where
        stored pages become wire rows; the gather fetches the named
        blocks only). Scheduler thread only: the sole safe ``_state``
        reader, since jit donation deletes buffers under a racing
        read."""
        def rows(pools):
            # a host sync by design: the pages must reach host memory
            # before the request completes or the chunk streams (one
            # gather per layer, one strided copy into wire order)
            bs, n_kv, D = page_dims(pools[0])
            out = np.empty((len(pools), idx.shape[0], bs, n_kv, D),
                           np.dtype(pools[0].dtype))
            for layer, pool in zip(out, pools):
                layer[...] = pages_to_rows(np.asarray(pool[idx]))
            return out

        return rows(self._state.caches_k), rows(self._state.caches_v)

    def _step_import(self) -> None:
        """Service at most ONE staged KV import per scheduler pass —
        the same pass quantum as chunked prefill, so a burst of imports
        never starves the decode batch. Runs on the scheduler thread
        only (the sole ``_state`` writer); alloc → scatter → trie
        insert → drop our alloc hold, leaving the imported blocks at
        trie-only refcount exactly like a parked prefix: LRU-evictable,
        never pool-pinning. Spans already cached keep the existing
        trie nodes and our duplicate fresh blocks free right back —
        dedup by construction (freed blocks hold junk pages, harmless:
        every owned block is fully rewritten before any read)."""
        with self._lock:
            task = self._imports.pop(0) if self._imports else None
        if task is None:
            return
        with annotate("engine.import"):
            n = int(task.pages_k.shape[1])
            L = len(self._state.caches_k)
            bs, n_kv, D = page_dims(self._state.caches_k[0])
            want = (L, n, bs, n_kv, D)  # the wire is token-major
            cache_dt = np.dtype(self._state.caches_k[0].dtype)
            if (
                np.dtype(task.pages_k.dtype) != cache_dt
                or np.dtype(task.pages_v.dtype) != cache_dt
            ):
                # distinct from shape_mismatch: a dtype disagreement means
                # the fleet mixes kv_dtype configurations, which the wire's
                # version negotiation should have caught upstream
                task.reason = "kv_dtype_mismatch"
                self._note("import_reject", blocks=n, reason=task.reason)
                task.done.set()
                return
            if (
                tuple(task.pages_k.shape) != want
                or tuple(task.pages_v.shape) != want
            ):
                task.reason = "shape_mismatch"
                self._note("import_reject", blocks=n, reason=task.reason)
                task.done.set()
                return
            # trie/pool mutations take _lock (HTTP threads walk the trie in
            # cache_summary); the jit scatter between them stays OFF-lock —
            # only this thread allocs, so the two sections can't interleave
            start = task.start_block
            with self._lock:
                shared: list[int] = []
                if start:
                    # chunked import (wire v3): this chunk stacks on the
                    # blocks the previous chunks inserted. The trie walk
                    # refs its matches (ours until the final insert/unref
                    # below); fewer matches than start_block means the base
                    # was evicted between chunks — reject rather than cache
                    # a chain with a hole, the importer restarts the prefix
                    matched = self._radix.match(
                        task.tokens[: start * self.block_size]
                    )
                    if len(matched) < start:
                        if matched:
                            self._pool.unref(matched)
                        task.reason = "missing_prefix"
                        self._note("import_reject", blocks=n,
                                   reason=task.reason)
                        task.done.set()
                        return
                    shared = matched[:start]
                    if len(matched) > start:
                        self._pool.unref(matched[start:])
                if not self._radix.ensure_free(n):
                    if shared:
                        self._pool.unref(shared)
                    task.reason = "backpressure"
                    self._note("import_reject", blocks=n, reason=task.reason)
                    task.done.set()
                    return
                fresh = self._pool.alloc(n)
            table_row = np.zeros(self.max_blocks, np.int32)
            table_row[:n] = fresh
            own_mask = np.zeros(self.max_blocks, bool)
            own_mask[:n] = True
            # the edge where wire rows become stored pages: a strided
            # host copy of the imported blocks, nothing on the device
            pk = np.zeros(
                (L, *pool_shape(self.max_blocks, bs, n_kv, D)), cache_dt)
            pk[:, :n] = rows_to_pages(task.pages_k)
            pv = np.zeros_like(pk)
            pv[:, :n] = rows_to_pages(task.pages_v)
            # all-ones padding keeps null-block scales at their init value;
            # the bf16 pytree carries no scale leaves and jit drops these
            sk = np.ones((L, self.max_blocks, n_kv), np.float32)
            sv = np.ones((L, self.max_blocks, n_kv), np.float32)
            if task.scales_k is not None:
                sk[:, :n] = task.scales_k
                sv[:, :n] = task.scales_v
            # lint: allow[lock-discipline] scheduler thread is the only _state writer; see _loop
            self._state = _import_blocks(
                self._state, jnp.asarray(table_row), jnp.asarray(own_mask),
                jnp.asarray(pk), jnp.asarray(pv),
                jnp.asarray(sk), jnp.asarray(sv),
            )
            with self._lock:
                # the insert covers the WHOLE chain so far (shared base +
                # this chunk); the trie takes its own reference per block
                # and both our holds return here, leaving the chain at
                # trie-only refcount — LRU-evictable like any parked prefix
                created = self._radix.insert(task.tokens, shared + fresh)
                self._pool.unref(shared + fresh)
            self.imports_total += 1
            self.imported_blocks_total += n
            task.imported = n
            self._note("import", blocks=n, created_nodes=created,
                       start_block=start)
            task.done.set()

    def scheduler_stats(self) -> dict:
        """Preemption/chunking accounting for /metrics: monotonic
        preempt/resume/chunk counters (the server converts them by
        delta at scrape time) plus the instantaneous chunk-queue and
        parked-row depths. Lockless reads, same torn-read tolerance as
        stats_summary — a scrape must never stall behind an admit
        compile."""
        dispatches, decode_steps = self.profiler.totals()
        return {
            "preempted": self.preempted_total,
            "resumed": self.resumed_total,
            "chunks": self.chunks_total,
            # where the work happened (stepprof totals + the admit
            # path's token accounting): dispatches by phase, model
            # steps of decode/verify windows, prompt tokens by fate
            "dispatches": dispatches,
            "decode_steps": decode_steps,
            "decode_row_steps": self.profiler.decode_row_steps,
            "prefill_tokens": dict(self.prefill_tokens),
            "prefix_refused": dict(self.prefix_refused),
            "moe": dict(self.moe_counts),
            "chunk_queue": len(self._prefills),
            "parked": len(self._parked),
            # fused decode dispatches (each covers 1..max_window steps)
            "windows": self.windows_total,
            # verify-window accounting (speculative decode on the paged
            # batch): proposed / host-accepted draft tokens and windows
            # that rolled at least one draft back
            "spec_draft_tokens": self.spec_draft_tokens,
            "spec_accepted_tokens": self.spec_accepted_tokens,
            "spec_rollbacks": self.spec_rollbacks,
            # disaggregated prefill: serviced imports / blocks landed
            "kv_imports": self.imports_total,
            "kv_imported_blocks": self.imported_blocks_total,
            # live-session migration: sessions handed off, chunks and
            # blocks streamed out (drain/evacuate/rebalance paths)
            "migrated": self.migrated_total,
            "migration_chunks": self.migration_chunks_total,
            "migration_blocks": self.migration_blocks_total,
        }

    def _span_ids(self, req: "_Request") -> dict:
        """Fleet-join attrs carried by every engine span: the request
        id under the flight ring's literal ``req`` key (so spans and
        flight decisions correlate by the same id), plus the replica
        name when this engine has one — fleetview groups a merged
        trace's hops per replica by that attr. No replica_name = no
        attr, so single-engine traces are unchanged."""
        ids = {"req": req.rid}
        if self.replica_name is not None:
            ids["replica"] = self.replica_name
        return ids

    def _note(self, kind: str, **detail) -> None:
        """Flight-recorder entry with queue depth + pool occupancy
        observed NOW. Callable from any thread: qsize and the pool
        counters each take their own locks; the holdover is not folded
        in (reading it here would need the engine lock from submit()'s
        HTTP threads — queue_depth is a decision-time signal, not an
        accounting invariant)."""
        self.flight.note(
            kind,
            queue_depth=self._queue.qsize(),
            kv_in_use=self._pool.used_blocks,
            kv_free=self._pool.free_blocks,
            **detail,
        )

    def slo_burn(self) -> float:
        """Worst burn rate across every objective and window — the
        scalar the reconciler's evacuation pass thresholds on (a
        replica persistently burning error budget gets drained before
        it starts failing requests outright). 0.0 without an SLO
        monitor or without traffic; callable from any thread."""
        if self._slo is None:
            return 0.0
        rates = self._slo.burn_rates()
        worst = 0.0
        for per_window in rates.values():
            for rate in per_window.values():
                worst = max(worst, float(rate))
        return worst

    def stats_summary(self, window_s: float = 60.0) -> dict:
        """One-dict replica serving summary for the node agent's
        NodeState heartbeat (and /debug callers): occupancy, queue
        depth, goodput, free blocks, prefix hit rate. Everything here
        is advertised to the control-plane store, where ROADMAP item 4's
        prefix-cache-aware router and the reconciler's cost tensor can
        finally see per-replica load. Plain JSON-serializable scalars
        only — NodeState.to_dict embeds it verbatim."""
        prof = self.profiler.summary(window_s=window_s)
        kv = self.kv_cache_stats()
        # lockless holdover/parked peeks: the engine lock is held across
        # admit jit compiles (potentially tens of seconds) and a
        # heartbeat must never stall behind one; a torn read here only
        # skews queue_depth by 1 for one sample. Parked rows count as
        # waiting — they hold no slot and need a readmit to progress.
        waiting = len(self._holdover) + len(self._parked)
        lookups = kv["hits"] + kv["misses"]
        return {
            "n_slots": self.n_slots,
            "block_size": self.block_size,
            # device layout, advertised so the fleet router / capacity
            # dashboards can tell a tp=4 replica's pool shard from a
            # single-device pool of the same logical block count
            "tp_degree": self.layout.tp,
            "mesh_devices": self.layout.mesh_devices,
            "queue_depth": self._queue.qsize() + waiting,
            "batch_occupancy": round(prof["batch_occupancy"], 6),
            "goodput_tokens_per_sec": round(
                prof["goodput_tokens_per_sec"], 6
            ),
            "padding_waste_frac": round(prof["padding_waste_frac"], 6),
            "kv_blocks_free": kv["blocks_free"],
            "kv_blocks_in_use": kv["blocks_in_use"],
            "kv_dtype": self.kv_dtype,
            "kv_pool_bytes": kv["pool_bytes"],
            # weight precision axis + resident param footprint: the
            # capacity twin of the kv fields above, so fleet dashboards
            # and the router can tell an int8-weights replica (≈2x
            # model headroom) from a bf16 one on the same heartbeat
            "weight_dtype": self.weight_dtype,
            "model_param_bytes": self.model_param_bytes,
            "prefix_hit_rate": round(
                kv["hits"] / lookups if lookups else 0.0, 6
            ),
            "prefix_cached_tokens": kv["cached_tokens"],
            # drain awareness for the router (skip for new work) and
            # the reconciler's evacuation trigger — both ride the same
            # heartbeat this dict feeds
            "draining": bool(self._draining),
            "slo_burn": round(self.slo_burn(), 6),
            # the router's prefix-affinity signal, already capped at
            # kv_blocks.SUMMARY_FINGERPRINT_BUDGET so a big trie cannot
            # bloat the store write this dict rides in (the node agent
            # re-clamps defensively — the callback is injectable)
            "cache_summary": self._radix.summary(),
        }

    def start(self) -> "ContinuousEngine":
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="continuous-batcher"
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
        # release every waiter AS FAILURES: queued requests never
        # admitted and in-slot requests mid-decode would otherwise block
        # their callers for the full generate() timeout — and a
        # truncated token list must not read as a normal completion
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            req.failed = "engine stopped before the request was served"
            self._note("fail", req=req.rid, reason="engine stopped")
            req.done.set()
        # the join above can expire behind a long jit compile, leaving
        # the scheduler live — and the scheduler may PUBLISH a slot
        # after this sweep ran (admission was mid-compile during
        # the snapshot). The loop's epilogue runs the same sweep from
        # the scheduler thread when it observes _stop, so whichever
        # side sees the published state last releases the waiters.
        self._fail_inflight()

    def _fail_inflight(self) -> None:
        """Fail over every published in-flight request (slots,
        holdover, parked rows, chunked prefills) — shared by
        stop() and the scheduler loop's epilogue; all handoff fields
        are swapped under the lock."""
        failed = 0
        with self._lock:
            held = list(self._holdover)
            self._holdover.clear()
            parked, self._parked = self._parked, []
            # staged admissions hold pool references but no slot yet:
            # release the planned blocks and fail the requests (they
            # were popped from the pending order, so nothing else will
            # serve them)
            staged, self._staged = self._staged, []
            # staged KV imports hold no pool references yet (alloc
            # happens in _step_import); releasing their waiters is the
            # whole cleanup
            imports, self._imports = self._imports, []
            # chunked-prefill tasks' requests are already published in
            # _slot_req (the slot is reserved at plan time), so the
            # slot sweep below releases them; only the task list needs
            # clearing so a mid-compile chunk cannot be re-dispatched
            self._prefills.clear()
            for slot, req in enumerate(self._slot_req):
                if req is not None:
                    self._slot_req[slot] = None
                    req.failed = "engine stopped mid-generation"
                    self._note("fail", req=req.rid,
                               reason="stopped mid-generation")
                    req.done.set()
                    failed += 1
        for holdover in held:
            holdover.failed = "engine stopped before the request was served"
            # lint: allow[protocol-order] consecutive sweeps fail DISTINCT request populations (slots, holdover, parked, staged); each chain sees exactly one fail
            self._note("fail", req=holdover.rid, reason="stopped unserved")
            holdover.done.set()
            failed += 1
        for req in parked:
            # parked requests carry partial output: fail, never return
            # a truncated token list as a normal completion
            req.failed = "engine stopped mid-generation"
            # lint: allow[protocol-order] distinct population from the holdover sweep above
            self._note("fail", req=req.rid, reason="stopped while parked")
            req.done.set()
            failed += 1
        for req, _slot, kv_plan, _tokens in staged:
            table_row, _own, _reuse, total, _spec = kv_plan
            self._pool.unref([int(b) for b in table_row[:total]])
            req.failed = "engine stopped before the request was served"
            # lint: allow[protocol-order] distinct population from the parked sweep above
            self._note("fail", req=req.rid, reason="stopped while staged")
            req.done.set()
            failed += 1
        for task in imports:
            task.reason = "stopped"
            task.done.set()
        if failed:
            # auto-dump the flight recorder: the post-mortem needs the
            # scheduler's last decisions in the log stream even if the
            # process dies before anyone curls /debug/flightrecorder.
            # Guarded on failed>0 so the stop()+epilogue double
            # invocation dumps at most once (the second sweep finds
            # nothing published).
            self._note("fail_inflight", failed=failed)
            log.warning(
                "engine stopped with %d in-flight request(s); "
                "flight recorder dump:\n%s", failed, self.flight.render(),
            )

    # -- scheduler loop ---------------------------------------------------

    def _plan_kv(self, tokens: list[int], max_new: int, rid: int = -1):
        """Host-side paged-admit plan: radix match → capacity clamp →
        evict/alloc. ``tokens`` is the EFFECTIVE prompt — the original
        prompt for a fresh admit, prompt + generated-so-far for a
        parked readmit (whose park inserted those full blocks into the
        trie, so the match below recovers them with zero recompute) —
        and ``max_new`` the REMAINING budget, so the block horizon is
        identical across preemptions. Returns ``(table_row, own_mask,
        reuse, total, spec_ok)`` — the static-shape operands
        ``_admit_slot`` needs plus whether verify slack was reserved —
        or None when the pool cannot supply the fresh blocks
        (admission backpressure; unreachable with the __init__ sizing
        floor but kept for custom pools). On success the slot holds one
        reference per block in ``table_row[:total]``.

        Verify slack: a verify window scatters KV up to position
        ``offset + spec_k``, past the plain decode horizon, so a
        spec-capable slot holds ceil((p + max_new + spec_k) / bs)
        blocks. The slack is best-effort — under pool pressure the plan
        falls back to the plain horizon with ``spec_ok=False`` and the
        slot simply decodes through decode_window (degraded throughput,
        never degraded correctness)."""
        p = len(tokens)
        bs = self.block_size
        if self._recurrent:
            # blocks without the state that goes with them resume
            # nothing: no lookup, no reuse, the whole prompt recomputes
            # (a parked row too), and the refusal is counted
            matched = []
            self.prefix_refused["recurrent_state"] += 1
        else:
            matched = self._radix.match(tokens)  # +1 ref each, ours now
        # full blocks only, and never the whole prompt: the last token
        # must be recomputed so the admit has logits to sample from
        reuse = min(len(matched), (p - 1) // bs)
        # the suffix pads to a bucket and the prefill window must fit
        # the logical cache: shrinking reuse widens the recompute
        # window, terminating by submit()'s guarantee that the cold
        # bucket fits. Buckets stay canonical (engine._bucket) so warm
        # admits share the cold traces' compile cache.
        while reuse > 0 and reuse * bs + _bucket(p - reuse * bs) > \
                self.cache_len:
            reuse -= 1
        if reuse < len(matched):
            self._pool.unref(matched[reuse:])
        shared = matched[:reuse]
        plain = -(-(p + max_new) // bs)  # ceil; fits() bounds it
        spec_ok = (
            self.spec_draft is not None
            and p + max_new + self.spec_k <= self.cache_len
        )
        total = -(-(p + max_new + self.spec_k) // bs) if spec_ok else plain
        ev_before = self._radix.stats()["evictions"]
        if not self._radix.ensure_free(total - reuse):
            # drop the verify slack first: a spec-capable plan must
            # never fail an admission the plain plan could serve
            if spec_ok and total > plain and \
                    self._radix.ensure_free(plain - reuse):
                total, spec_ok = plain, False
            else:
                if shared:
                    self._pool.unref(shared)
                # the fail-fast precheck (kv_blocks.ensure_free) means
                # this fires WITHOUT stripping the trie when the
                # shortfall is structural; the detail says which case
                # the post-mortem is looking at (free+evictable < need
                # = pinned by live rows)
                self._note("backpressure", req=rid, prompt_tokens=p,
                           need_blocks=total - reuse,
                           free_blocks=self._pool.free_blocks,
                           evictable_blocks=self._radix.evictable_blocks(),
                           reason="pool pinned beyond eviction reach")
                return None
        evicted = self._radix.stats()["evictions"] - ev_before
        if evicted:
            self._note("evict", nodes=evicted, need_blocks=total - reuse)
        fresh = self._pool.alloc(total - reuse)
        self._radix.note_result(reuse)
        table_row = np.zeros(self.max_blocks, np.int32)
        table_row[:reuse] = shared
        table_row[reuse:total] = fresh
        own_mask = np.zeros(self.max_blocks, bool)
        own_mask[reuse:total] = True
        return table_row, own_mask, reuse, total, spec_ok

    def _admit(self, slot: int, req: _Request, kv_plan,
               tokens: list[int]) -> None:
        """Reserve ``slot`` for ``req`` and start its prefill. With
        chunking enabled and a long novel suffix, only a task is queued
        — ``_step_prefill`` dispatches one chunk per scheduler pass so
        decode steps interleave; otherwise (short suffix, chunking off)
        the whole suffix goes through ``_finalize_admit`` in one
        dispatch, exactly the pre-chunking admit."""
        table_row, own_mask, reuse, total, spec_ok = kv_plan
        resumed = bool(req.out_tokens)
        if not resumed:
            # first admission only: a readmit is not a queue exit (the
            # request's TTFT clock kept running while parked — it
            # already has tokens)
            req.t_admit = tracing.now()
            req.wait_window_s, req.wait_backlog_s = self._split_wait(
                req.t_submit, req.t_admit
            )
            _TRACER.record_span(
                "engine.queue_wait", start=req.t_submit, end=req.t_admit,
                parent=req.trace_parent, slot=slot,
                window_s=req.wait_window_s,
                backlog_s=req.wait_backlog_s,
                **self._span_ids(req),
            )
            if self._slo is not None:
                self._slo.observe(
                    "queue_wait", req.t_admit - req.t_submit,
                    t=req.t_admit,
                )
        self._slot_req[slot] = req
        self._slot_blocks[slot] = [int(b) for b in table_row[:total]]
        # the flag flips TRUE only when _finalize_admit also committed
        # the draft row; until then the slot is mid-prefill (inactive)
        # and never counted by the verify gate anyway
        self._slot_spec_ok[slot] = False
        req.tokens_at_admit = len(req.out_tokens)
        task = _PrefillTask(
            req=req, slot=slot, table_row=table_row, own_mask=own_mask,
            reuse=reuse, total=total, pos=reuse * self.block_size,
            tokens=tokens, resumed=resumed, spec_ok=spec_ok,
        )
        if self._next_chunk_len(task) is not None:
            self._prefills.append(task)
            return
        self._finalize_admit(task)

    def _split_wait(self, t_submit: float,
                    t_admit: float) -> tuple[float, float]:
        """(window, backlog) seconds of one first admission's queue
        wait. ``b`` is the first window boundary (the ``step_t`` of a
        decode or verify window) at or after ``t_submit``: up to it the
        request waited for the window in flight, which no admission
        can interrupt; after it, for something else (slots full, pool
        backpressure, another request's admit at the same boundary).
        No boundary before ``t_admit`` (idle engine): all of it is
        window. A wait longer than the ring remembers counts the
        oldest boundary it still has, which only moves time from
        backlog to window."""
        b = None
        for t in reversed(self._boundaries):
            if t < t_submit:
                break
            b = t
        if b is None or b >= t_admit:
            return t_admit - t_submit, 0.0
        return b - t_submit, t_admit - b

    def _next_chunk_len(self, task: _PrefillTask) -> int | None:
        """Chunk width for ``task``'s next dispatch, or None when the
        remaining suffix should finalize through ``_admit_slot``. A
        chunk is taken only while the POST-chunk tail still pads to a
        canonical bucket that fits the logical row — otherwise the
        final suffix is simply taken larger (still a canonical bucket,
        so the compile-shape set stays {C} ∪ prefill buckets)."""
        C = self.chunk_tokens
        if not C:
            return None
        rem = len(task.tokens) - task.pos
        if rem <= C:
            return None
        if task.pos + C + _bucket(rem - C) > self.cache_len:
            return None
        return C

    def _step_prefill(self) -> None:
        """Advance the oldest chunked prefill by AT MOST one dispatch —
        the scheduler's pass quantum, so a long cold prompt never
        blocks the decode batch for more than one chunk's latency
        (Sarathi-SC's stall-free schedule, PAPERS.md)."""
        with self._lock:
            task = self._prefills[0] if self._prefills else None
        if task is None:
            return
        if task.req.cancelled.is_set():
            with self._lock:
                if self._prefills and self._prefills[0] is task:
                    self._prefills.pop(0)
                    self._abort_prefill(task)
            return
        C = self._next_chunk_len(task)
        if C is None:
            with self._lock:
                if not self._prefills or self._prefills[0] is not task:
                    return  # stop() cleared the queue mid-pass
                self._prefills.pop(0)
                # lint: allow[blocking-under-lock] the tail-bucket admit compile (tens of seconds cold) deliberately spans _lock: slot tables and the prefill queue must swap atomically vs stop(); deployments prewarm (see class docstring)
                self._finalize_admit(task)
            return
        window = np.asarray(
            task.tokens[task.pos:task.pos + C], np.int32
        )[None]
        t0 = tracing.now()
        # device work outside the lock (first chunk of a width pays its
        # compile; stop() must still be able to fail the slots)
        with annotate("engine.chunk.dispatch", rid=task.req.rid,
                      slot=task.slot, tokens=C, pos=task.pos):
            # lint: allow[lock-discipline] scheduler thread is the only _state writer; see _loop
            self._state = _prefill_chunk(
                self.params, self._state, jnp.asarray(window),
                jnp.int32(task.pos), self.cfg,
                jnp.asarray(task.table_row), jnp.asarray(task.own_mask),
                wq_gspmd=self._sharded,
                **({"slot": jnp.int32(task.slot)}
                   if self._recurrent else {}),
            )
        task.pos += C
        self.chunks_total += 1
        self.prefill_tokens["computed"] += C
        # the dispatch is asynchronous and nothing is read back here:
        # t1 - t0 is the dispatch alone, and the chunk's device time
        # shows in the profile (jit__prefill_chunk), not in this record
        t1 = tracing.now()
        with self._lock:
            live_rows = sum(1 for r in self._slot_req if r is not None)
        # every chunk token is live prompt work — no bucket padding by
        # construction (intermediate chunks are exactly C tokens)
        self.profiler.record(
            "chunk", bucket=C, live_rows=live_rows,
            live_tokens=C, padded_tokens=0, start=t0, end=t1,
        )
        self._note("chunk", req=task.req.rid, slot=task.slot,
                   pos=task.pos, prompt_tokens=len(task.tokens))

    def _abort_prefill(self, task: _PrefillTask) -> None:
        """Drop a cancelled mid-chunk prefill (caller holds the lock).
        The row was never activated — its table is still all-null and
        ``active`` False — so releasing the block holds is the whole
        cleanup; no device state to touch."""
        slot, req = task.slot, task.req
        self._slot_req[slot] = None
        self._slot_spec_ok[slot] = False
        blocks, self._slot_blocks[slot] = self._slot_blocks[slot], []
        if blocks:
            self._pool.unref(blocks)
        req.t_done = tracing.now()
        self._note("retire", req=req.rid, slot=slot,
                   tokens=len(req.out_tokens),
                   freed_blocks=len(blocks), cancelled=True)
        req.done.set()

    def _finalize_admit(self, task: _PrefillTask) -> None:
        """Prefill the remaining suffix, sample the next token, and
        flip the slot live — one ``_admit_slot`` dispatch (caller holds
        the lock). For a resumed request the suffix counter equals the
        uninterrupted run's decode counter at the same position
        (_admit_slot folds prompt_len == original prompt + generated;
        stepper.decode_body folds offset + 1), so preempted and uninterrupted
        runs draw identical sampling noise — the token-identity
        invariant the preemption tests pin."""
        req, slot, tokens = task.req, task.slot, task.tokens
        reuse, total = task.reuse, task.total
        p = len(tokens)
        start = task.pos
        suffix_len = p - start
        t0 = tracing.now()
        T = _bucket(suffix_len)  # _next_chunk_len kept start + T fitting
        with annotate("engine.admit", rid=req.rid, slot=slot, bucket=T,
                      suffix_tokens=suffix_len,
                      cached_tokens=reuse * self.block_size):
            with annotate("engine.admit.host_prep"):
                padded = np.zeros((1, T), np.int32)
                padded[0, :suffix_len] = tokens[start:]
                # full effective-prompt id set computed host-side: the jit
                # only sees the suffix, but repetition penalty must cover
                # reused and pre-preemption tokens too
                seen_row = np.zeros((1, self.cfg.vocab_size), bool)
                seen_row[0, np.asarray(tokens, np.int64)] = True
                # explicit impl: stepper.sample_rows wraps with threefry2x32
                # and SlotState.rng is u32[B, 2]; deriving from the
                # default-impl PRNGKey would break under
                # jax_default_prng_impl=rbg (u32[4])
                key_data = jax.random.key_data(
                    jax.random.key(req.seed, impl="threefry2x32")
                ).astype(jnp.uint32)
            with annotate("engine.admit.dispatch"):
                self._state = _admit_slot(
                    self.params, self._state, jnp.asarray(padded),
                    jnp.int32(suffix_len), jnp.int32(start), jnp.int32(p),
                    self.cfg, jnp.int32(slot),
                    jnp.asarray(task.table_row), jnp.asarray(task.own_mask),
                    jnp.float32(req.temperature), jnp.int32(req.top_k),
                    jnp.float32(req.top_p), jnp.float32(req.rep_penalty),
                    key_data, jnp.asarray(seen_row), wq_gspmd=self._sharded,
                )
            counted = self.prefill_tokens
            counted["computed"] += suffix_len
            counted["cached"] += reuse * self.block_size
            counted["padded"] += T - suffix_len
            if self.spec_draft is not None and task.spec_ok:
                # draft-row prefill rides the same boundary: the draft has
                # no radix reuse (and no chunking — it is small enough not
                # to need either), so the FULL effective prompt recomputes
                # in one dispatch, compiled per full-prompt bucket. The
                # bucket fits by the same guards that admitted the target
                # (submit's fits() for fresh prompts, _pick_victim's bucket
                # check for readmits).
                Td = _bucket(p)
                dwin = np.zeros((1, Td), np.int32)
                dwin[0, :p] = tokens
                self._dstate = _admit_draft(
                    self._dparams, self._dstate, jnp.asarray(dwin),
                    jnp.int32(p), self._dcfg, jnp.int32(slot),
                )
                self._slot_spec_ok[slot] = True
            # cache the effective prompt's FULL blocks for later admits —
            # including this one's fresh blocks (their KV is committed by
            # the scatter above; the partial tail block stays private)
            full = 0 if self._recurrent else p // self.block_size
            if self.kv_dtype == "int8":
                # every owned full block was quantize-committed by the
                # scatter above (chunked prefills requantize the same
                # blocks — one logical commit, counted once here)
                self.quant_blocks_total += max(0, full - reuse)
            if full:
                self._radix.insert(
                    tokens, [int(b) for b in task.table_row[:full]]
                )
            if req.export_kv and full:
                # disaggregated prefill export (disagg/): capture the
                # committed full-block pages HERE — the scheduler thread is
                # the only safe _state reader (jit donation deletes buffers
                # under any racing HTTP-thread read), and right after the
                # insert above the trie holds exactly these blocks. The
                # fingerprints ride out of the trie walk
                # (match_with_fingerprints) so the wire's content addresses
                # are the very chain the router and importers recompute.
                idx = jnp.asarray(
                    np.asarray(task.table_row[:full], np.int32)
                )
                pages_k, pages_v = self._export_pages(idx)
                pairs = self._radix.match_with_fingerprints(
                    tokens[:full * self.block_size]
                )
                # the walk refs its matches for us; the slot already holds
                # these blocks, so the extra hold is returned immediately
                self._pool.unref([b for b, _ in pairs])
                req.kv_export = {
                    "pages_k": pages_k,
                    "pages_v": pages_v,
                    "fingerprints": [fp for _, fp in pairs],
                    "block_size": self.block_size,
                    "kv_dtype": self.kv_dtype,
                }
                if self.kv_dtype == "int8":
                    # committed pages are int8 — the scales travel with
                    # them so the importer lands bit-identical blocks (the
                    # partial tail block is NOT in table_row[:full] and
                    # never leaves the engine in bf16)
                    req.kv_export["scales_k"] = np.stack([
                        # lint: allow[host-sync] export capture (same boundary as pages_k above)
                        np.asarray(sk[idx]) for sk in self._state.scales_k
                    ])
                    req.kv_export["scales_v"] = np.stack([
                        # lint: allow[host-sync] export capture (same boundary as pages_k above)
                        np.asarray(sv[idx]) for sv in self._state.scales_v
                    ])
            # the prefill already produced the next generated token —
            # except in prefill-only mode (max_new == 0, the disagg export
            # role), where the sampled token is discarded: the request's
            # contract is "KV cached, nothing generated", and the decode
            # replica resamples token #1 itself from the identical
            # distribution (committed-blocks rule: it recomputes the last
            # prompt position)
            with annotate("engine.admit.readback"):
                # lint: allow[host-sync] admission boundary: the first token must reach the request result now
                first = int(self._state.last_token[slot])
            now = tracing.now()
            if req.max_new > 0:
                req.out_tokens.append(first)
                req.token_times.append(now)
            # a preemption readmit keeps the stamp from its original admit,
            # but a server-level resume (migration hand-off) never had one
            # in THIS engine — without the stamp here the server's TTFT
            # breakdown degrades to whole-request duration and the
            # import-vs-reprefill comparison measures the decode tail
            if not req.t_first:
                req.t_first = now
            # one profiler record per prefill dispatch, bracketing the
            # _admit_slot call + its host sync above. The dispatch's one
            # live token is the sampled token; the padding waste is the
            # bucket tail (T - suffix_len) the static shapes force us to
            # compute.
            live_rows = sum(1 for r in self._slot_req if r is not None)
            self.profiler.record(
                "prefill", bucket=T, live_rows=live_rows,
                live_tokens=suffix_len, padded_tokens=T - suffix_len,
                start=t0, end=now,
            )
            if task.resumed:
                self.resumed_total += 1
                self._note("resume", req=req.rid, slot=slot, suffix_bucket=T,
                           reuse_blocks=reuse, total_blocks=total,
                           preemptions=req.preemptions)
            else:
                self._note("admit", req=req.rid, slot=slot, suffix_bucket=T,
                           reuse_blocks=reuse, total_blocks=total)
            # span start: a FRESH admission's prefill phase begins at
            # t_admit — exactly where engine.queue_wait ends (the serving
            # breakdown is contiguous by construction, and with chunking
            # the intermediate chunk dispatches belong inside the prefill
            # phase). A readmit never exited a queue, so its span brackets
            # just the finalize dispatch.
            sp = _TRACER.start_span(
                "engine.prefill", parent=req.trace_parent,
                start=t0 if task.resumed else req.t_admit,
                slot=slot, prompt_tokens=p, bucket=T,
                reused_tokens=reuse * self.block_size, prefix_hit=reuse > 0,
                **self._span_ids(req),
            )
            sp.event("first-token", ts=now)
            _TRACER.finish(sp, end=now)
            self._maybe_retire(slot)

    def _maybe_retire(self, slot: int) -> None:
        req = self._slot_req[slot]
        if req is None:
            return
        finished = (
            req.cancelled.is_set()
            or len(req.out_tokens) >= req.max_new
            or (
                req.eos_id >= 0 and req.out_tokens
                and req.out_tokens[-1] == req.eos_id
            )
        )
        if finished:
            self._slot_req[slot] = None
            self._slot_spec_ok[slot] = False
            # a drain may have been streaming this slot; the slot id is
            # about to be reusable, and a stale cursor would make a
            # later drain stream the wrong blocks
            self._migrate_cursor.pop(slot, None)
            blocks, self._slot_blocks[slot] = self._slot_blocks[slot], []
            if blocks:
                # drop the slot's hold; blocks also cached in the trie
                # keep the trie's reference and stay reusable
                self._pool.unref(blocks)
            self._state = dataclasses.replace(
                self._state,
                active=self._state.active.at[slot].set(False),
                # the row's table goes all-null BEFORE its next decode
                # scatter: freed blocks may be re-issued to another
                # slot, and a stale table would keep writing into them
                tables=self._state.tables.at[slot].set(0),
            )
            req.t_done = tracing.now()
            self._note("retire", req=req.rid, slot=slot,
                       tokens=len(req.out_tokens),
                       freed_blocks=len(blocks),
                       cancelled=req.cancelled.is_set())
            sp = _TRACER.start_span(
                "engine.decode", parent=req.trace_parent,
                start=req.t_first or req.t_done, slot=slot,
                tokens=len(req.out_tokens),
                cancelled=req.cancelled.is_set(),
                **self._span_ids(req),
                # stamped whenever any token event below carries an
                # interpolated timestamp (fused windows observe one
                # bracket per K tokens, not one clock read per token) —
                # trace readers must not treat the events as per-step
                # measurements (docs/OBSERVABILITY.md, TPOT row)
                **({"kubeinfer.interpolated": True}
                   if req.interpolated else {}),
                **({"kubeinfer.spec_accepted": req.spec_accepted,
                    "kubeinfer.spec_rollbacks": req.spec_rollbacks}
                   if self.spec_draft is not None else {}),
            )
            for i, ts in enumerate(req.token_times[:_MAX_TOKEN_EVENTS]):
                sp.event("token", ts=ts, i=i)
            _TRACER.finish(sp, end=req.t_done)
            req.done.set()

    # -- preemptive scheduling --------------------------------------------

    def _park_slot(self, slot: int) -> None:
        """Preempt a decoding row: bump its committed full blocks into
        the radix trie (the trie's own +1 reference), release every
        slot hold, and free the slot. The readmit later radix-matches
        those exact blocks, so an unevicted park costs only the partial
        tail block's recompute — vLLM preempts by recomputing the WHOLE
        sequence; the trie is what makes parking nearly free here.
        Parked blocks sit at trie-only refcount 1, i.e. they stay LRU-
        evictable: a parked row can never pin the pool (eviction only
        degrades its resume toward a colder admit, never correctness).
        Caller holds the engine lock; lock order engine→radix→pool is
        preserved through the insert/unref below."""
        req = self._slot_req[slot]
        if req is None:
            return
        toks = req.prompt + req.out_tokens
        blocks, self._slot_blocks[slot] = self._slot_blocks[slot], []
        # the LAST generated token's KV is not committed yet (the next
        # decode step would have written it at the row's offset), so
        # only blocks fully inside [0, len-1) may enter the trie — a
        # block-aligned park would otherwise cache a block whose final
        # position is junk, poisoning every later content-addressed
        # match of it (the readmit itself recomputes the tail, but a
        # LONGER continuation would reuse the poisoned block verbatim)
        committed = toks[:-1]
        # a model with recurrent layers caches nothing: its readmit
        # recomputes the whole effective prompt (_plan_kv)
        full = 0 if self._recurrent else len(committed) // self.block_size
        if full:
            self._radix.insert(committed, blocks[:full])
        self._slot_req[slot] = None
        self._slot_spec_ok[slot] = False
        self._migrate_cursor.pop(slot, None)  # slot id becomes reusable
        if blocks:
            self._pool.unref(blocks)
        self._state = dataclasses.replace(
            self._state,
            active=self._state.active.at[slot].set(False),
            # all-null BEFORE the next decode scatter: freed blocks may
            # be re-issued to another slot, and a stale table would
            # keep writing into them
            tables=self._state.tables.at[slot].set(0),
        )
        req.t_parked = tracing.now()
        req.preemptions += 1
        self.preempted_total += 1
        self._parked.append(req)
        self._note("preempt", req=req.rid, slot=slot,
                   tokens=len(req.out_tokens),
                   cached_blocks=full, parked=len(self._parked))

    # -- live-session migration (drain) -----------------------------------

    def _mark_migrated(self, req: "_Request", streamed: int) -> None:
        """Complete ``req`` as MIGRATED: the terminal state a drained
        session reaches instead of done/failed. The waiter wakes with
        ``req.migrated`` set and out_tokens a PREFIX of the final
        answer — the serving layer re-routes with those tokens as the
        resume prefix, and token identity across the hop is the same
        position-folded-key invariant park/readmit pins. Scheduler
        thread only; safe with or without the lock (mutates only the
        request and monotonic counters)."""
        req.migrated = {
            "tokens": list(req.out_tokens),
            "blocks": int(streamed),
            "block_size": self.block_size,
            "kv_dtype": self.kv_dtype,
        }
        req.t_done = tracing.now()
        self.migrated_total += 1
        self._note("migrate", req=req.rid, tokens=len(req.out_tokens),
                   blocks=streamed)
        req.done.set()

    def _migrate_slot(self, slot: int, req: "_Request",
                      streamed: int) -> None:
        """Park-for-migrate: release ``slot`` exactly like ``_park_slot``
        — committed full blocks go into the radix trie — but the
        request completes as migrated instead of joining ``_parked``.
        The trie insert is load-bearing for the fallback story: if the
        router bounces the session back here (target died, or this was
        a rebalance and we undrain), the resume admit radix-matches
        these exact blocks and costs only the tail recompute."""
        with self._lock:
            if self._slot_req[slot] is not req:
                return  # retired or cancelled since the snapshot
            toks = req.prompt + req.out_tokens
            blocks, self._slot_blocks[slot] = self._slot_blocks[slot], []
            # same committed-blocks rule as _park_slot: the last
            # token's KV is uncommitted, so only blocks fully inside
            # [0, len-1) may be cached (or streamed — the export cursor
            # obeys the identical bound)
            committed = toks[:-1]
            full = len(committed) // self.block_size
            if full:
                self._radix.insert(committed, blocks[:full])
            self._slot_req[slot] = None
            self._slot_spec_ok[slot] = False
            self._migrate_cursor.pop(slot, None)
            if blocks:
                self._pool.unref(blocks)
            self._state = dataclasses.replace(
                self._state,
                active=self._state.active.at[slot].set(False),
                tables=self._state.tables.at[slot].set(0),
            )
        self._mark_migrated(req, streamed)

    def _step_drain(self) -> None:
        """One drain pass (scheduler thread only): sweep every queued
        population to a terminal state, then advance at most ONE live
        slot — stream one chunk of its committed blocks through
        ``migration_sink``, or park-and-migrate it once the stream has
        caught up with the decode head. One chunk per pass is the same
        quantum as chunked prefill and KV import: the decode windows
        between chunks keep emitting tokens — that interleave is what
        'migrate while decoding' means, and the catch-up always
        terminates because a pass streams chunk_blocks * block_size
        token positions while decode advances at most one window."""
        # never-admitted work first: it holds no KV, so 'migrating' it
        # is just handing the request (plus any resume prefix) back to
        # the router for placement elsewhere
        with self._lock:
            pending: list[_Request] = list(self._holdover)
            self._holdover.clear()
            pending.extend(self._parked)
            self._parked.clear()
            while True:
                try:
                    pending.append(self._queue.get_nowait())
                except queue.Empty:
                    break
            staged, self._staged = self._staged, []
            for req, _slot, kv_plan, _tokens in staged:
                # release the plan's block holds (same bookkeeping as
                # the cancelled-staged path in _admit_pending)
                table_row, _own, _reuse, total, _spec = kv_plan
                self._pool.unref([int(b) for b in table_row[:total]])
                pending.append(req)
        for req in pending:
            if req.cancelled.is_set():
                req.t_done = tracing.now()
                req.done.set()
                continue
            self._mark_migrated(req, streamed=0)
        sink = self.migration_sink
        stream = None  # (slot, req, toks, cursor, n, blocks)
        final = None  # (slot, req, cursor)
        with self._lock:
            # mid-prefill rows keep prefilling (they become decoding
            # rows in a pass or two); cancelled rows retire at the next
            # window boundary — neither is a migration candidate yet
            prefilling = {t.slot for t in self._prefills}
            for slot, req in enumerate(self._slot_req):
                if req is None or slot in prefilling \
                        or req.cancelled.is_set():
                    continue
                toks = req.prompt + req.out_tokens
                committed = (len(toks) - 1) // self.block_size
                cursor = self._migrate_cursor.get(slot, 0)
                if sink is not None and cursor < committed:
                    n = min(self.migration_chunk_blocks,
                            committed - cursor)
                    stream = (slot, req, toks, cursor, n, list(
                        self._slot_blocks[slot][cursor:cursor + n]
                    ))
                else:
                    # caught up (or no sink is wired — then nothing
                    # streams and the target resumes by re-prefill,
                    # warm off the trie insert if it lands back here)
                    final = (slot, req, cursor)
                break
        if stream is not None:
            slot, req, toks, cursor, n, blocks = stream
            bs = self.block_size
            # page capture off the lock: only this thread writes
            # _state, so the gather cannot race a donation; same
            # boundary as _finalize_admit's export capture
            idx = jnp.asarray(np.asarray(blocks, np.int32))
            pages_k, pages_v = self._export_pages(idx)
            # fingerprints recomputed from the tokens, not read from
            # the trie: the streamed blocks are slot-held (not yet
            # inserted), and the chain from token 0 is exactly what the
            # importer recomputes to verify the slice
            fps = prefix_fingerprints(toks[:(cursor + n) * bs], bs)
            chunk = {
                "start_block": cursor,
                "pages_k": pages_k,
                "pages_v": pages_v,
                "fingerprints": fps[cursor:cursor + n],
                "block_size": bs,
                "kv_dtype": self.kv_dtype,
            }
            if self.kv_dtype == "int8":
                # committed blocks are already quantized (window-
                # boundary commit), so the scales travel with the chunk
                chunk["scales_k"] = np.stack([
                        np.asarray(sk[idx]) for sk in self._state.scales_k
                ])
                chunk["scales_v"] = np.stack([
                        np.asarray(sv[idx]) for sv in self._state.scales_v
                ])
            try:
                sink(chunk)
            except Exception:  # noqa: BLE001 — sink is injected code
                # a broken sink must not wedge the drain: hand the
                # session off with what was already streamed; the
                # target re-prefills the rest from the last verified
                # chunk (or from scratch), token-identical either way
                self._note("migrate_sink_error", req=req.rid, slot=slot,
                           start_block=cursor)
                self._migrate_slot(slot, req, cursor)
                return
            with self._lock:
                self._migrate_cursor[slot] = cursor + n
                self.migration_chunks_total += 1
                self.migration_blocks_total += n
            self._note("migrate_chunk", req=req.rid, slot=slot,
                       start_block=cursor, blocks=n)
            return
        if final is not None:
            slot, req, cursor = final
            self._migrate_slot(slot, req, cursor)
            return
        # nothing to advance: drained once every population is empty
        # (prefills finish through their own stepper)
        with self._lock:
            live = (
                any(r is not None for r in self._slot_req)
                or bool(self._holdover) or bool(self._parked)
                or bool(self._prefills) or bool(self._staged)
            )
        if not live and self._queue.empty():
            self._drained.set()

    def _pick_victim(self, pol: PreemptionPolicy) -> int | None:
        """Lowest-priority preemptable row: the YOUNGEST-arrival active
        decoding slot (LIFO victim order keeps the oldest work running,
        matching the longest-pending-first admission order) that has
        decoded at least ``min_progress`` tokens since its own
        (re)admission and whose cold readmit would still fit a slot.
        Mid-prefill rows are never parked — their KV is half-committed
        and they produced nothing to cache. Caller holds the lock."""
        prefilling = {t.slot for t in self._prefills}
        victim, victim_t = None, -1.0
        for slot, req in enumerate(self._slot_req):
            if req is None or slot in prefilling:
                continue
            if len(req.out_tokens) - req.tokens_at_admit < \
                    max(1, pol.min_progress):
                continue
            # a parked row readmits with effective prompt = prompt +
            # generated; if the trie got evicted meanwhile the resume
            # is COLD, so the full bucket must still fit the row
            if _bucket(len(req.prompt) + len(req.out_tokens)) > \
                    self.cache_len:
                continue
            if req.t_submit > victim_t:
                victim, victim_t = slot, req.t_submit
        return victim

    def _maybe_preempt(self) -> None:
        """Park one decoding row for the longest-pending waiter when
        queue-wait pressure crosses the policy's burn-rate threshold.
        At most one preemption per call, gated by the cooldown — the
        scheduler never mass-evicts its own batch."""
        pol = self.preemption
        if pol is None or self._slo is None:
            return
        with self._lock:
            waiter = self._holdover[0] if self._holdover else None
            free = any(r is None for r in self._slot_req)
        if waiter is None or free:
            return
        with annotate("engine.preempt_check"):
            now = tracing.now()
            wait = now - waiter.pending_since
            if wait < pol.threshold_s or \
                    self._steps_since_preempt < pol.cooldown_steps:
                return
            # feed the live head-wait in: a fully wedged engine admits
            # nothing, so admit-time observations alone would never show
            # the burn rising exactly when preemption is needed most
            self._slo.observe("queue_wait", wait, t=now)
            burn = max(self._slo.burn_rates(now=now)["queue_wait"].values())
            if burn < pol.burn_limit:
                return
            with self._lock:
                victim = self._pick_victim(pol)
                if victim is None:
                    return
                self._park_slot(victim)
            self._steps_since_preempt = 0
            # admit the waiter into the freed slot NOW — the parked victim
            # re-enters the pending order behind it (pending_since just
            # reset), so each preemption transfers the slot to strictly
            # older work
            self._admit_pending()

    def _place(self, req: "_Request") -> bool:
        """Admit one pending request into a free slot; False stashes it
        back at the front of the holdover (all slots busy, or pool
        backpressure). Caller must NOT hold the lock."""
        if req.cancelled.is_set():
            req.t_done = tracing.now()
            req.done.set()
            return True
        with self._lock:
            for slot in range(self.n_slots):
                if self._slot_req[slot] is None:
                    tokens = req.prompt + req.out_tokens
                    kv_plan = self._plan_kv(
                        tokens, req.max_new - len(req.out_tokens),
                        rid=req.rid,
                    )
                    if kv_plan is None:
                        break  # pool backpressure: hold until a retire
                    # lint: allow[blocking-under-lock] known ceiling: the admit-path jit compile (cold bucket ~tens of seconds) runs under _lock so stop() sees a consistent slot/pool state; stats_summary went lockless for exactly this reason (PR 6)
                    self._admit(slot, req, kv_plan, tokens)
                    return True
            # front, not back: this was the oldest pending request and
            # must stay first in line
            self._holdover.appendleft(req)
        return False

    def _pop_pending(self) -> "_Request | None":
        """Longest-pending-first admission order across the three
        waiting populations: the holdover deque, the parked list, and
        the arrival queue (pulled through the holdover so its head's
        age is comparable). This order is the anti-livelock guarantee:
        a just-parked victim's ``pending_since`` restarts at its park
        time, so it can never preempt-loop ahead of the waiter it was
        parked for."""
        with self._lock:
            if not self._holdover:
                try:
                    self._holdover.append(self._queue.get_nowait())
                except queue.Empty:
                    pass
            hold = self._holdover[0] if self._holdover else None
            park = self._parked[0] if self._parked else None
            if park is not None and (
                hold is None or park.pending_since <= hold.pending_since
            ):
                return self._parked.pop(0)
            if hold is not None:
                return self._holdover.popleft()
            return None

    def _admit_pending(self) -> None:
        """Place pending requests (parked readmits and arrivals, oldest
        first) until something has to wait — all slots busy, or pool
        backpressure. Plans staged by ``_plan_admissions`` while the
        last decode window was in flight go first: their radix/alloc
        work is already done, and they were popped from the pending
        order ahead of whatever is still queued."""
        with annotate("engine.admit_pending") as span:
            with self._lock:
                staged, self._staged = self._staged, []
            placed = 0
            for req, slot, kv_plan, tokens in staged:
                with self._lock:
                    if req.cancelled.is_set() or \
                            self._slot_req[slot] is not None:
                        # release the plan's block holds; a cancelled
                        # request retires unserved, an occupied slot (only
                        # reachable through a future scheduler change —
                        # this thread is the sole admitter) sends the
                        # request back to the head of the line
                        table_row, _own, _reuse, total, _spec = kv_plan
                        self._pool.unref(
                            [int(b) for b in table_row[:total]]
                        )
                        if req.cancelled.is_set():
                            req.t_done = tracing.now()
                            req.done.set()
                        else:
                            self._holdover.appendleft(req)
                        continue
                    # lint: allow[blocking-under-lock] same ceiling as _place: the admit-path jit compile (cold bucket ~tens of seconds) runs under _lock so stop() sees a consistent slot/pool state
                    self._admit(slot, req, kv_plan, tokens)
                    placed += 1
            while True:
                req = self._pop_pending()
                if req is None or not self._place(req):
                    break
                placed += 1
            span.set_metadata(placed=placed)

    def _plan_admissions(self) -> None:
        """The host half of admission, overlapped with the in-flight
        decode window: pop pending requests (same longest-pending-first
        order as ``_admit_pending``) and run radix match + reuse clamp
        + block alloc, staging ``(req, slot, plan, tokens)`` for the
        next window boundary. No device dispatch and no readback
        happens here, so the whole pass runs while the device chews
        the window; the jit admits (which may compile for tens of
        seconds) stay at the boundary."""
        with annotate("engine.plan_admissions") as span:
            staged = 0
            while True:
                with self._lock:
                    taken = {s for _r, s, _p, _t in self._staged}
                    free = [
                        s for s in range(self.n_slots)
                        if self._slot_req[s] is None and s not in taken
                    ]
                if not free:
                    break
                req = self._pop_pending()
                if req is None:
                    break
                if req.cancelled.is_set():
                    req.t_done = tracing.now()
                    req.done.set()
                    continue
                with self._lock:
                    tokens = req.prompt + req.out_tokens
                    kv_plan = self._plan_kv(
                        tokens, req.max_new - len(req.out_tokens),
                        rid=req.rid,
                    )
                    if kv_plan is None:
                        self._holdover.appendleft(req)
                        break
                    self._staged.append((req, free[0], kv_plan, tokens))
                    staged += 1
            span.set_metadata(staged=staged)

    def _read_moe_stats(self) -> None:
        """The routed experts' device-side counters, read where the
        window's tokens are (both are outputs of the program that has
        just been waited for, so this adds no synchronisation). The
        device's u32 sums wrap; the differences do not."""
        if not self._state.moe_stats:
            return
        now = np.asarray(self._state.moe_stats[0])
        seen = self._moe_seen if self._moe_seen is not None \
            else np.zeros_like(now)
        for name, d in zip(MOE_STATS, (now - seen).tolist()):
            self.moe_counts[name] += d
        self._moe_seen = now

    def _pick_horizon(self, budgets: list[int], host_work: bool) -> int:
        """Decode-window horizon for this pass, from the static bucket
        set (one compiled shape each). K collapses to 1 whenever the
        host has competing work — pending admissions, chunked prefills,
        a cancelled row — so fused windows never starve admission,
        prefill interleave, or retirement; otherwise
        K is the largest bucket no row can overshoot (min remaining
        budget), so ``max_new`` is never crossed mid-window, every
        retirement lands exactly at a window boundary, and every write
        stays inside the row's allocated block span. SLO burn needs no
        separate clamp: preemption pressure requires a waiter, and any
        waiter already forces K=1 (the preemption check itself runs
        between windows, so parks land at boundaries too)."""
        if host_work or not budgets:
            return 1
        lim = min(min(budgets), self.max_window)
        k = 1
        for b in self._window_buckets:
            if b <= lim:
                k = b
        return k

    def _loop(self) -> None:
        while not self._stop.is_set():
            with annotate("engine.pass") as span:
                self._pass(span)
        # epilogue: anything published after stop()'s sweep (admission
        # was mid-compile during the snapshot) is released here — the
        # last observer of the handoff fields cleans up
        if self._stop.is_set():
            self._fail_inflight()

    def _pass(self, span) -> None:
        """One scheduler pass, inside its ``engine.pass`` span (every
        other ``engine.*`` span of this thread opens under it)."""
        # staged KV imports first (at most one per pass): an import
        # usually precedes the very request that wants its blocks,
        # so servicing it ahead of admissions turns that request's
        # admit into a warm one instead of a cold prefill
        self._step_import()
        with self._lock:
            busy = any(r is not None for r in self._slot_req)
            idle = not busy and not self._parked
            have_holdover = bool(self._holdover)
        if idle:
            if self._draining:
                # drain sweeps the queue itself (racing submits
                # land there past the lockless refusal) and flips
                # _drained once every population is empty
                with annotate("engine.drain"):
                    self._step_drain()
                with annotate("engine.idle_wait"):
                    self._stop.wait(0.05)
                return
            # fully idle: block briefly for the next arrival
            if not have_holdover:
                try:
                    with annotate("engine.idle_wait"):
                        nxt = self._queue.get(timeout=0.05)
                except queue.Empty:
                    return
                with self._lock:
                    self._holdover.append(nxt)
            self._admit_pending()
            return
        # live work: non-blocking admissions, a preemption check
        # when the waiters' SLO pressure warrants one, then one
        # step of each active machine — the decode batch and at most
        # ONE prefill chunk advance in lockstep per loop pass, so
        # neither starves the other. This interleave is the tentpole:
        # prefill stopped being one atomic dispatch and became
        # schedulable work competing with decode under an explicit
        # policy.
        if self._draining:
            # admission and preemption stand down; the drain pass
            # streams one chunk (or finalizes one caught-up slot)
            # and the decode window below keeps the batch emitting
            # tokens between chunks
            with annotate("engine.drain"):
                self._step_drain()
        else:
            self._admit_pending()
            self._maybe_preempt()
        with self._lock:
            # mid-prefill rows are reserved but not yet decoding
            # (active=False, null tables); they are padding in the
            # decode dispatch, not live rows
            prefilling = {t.slot for t in self._prefills}
            budgets = [
                r.max_new - len(r.out_tokens)
                for s, r in enumerate(self._slot_req)
                if r is not None and s not in prefilling
            ]
            decode_rows = len(budgets)
            # verify windows are all-or-nothing: a live row whose
            # admit fell back to the plain block budget (_plan_kv
            # spec_ok=False) has no +spec_k slack, and the fused
            # dispatch cannot exclude single rows — so any such row
            # drops the whole batch to plain decode until it
            # retires or parks
            spec_ready = self.spec_draft is not None and bool(
                budgets
            ) and all(
                self._slot_spec_ok[s]
                for s, r in enumerate(self._slot_req)
                if r is not None and s not in prefilling
            )
            waiting = len(self._holdover)
            host_work = (
                bool(self._holdover) or bool(self._parked)
                or bool(self._prefills)
                or any(
                    r is not None and r.cancelled.is_set()
                    for r in self._slot_req
                )
            )
        # arrival-queue peek outside the lock (qsize takes the
        # queue's own lock); a racing submit only costs one pass
        # of K=1 or one window of delayed admission — never
        # correctness
        host_work = host_work or not self._queue.empty()
        # draining forces K=1: short windows keep the chunk stream
        # close behind the decode head, so the park-and-move tail
        # (and the drain itself) lands sooner
        host_work = host_work or self._draining
        if profiling():
            # qsize takes the queue's lock: only for a recorded span
            span.set_metadata(
                decode_rows=decode_rows,
                queue_depth=self._queue.qsize() + waiting,
            )
        if decode_rows and spec_ready:
            # the speculative twin of the fused branch below: one
            # verify dispatch advances every row by 1..spec_k+1
            # tokens (data-dependent, unlike the fixed-K window),
            # and the boundary drain is where accept/rollback meets
            # the scheduler — truncation below always coincides
            # with retirement, so discarded device progress never
            # leaks into a continuing row
            step_t0 = tracing.now()
            with annotate("engine.verify.dispatch", k=self.spec_k,
                          rows=decode_rows):
                # lint: allow[lock-discipline] scheduler thread is the only _state writer; see comment above
                self._state, self._dstate, tokens = verify_window(
                    self.params, self._state, self._dparams,
                    self._dstate, self.cfg, self._dcfg, self.spec_k,
                    sharded=self._sharded,
                )
            if not self._draining:
                # a drain must not stage new plans (their block
                # holds would just be unwound by the next sweep)
                self._plan_admissions()
            with annotate("engine.verify.readback"):
                # lint: allow[host-sync] window boundary: the [n_slots, spec_k+1] token matrix feeds the Python result queues
                toks = np.asarray(tokens)
            step_t = tracing.now()
            self._boundaries.append(step_t)
            self.windows_total += 1
            self._steps_since_preempt += self.spec_k
            accepted = 0
            with annotate("engine.verify.emit") as emit, self._lock:
                for slot in range(self.n_slots):
                    req = self._slot_req[slot]
                    row = toks[slot]
                    n_dev = int((row >= 0).sum())
                    if req is None or n_dev == 0:
                        continue
                    self.spec_draft_tokens += self.spec_k
                    if self.kv_dtype == "int8":
                        # offset invariant: p + emitted - 1 (the
                        # newest token's KV is uncommitted); the
                        # device advanced this row n_dev positions
                        # and quantize-committed one block per
                        # boundary crossing
                        old = len(req.prompt) \
                            + len(req.out_tokens) - 1
                        self.quant_blocks_total += (
                            (old + n_dev) // self.block_size
                            - old // self.block_size
                        )
                    # device acceptance may overshoot the request
                    # budget or run past EOS (the window cannot
                    # stop mid-dispatch); the host emits the
                    # truncated prefix and every truncation lands
                    # on a retirement below, so the row's advanced
                    # device state is discarded, never resumed —
                    # that is what keeps truncation identity-safe
                    n_host = min(n_dev, req.max_new
                                 - len(req.out_tokens))
                    if req.eos_id >= 0:
                        for i in range(n_host):
                            if int(row[i]) == req.eos_id:
                                n_host = i + 1
                                break
                    for j in range(n_host):
                        t_j = step_t0 + (j + 1) * (
                            step_t - step_t0) / n_host
                        req.out_tokens.append(int(row[j]))
                        req.token_times.append(t_j)
                    if n_host > 1:
                        req.interpolated = True
                    accepted += n_host
                    # n_dev = accepted drafts + the bonus token
                    # the verify forward samples past the last
                    # accepted draft, so drafts-accepted is n_dev-1
                    acc_d = n_dev - 1
                    self.spec_accepted_tokens += acc_d
                    req.spec_accepted += acc_d
                    if acc_d < self.spec_k:
                        self.spec_rollbacks += 1
                        req.spec_rollbacks += 1
                    self._maybe_retire(slot)
                emit.set_metadata(tokens=accepted)
            # ONE record per verify dispatch, phase "verify" so the
            # decode-dispatches-per-token summary and the compile
            # proxy (first-seen phase/bucket) stay honest about
            # which compiled shape ran; bucket is spec_k (one
            # compiled verify shape per K)
            self.profiler.record(
                "verify", bucket=self.spec_k,
                live_rows=decode_rows, live_tokens=accepted,
                padded_tokens=(
                    self.n_slots * (self.spec_k + 1) - accepted
                ),
                start=step_t0, end=step_t, steps=self.spec_k,
            )
        elif decode_rows:
            k = self._pick_horizon(budgets, host_work)
            # device window outside the lock (it can block on a
            # compile; stop() must still be able to fail the slots)
            step_t0 = tracing.now()
            with annotate("engine.decode.dispatch", k=k,
                          rows=decode_rows):
                # lint: allow[lock-discipline] scheduler thread is the only _state writer; see comment above
                self._state, tokens = decode_window(
                    self.params, self._state, self.cfg, k,
                    sharded=self._sharded,
                )
            # the dispatch returns a future immediately (JAX async
            # dispatch): the admission planning below is the host
            # work overlapped with the device window, and the
            # readback after it is the one synchronization point
            if not self._draining:
                # same stand-down as the verify branch: no new
                # plans while draining
                self._plan_admissions()
            with annotate("engine.decode.readback"):
                # the routed experts' counters leave the device with
                # the tokens, not in a second round trip behind them
                for stats in self._state.moe_stats:
                    stats.copy_to_host_async()
                # lint: allow[host-sync] window boundary: the [n_slots, k] token matrix feeds the Python result queues
                toks = np.asarray(tokens)
                self._read_moe_stats()
            # one clock read per WINDOW, outside the lock: token
            # times inside the bracket are interpolated below
            # (docs/OBSERVABILITY.md — traces carry
            # kubeinfer.interpolated so nobody reads them as
            # per-step measurements)
            step_t = tracing.now()
            self._boundaries.append(step_t)
            self.windows_total += 1
            self._steps_since_preempt += k
            accepted = 0
            with annotate("engine.decode.emit") as emit, self._lock:
                if self.kv_dtype == "int8":
                    # every decoding row advanced k positions on
                    # the device (retirement is host work below);
                    # one tail block quantize-commits per boundary
                    # crossing. Offset invariant: p + emitted - 1.
                    for s, r in enumerate(self._slot_req):
                        if r is None or s in prefilling:
                            continue
                        old = len(r.prompt) + len(r.out_tokens) - 1
                        self.quant_blocks_total += (
                            (old + k) // self.block_size
                            - old // self.block_size
                        )
                for j in range(k):
                    t_j = step_t0 + (j + 1) * (step_t - step_t0) / k
                    for slot in range(self.n_slots):
                        # host-side EOS masking: _maybe_retire
                        # clears _slot_req at the EOS/budget token,
                        # so a retired row's tail tokens in the
                        # same window fall through the req-is-None
                        # check — the device kept scattering junk
                        # into the row's own refcounted blocks,
                        # which nobody reads (same null-block
                        # discipline as retirement, and always
                        # inside the row's allocated span by the
                        # horizon clamp)
                        req = self._slot_req[slot]
                        if req is None or toks[slot, j] < 0:
                            continue
                        req.out_tokens.append(int(toks[slot, j]))
                        req.token_times.append(t_j)
                        if k > 1:
                            req.interpolated = True
                        accepted += 1
                        self._maybe_retire(slot)
                emit.set_metadata(tokens=accepted)
            # ONE record per fused dispatch: bucket=k is the
            # compiled-shape knob (first-seen per window bucket ==
            # one compile each), live_tokens counts only tokens
            # that reached a request — inactive rows and masked
            # post-EOS tails are padding of the n_slots x k window
            self.profiler.record(
                "decode", bucket=k, live_rows=decode_rows,
                live_tokens=accepted,
                padded_tokens=self.n_slots * k - accepted,
                start=step_t0, end=step_t, steps=k,
            )
        self._step_prefill()  # at most one chunk per pass
