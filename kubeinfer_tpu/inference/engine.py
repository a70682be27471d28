"""Static-shape generation engine: prefill + KV-cache decode.

XLA-friendly by construction (SURVEY.md §7 / task brief "no
data-dependent Python control flow inside jit"):

- prompts pad to bucketed lengths (powers of two), so prefill compiles
  once per bucket;
- the decode loop is ONE jitted ``lax.scan`` over ``max_new_tokens``
  steps writing into a fixed-capacity KV cache — no per-token dispatch,
  no dynamic shapes; finished sequences (EOS) keep stepping but their
  outputs are masked (the standard static-shape idiom). The loop itself
  lives in stepper.py (shared with the sequence-parallel engine and the
  continuous batcher's fused windows — ROADMAP item 3's unification);
- sampling is greedy or temperature (gumbel trick) selected by a traced
  scalar, so one compilation serves both.

The engine is deliberately single-batch-slot-array: request batching
happens by stacking prompts into the [B] axis (the server batches
per-request today; continuous batching slots into the same static
shapes).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from kubeinfer_tpu.inference.config import ModelConfig
from kubeinfer_tpu.inference.flash_attention import (
    attention_auto,
    flash_attention_ragged,
    flash_available,
)
from kubeinfer_tpu.inference.model import Params, forward
from kubeinfer_tpu.observability import tracing

_TRACER = tracing.get_tracer("engine")

PROMPT_BUCKETS = (
    16, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384, 32768,
    65536, 131072,  # long-context models advertise up to 128k positions
)
# Prefill processes the prompt in chunks of this many tokens (peak
# attention memory O(chunk * cache_len), not O(T^2)); every bucket > 512
# is a multiple of it.
PREFILL_CHUNK = 512
# Per-call prefill token budget: larger chunks amortize the per-chunk
# weight sweep and per-tile entry costs (measured on v5e at the 280M
# bench model, 2048-token prompt: chunk 512 -> 0.37 MFU, 2048 -> 0.46),
# while the budget bounds the transient [B, C, *] activation memory as
# the batch grows. The full-vocab head no longer scales with C
# (chunked_prefill applies it once on the selected rows).
PREFILL_TOKEN_BUDGET = 2048


def prefill_chunk_for(batch: int, prompt_bucket: int) -> int:
    """Adaptive prefill chunk: as much of the token budget as one row's
    bucket can use, never below PREFILL_CHUNK (the long-prompt floor).

    Floored to a power of two so the chunk always DIVIDES the bucket
    (PROMPT_BUCKETS are powers of two; PREFILL_CHUNK is too): a
    non-dividing chunk would make the scan's final dynamic_slice clamp
    its start and silently re-process tokens at wrong RoPE/cache
    positions (review-found with batch=3)."""
    per_row = max(PREFILL_TOKEN_BUDGET // max(batch, 1), 1)
    pow2 = 1 << (per_row.bit_length() - 1)
    return min(prompt_bucket, max(PREFILL_CHUNK, pow2))


def _bucket(n: int) -> int:
    for b in PROMPT_BUCKETS:
        if n <= b:
            return b
    raise ValueError(f"prompt length {n} exceeds {PROMPT_BUCKETS[-1]}")


@dataclass
class GenerationResult:
    tokens: np.ndarray  # i32[B, max_new] generated ids (EOS-padded)
    lengths: np.ndarray  # i32[B] generated length per sequence


# Static cap for the top-k filter: lax.top_k needs a static k, so the
# kth-largest threshold reads from a fixed [.., TOP_K_CAP] candidate
# slice; requested k above the cap clips to it (k=64 is already far past
# any practically distinguishable nucleus).
TOP_K_CAP = 64


def filter_logits(
    logits: jax.Array,  # f32[..., V]
    top_k: jax.Array,  # i32 broadcastable to logits[..., 0]; <1 = off
    top_p: jax.Array,  # f32 broadcastable to logits[..., 0]; >=1 = off
) -> jax.Array:
    """Top-k then nucleus (top-p) filtering: non-kept logits -> -inf.

    Both knobs are traced (per-row in the continuous batcher), so the
    expensive parts — the top-k candidate scan and the full-vocab sort
    nucleus needs — sit behind ``lax.cond`` on "any row has the filter
    on": a disabled filter costs nothing per decode step at runtime.
    Order matches HF: temperature scaling happens in the caller BEFORE
    filtering, so top-p nuclei are computed on the tempered
    distribution. One documented divergence (advisor r2): the nucleus
    cut is a probability THRESHOLD, so vocab entries exactly tying the
    boundary token's probability are all kept — a slightly wider nucleus
    than HF's shift-right positional cutoff on exact ties (e.g. sorted
    probs [.4, .3, .3] at top_p=0.7 keep 3 here, 2 in HF). Exact
    probability ties are measure-zero for real logits; the threshold
    form avoids a scatter back through argsort indices on TPU.
    """
    V = logits.shape[-1]
    cap = min(TOP_K_CAP, V)
    lead = logits.shape[:-1]
    top_k = jnp.broadcast_to(top_k, lead)
    top_p = jnp.broadcast_to(top_p, lead)

    def apply_topk(x):
        topvals = jax.lax.top_k(x, cap)[0]  # [..., cap] descending
        k_idx = jnp.clip(top_k - 1, 0, cap - 1)[..., None]
        kth = jnp.take_along_axis(topvals, k_idx, axis=-1)
        on = (top_k >= 1)[..., None]
        return jnp.where(on & (x < kth), -jnp.inf, x)

    def apply_topp(x):
        probs = jax.nn.softmax(x, axis=-1)
        sorted_p = jnp.flip(jnp.sort(probs, axis=-1), axis=-1)
        cum_excl = jnp.cumsum(sorted_p, axis=-1) - sorted_p
        keep_sorted = cum_excl < top_p[..., None]
        # the argmax always survives, even for top_p <= 0 (where the
        # cumulative test keeps nothing and sampling would otherwise
        # collapse to token id 0 via an all -inf row)
        keep_sorted = keep_sorted.at[..., 0].set(True)
        thresh = jnp.min(
            jnp.where(keep_sorted, sorted_p, jnp.inf), axis=-1,
            keepdims=True,
        )
        on = (top_p < 1.0)[..., None]
        return jnp.where(on & (probs < thresh), -jnp.inf, x)

    logits = jax.lax.cond(
        jnp.any(top_k >= 1), apply_topk, lambda x: x, logits
    )
    return jax.lax.cond(
        jnp.any(top_p < 1.0), apply_topp, lambda x: x, logits
    )


def seen_from_prompt(
    prompt: jax.Array,  # i32[B, T] 0-padded
    prompt_len: jax.Array,  # i32[B]
    vocab: int,
) -> jax.Array:
    """bool[B, V]: which vocab ids appear in each row's real prompt.
    Pad columns are excluded (id 0 would otherwise always count).

    Scatter-max, NOT a one-hot contraction: a [B, T, V] one-hot at
    production vocab (152k) and a 4096 bucket is ~20GB of f32 — the
    scatter is O(B*T) work into the O(B*V) output. It runs once per
    generate(), so the TPU scatter-serialization cost is irrelevant.
    """
    B, T = prompt.shape
    valid = jnp.arange(T)[None, :] < prompt_len[:, None]
    return (
        jnp.zeros((B, vocab), bool)
        .at[jnp.arange(B)[:, None], prompt]
        .max(valid)
    )


def record_seen(
    seen: jax.Array,  # bool[B, V]
    tokens: jax.Array,  # i32[B] newly generated ids
    penalty: jax.Array,  # f32 broadcastable to [B]; 1.0 = disabled
) -> jax.Array:
    """Mark freshly generated ids as seen — behind the same disabled
    check as the penalty itself, so penalty-free decodes don't pay a
    [B, V] update per step."""

    def update(s):
        B = tokens.shape[0]
        return s.at[jnp.arange(B), tokens].max(True)

    return jax.lax.cond(jnp.any(penalty != 1.0), update, lambda s: s, seen)


def apply_repetition_penalty(
    logits: jax.Array,  # f32[B, V]
    seen: jax.Array,  # bool[B, V] ids present in prompt or generated
    penalty: jax.Array,  # f32 broadcastable to [B]; 1.0 = disabled
) -> jax.Array:
    """HF RepetitionPenaltyLogitsProcessor semantics: seen ids get
    logit/penalty when positive, logit*penalty when negative. Runs on
    RAW logits before temperature, and — unlike the top-k/top-p
    filters — affects the greedy argmax too (it reshapes the
    distribution, not just the sampling set). Behind lax.cond: disabled
    costs nothing per step."""
    penalty = jnp.broadcast_to(jnp.asarray(penalty, jnp.float32),
                               logits.shape[:-1])

    def apply(x):
        pen = penalty[..., None]
        adj = jnp.where(x > 0, x / pen, x * pen)
        return jnp.where(seen, adj, x)

    return jax.lax.cond(
        jnp.any(penalty != 1.0), apply, lambda x: x, logits
    )


def gumbel_pick(
    raw_logits: jax.Array,
    filtered_scaled: jax.Array,
    key: jax.Array,
    temperature: jax.Array,
) -> jax.Array:
    """Final sampling step shared by every path: greedy argmax on the
    RAW logits when temperature <= 0, gumbel-argmax on the pre-tempered,
    pre-filtered logits otherwise. Split out so the continuous batcher
    can run ``filter_logits`` once at batch level (its lax.cond
    fast-path dies under vmap — a batched predicate lowers to select)
    and still share this exact pick."""
    greedy = jnp.argmax(raw_logits, axis=-1).astype(jnp.int32)
    g = jax.random.gumbel(key, raw_logits.shape, jnp.float32)
    sampled = jnp.argmax(filtered_scaled + g, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0, sampled, greedy)


def gumbel_sample(
    logits: jax.Array,
    key: jax.Array,
    temperature: jax.Array,
    top_k: jax.Array | int = 0,
    top_p: jax.Array | float = 1.0,
) -> jax.Array:
    """Temperature sampling via the gumbel trick; temperature <= 0 means
    greedy (filters don't apply — argmax always survives both). ONE home
    for the sampling math — the per-request engine and the continuous
    batcher must sample identically for the same params.
    """
    scaled = logits / jnp.maximum(temperature, 1e-6)
    filtered = filter_logits(
        scaled, jnp.asarray(top_k, jnp.int32), jnp.asarray(top_p, jnp.float32)
    )
    return gumbel_pick(logits, filtered, key, temperature)


def chunked_prefill(
    params: Params,
    prompt: jax.Array,  # i32[B, T] left-aligned, 0-padded
    prompt_len: jax.Array,  # i32[B]
    cfg: ModelConfig,
    caches,  # per-layer (k, v) fixed-capacity caches
    prefill_chunk: int,
):
    """Scan the prompt through the model in fixed-size chunks, filling
    the KV caches; returns (caches, next_logits) where next_logits[b] is
    the logits at row b's LAST real prompt position.

    The per-request engine's prefill, and the single-chip reference
    the sequence-parallel KV hand-off is held to
    (tests/test_inference_sp_serving.py): peak attention memory is
    O(chunk * cache_len), one trace serves any prompt bucket, and the
    logits come from each row's last real position. Trace-time cost
    only: callers jit.
    """
    B, T = prompt.shape
    cache_len = caches[0][0].shape[1]
    C = min(T, prefill_chunk)
    pos = jnp.arange(cache_len)
    last = jnp.clip(prompt_len - 1, 0, T - 1)
    D = cfg.head_dim
    # static branch: kernel vs dense is decided by shapes/backend at
    # trace time, so only one path exists in the compiled program
    use_flash = flash_available(C, cache_len, D)

    def prefill_step(carry, c0):
        caches, next_hidden = carry
        chunk = jax.lax.dynamic_slice(prompt, (0, c0), (B, C))
        q_pos = c0 + jnp.arange(C)
        # attend to cache positions <= own position, and only to real
        # (non-pad) prompt positions. On the flash path this bool
        # [B, C, cache_len] is never consumed (the kernel derives the
        # identical mask in-kernel from (c0, prompt_len) iotas) and XLA
        # dead-code-eliminates its construction — nothing [T, S]-sized
        # exists at runtime there.
        mask = (
            (pos[None, None, :] <= q_pos[None, :, None])
            & (pos[None, None, :] < prompt_len[:, None, None])
        )
        mask = jnp.broadcast_to(mask, (B, C, cache_len))
        if use_flash:
            def attn_fn(q, k, v, _mask):
                return flash_attention_ragged(q, k, v, c0, prompt_len)
        else:
            # dense jnp path. Numerically equivalent to flash within
            # dtype tolerance, NOT bit-identical (online-softmax
            # reorders the summation), so near-tied greedy decodes may
            # differ across backends.
            attn_fn = attention_auto
        # hidden states, not logits: only ONE position per row feeds the
        # first sampled token, so the full-vocab head runs once on the
        # selected rows after the scan instead of per chunk token (~20%
        # of prefill FLOPs at 32k vocab, and no [C, V] f32 per chunk)
        hidden, caches = forward(
            params, chunk, cfg, attn_mask=mask, kv_caches=caches,
            cache_offset=c0, attn_fn=attn_fn, return_hidden=True,
        )
        # the row's next-token state lives in whichever chunk holds its
        # LAST REAL prompt position
        in_chunk = (last >= c0) & (last < c0 + C)
        idx = jnp.clip(last - c0, 0, C - 1)
        chunk_last = jnp.take_along_axis(
            hidden, idx[:, None, None], axis=1
        )[:, 0]
        next_hidden = jnp.where(in_chunk[:, None], chunk_last, next_hidden)
        return (caches, next_hidden), ()

    from kubeinfer_tpu.inference.model import lm_head_matrix

    (caches, next_hidden), _ = jax.lax.scan(
        prefill_step,
        (
            caches,
            jnp.zeros((B, cfg.hidden_size), params["norm"].dtype),
        ),
        jnp.arange(0, T, C),
    )
    next_logits = (next_hidden @ lm_head_matrix(params, cfg)).astype(
        jnp.float32
    )
    return caches, next_logits


def make_caches(cfg: ModelConfig, B: int, cache_len: int, dtype):
    return [
        (
            jnp.zeros((B, cache_len, cfg.num_key_value_heads, cfg.head_dim), dtype),
            jnp.zeros((B, cache_len, cfg.num_key_value_heads, cfg.head_dim), dtype),
        )
        for _ in range(cfg.num_hidden_layers)
    ]


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "max_new", "cache_len", "prefill_chunk"),
)
def _generate_jit(
    params: Params,
    prompt: jax.Array,  # i32[B, T_bucket] left-aligned, 0-padded
    prompt_len: jax.Array,  # i32[B]
    cfg: ModelConfig,
    max_new: int,
    cache_len: int,
    prefill_chunk: int,
    eos_id: jax.Array,  # i32 (negative = never stop)
    temperature: jax.Array,  # f32; <=0 = greedy
    top_k: jax.Array,  # i32; <1 = disabled
    top_p: jax.Array,  # f32; >=1 = disabled
    rep_penalty: jax.Array,  # f32; 1.0 = disabled
    rng_key: jax.Array,
):
    # stepper imports this module's sampling helpers at module level, so
    # the decode loop comes back lazily (trace time only — inside the
    # jit, like batching's kernel imports)
    from kubeinfer_tpu.inference.stepper import decode_scan

    B, T = prompt.shape
    caches = make_caches(cfg, B, cache_len, params["norm"].dtype)

    # --- prefill: chunked so long prompts never materialize [T, T] ------
    # Each chunk of C tokens attends causally against the cache (a
    # [C, cache_len] mask), so peak attention memory is O(C * S) instead
    # of O(T^2) — the difference between a 128k-token prompt fitting in
    # HBM or not. The chunk loop is a scan (one trace regardless of
    # chunk count; 131072/512 unrolled copies would blow up compile).
    caches, next_logits = chunked_prefill(
        params, prompt, prompt_len, cfg, caches, prefill_chunk
    )
    return decode_scan(
        params, cfg, caches, next_logits, prompt, prompt_len, max_new,
        cache_len, eos_id, temperature, top_k, top_p, rep_penalty, rng_key,
    )


def prepare_prompts(
    prompts: list[list[int]],
    max_new_tokens: int,
    max_cache_len: int,
):
    """Host-side prompt prep shared by the per-request and the
    sequence-parallel engine: validate, bucket, pad, and size the KV
    cache. Returns (padded i32[B, T_bucket], lens i32[B], cache_len)."""
    lens = np.asarray([len(p) for p in prompts], np.int32)
    if lens.min() < 1:
        raise ValueError("empty prompt")
    T = _bucket(int(lens.max()))
    need = int(lens.max()) + max_new_tokens
    if need > max_cache_len:
        raise ValueError(
            f"prompt+new tokens ({need}) exceed the model's context "
            f"capacity ({max_cache_len})"
        )
    # cache width: bucketed for jit-cache reuse, but never below the
    # prefill bucket T (a cache narrower than the prefill width would
    # write out of bounds). Bucket rounding may exceed max_cache_len;
    # positions stay < max_cache_len, extra columns are masked.
    cache_len = max(T, _bucket(need))
    padded = np.zeros((len(prompts), T), np.int32)
    for i, p in enumerate(prompts):
        padded[i, : len(p)] = p
    return padded, lens, cache_len


class Engine:
    """Generation front-end over a loaded model."""

    def __init__(self, params: Params, cfg: ModelConfig,
                 max_cache_len: int = 0) -> None:
        self.params = params
        self.cfg = cfg
        self.max_cache_len = max_cache_len or cfg.max_position_embeddings

    def generate(
        self,
        prompts: list[list[int]],
        max_new_tokens: int = 32,
        eos_id: int = -1,
        temperature: float = 0.0,
        seed: int = 0,
        top_k: int = 0,
        top_p: float = 1.0,
        repetition_penalty: float = 1.0,
    ) -> GenerationResult:
        """Batch generation, exact for ragged prompts.

        Prompts pad to a shared bucket for the prefill (pad columns
        masked via prompt_len) and the whole batch — mixed lengths
        included — decodes in ONE jit invocation: decode_scan carries a
        per-row cache offset, so no per-length grouping (the pre-ragged
        engine fragmented mixed traffic into per-length micro-batches,
        forfeiting what batching buys).
        """
        if not prompts:
            return GenerationResult(
                np.zeros((0, 0), np.int32), np.zeros((0,), np.int32)
            )
        B = len(prompts)
        with _TRACER.span("engine.generate", batch=B,
                          max_new=max_new_tokens):
            padded, lens, cache_len = prepare_prompts(
                prompts, max_new_tokens, self.max_cache_len
            )
            toks, glens = _generate_jit(
                self.params,
                jnp.asarray(padded),
                jnp.asarray(lens),
                self.cfg,
                max_new_tokens,
                cache_len,
                prefill_chunk_for(B, int(padded.shape[1])),
                jnp.int32(eos_id),
                jnp.float32(temperature),
                jnp.int32(top_k),
                jnp.float32(top_p),
                jnp.float32(repetition_penalty),
                jax.random.PRNGKey(seed),
            )
            # lint: allow[host-sync] serving boundary: one readback per batch
            toks_out = np.asarray(toks)
            lens_out = np.asarray(glens)  # lint: allow[host-sync] same readback as the line above
        return GenerationResult(toks_out, lens_out)
