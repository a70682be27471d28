"""Qwen3-Next through the normal serving path, at a small size on the
CPU: the program against the plain reference
(benchmarks/reference/qwen3_next.py) on logits, the Gated DeltaNet
forms against each other, the sparse expert block against the dense
oracle, and what such a model refuses.

Tolerances: everything here runs in float32 on the CPU, where the
program and the reference differ only in the order of their sums
(chunked against token-by-token recurrence, sorted pairs against a
dense product): logits of spread ~0.16 agree to a few 1e-6, and the
limits leave a decade of room. Two runs of the SAME program on inputs
that should not matter (padding, chunking) are held tighter still.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeinfer_tpu.inference import gdn, moe
from kubeinfer_tpu.inference.batching import (
    ContinuousEngine,
    _admit_slot,
    _prefill_chunk,
)
from kubeinfer_tpu.inference.config import PRESETS, ModelConfig
from kubeinfer_tpu.inference.model import forward, init_params
from kubeinfer_tpu.inference.stepper import (
    decode_window,
    init_slot_state,
    layer_caches,
    step_forward,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "benchmarks"))
from reference import qwen3_next as ref  # noqa: E402

VS_REFERENCE = 2e-5  # other order of sums, float32
SAME_PROGRAM = 2e-6  # the same program, inputs that must not matter

CONF = dict(
    model_type="qwen3_next", vocab_size=256, hidden_size=64,
    intermediate_size=128, num_hidden_layers=8, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, full_attention_interval=4,
    linear_conv_kernel_dim=4, linear_key_head_dim=16,
    linear_num_key_heads=2, linear_num_value_heads=4,
    linear_value_head_dim=16, moe_intermediate_size=32, num_experts=4,
    num_experts_per_tok=4, shared_expert_intermediate_size=32,
    norm_topk_prob=True, partial_rotary_factor=0.25, rms_norm_eps=1e-6,
    rope_theta=1e7, tie_word_embeddings=False,
    expert_parallel={"size": 4, "rank": 1}, max_position_embeddings=512,
)
CACHE, BS, SLOTS = 128, 16, 2


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig.from_hf_dict(CONF)
    served = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    # the values the server would hold, computed with in float32
    params = jax.tree.map(lambda x: x.astype(jnp.float32), served)
    ke, kl, kh = ref.weight_keys(0)
    plain = ref.make_ends(ke, kh, CONF)
    plain["layers"] = [ref.make_layer(kl, i, CONF, "bf16")
                       for i in range(CONF["num_hidden_layers"])]
    return cfg, params, plain, served


def _tokens(seed: int, n: int) -> list[int]:
    return np.random.default_rng(seed).integers(0, 256, n).tolist()


def _fresh(cfg):
    M = CACHE // BS
    return init_slot_state(cfg, SLOTS, CACHE, jnp.float32,
                           1 + SLOTS * M, BS)


def _admit(cfg, params, state, slot, prompt, start=0, pad_to=None):
    """_admit_slot on ``prompt[start:]`` padded to ``pad_to``, greedy."""
    M = CACHE // BS
    n = len(prompt) - start
    T = pad_to or n
    suffix = np.zeros((1, T), np.int32)
    suffix[0, :n] = prompt[start:]
    table = np.arange(1 + slot * M, 1 + (slot + 1) * M, dtype=np.int32)
    seen = np.zeros((1, cfg.vocab_size), bool)
    return _admit_slot(
        params, state, jnp.asarray(suffix), jnp.int32(n), jnp.int32(start),
        jnp.int32(len(prompt)), cfg, jnp.int32(slot), jnp.asarray(table),
        jnp.ones((M,), bool), jnp.float32(0), jnp.int32(0), jnp.float32(1),
        jnp.float32(1), jnp.zeros((2,), jnp.uint32), jnp.asarray(seen),
    )


def _chunk(cfg, params, state, slot, prompt, pos, C):
    M = CACHE // BS
    table = np.arange(1 + slot * M, 1 + (slot + 1) * M, dtype=np.int32)
    return _prefill_chunk(
        params, state, jnp.asarray([prompt[pos:pos + C]], jnp.int32),
        jnp.int32(pos), cfg, jnp.asarray(table), jnp.ones((M,), bool),
        slot=jnp.int32(slot),
    )


def _decode(cfg, params, state, steps):
    """``steps`` greedy steps: (state, logits f32[steps, SLOTS, V] before
    each step, tokens i32[steps, SLOTS])."""
    logits, toks = [], []
    for _ in range(steps):
        lg, _ = step_forward(
            params, cfg, state.last_token, state.offset,
            layer_caches(state, cfg), CACHE, block_tables=state.tables,
            active=state.active,
        )
        logits.append(lg)
        state, t = decode_window(params, state, cfg, 1)
        toks.append(t[:, 0])
    return state, jnp.stack(logits), jnp.stack(toks)


def _reference_rows(plain, prompt, generated):
    """The reference's logits at the positions that predict each of
    ``generated`` (the first from the prompt's last position)."""
    seq = jnp.asarray(prompt + generated[:-1], jnp.int32)
    return ref.forward(plain, seq, CONF)[len(prompt) - 1:]


def _against_reference(plain, prompt, first, logits, toks):
    generated = [int(first)] + [int(t) for t in toks]
    want = _reference_rows(plain, prompt, generated)
    # the admit's token is the reference's choice, and each decode
    # step's logits are the reference's at that position
    assert int(jnp.argmax(want[0])) == generated[0]
    np.testing.assert_allclose(logits, want[1:], atol=VS_REFERENCE, rtol=0)


# --- (a)-(e): the program against the reference, on logits ------------------


def test_one_forward_matches_the_reference(model):
    cfg, params, plain, _ = model
    toks = _tokens(1, 40)
    got, _ = forward(params, jnp.asarray([toks], jnp.int32), cfg)
    want = ref.forward(plain, jnp.asarray(toks, jnp.int32), CONF)
    assert float(jnp.std(want)) > 0.05  # a comparison of live logits
    np.testing.assert_allclose(got[0], want, atol=VS_REFERENCE, rtol=0)


def test_the_served_weights_are_the_references_bit_for_bit(model):
    _, _, plain, served = model
    ours = {"q_proj": "q_proj", "in_proj_qkvz": "in_proj_qkvz",
            "conv1d": "conv1d", "A_log": "A_log", "dt_bias": "dt_bias",
            "norm": "gdn_norm", "out_proj": "out_proj", "router": "router",
            "gate_proj": "experts_gate", "up_proj": "experts_up",
            "down_proj": "experts_down", "shared_gate_proj": "shared_gate",
            "shared_up_proj": "shared_up", "shared_down_proj": "shared_down",
            "shared_expert_gate": "shared_expert_gate", "k_proj": "k_proj",
            "v_proj": "v_proj", "o_proj": "o_proj", "q_norm": "q_norm",
            "k_norm": "k_norm", "in_proj_ba": "in_proj_ba",
            "input_layernorm": "input_layernorm",
            "post_attention_layernorm": "post_attention_layernorm"}
    n = 0
    for lp, rp in zip(served["layers"], plain["layers"]):
        flat = {**lp, **lp["moe"], **lp.get("linear_attn", {})}
        for name, leaf in flat.items():
            if isinstance(leaf, dict):
                continue
            assert leaf.dtype == rp[ours[name]].dtype, name
            assert np.array_equal(np.asarray(leaf, np.float32),
                                  np.asarray(rp[ours[name]], np.float32))
            n += 1
    for name in ("embed_tokens", "norm", "lm_head"):
        assert np.array_equal(np.asarray(served[name], np.float32),
                              np.asarray(plain[name], np.float32))
    assert n == 6 * 17 + 2 * 16


def test_prefill_then_decode_through_pages_and_state(model):
    cfg, params, plain, _ = model
    prompt = _tokens(2, 40)
    state = _admit(cfg, params, _fresh(cfg), 0, prompt, pad_to=64)
    first = state.last_token[0]
    _, logits, toks = _decode(cfg, params, state, 16)
    _against_reference(plain, prompt, first, logits[:, 0], toks[:, 0])


@pytest.mark.parametrize("how", ["padded", "chunked"])
def test_prefill_padding_and_chunking_change_nothing(model, how):
    """A prompt padded to its bucket against the same prompt unpadded,
    and a prompt prefilled in chunks (state and tail carried from chunk
    to chunk) against one piece."""
    cfg, params, _, _ = model
    prompt = _tokens(3, 40)
    whole = _admit(cfg, params, _fresh(cfg), 0, prompt)
    if how == "padded":
        other = _admit(cfg, params, _fresh(cfg), 0, prompt, pad_to=64)
    else:
        other = _fresh(cfg)
        for pos in (0, 16):
            other = _chunk(cfg, params, other, 0, prompt, pos, 16)
        other = _admit(cfg, params, other, 0, prompt, start=32, pad_to=16)
    assert int(whole.last_token[0]) == int(other.last_token[0])
    for a, b in zip(whole.gdn_state + whole.gdn_conv,
                    other.gdn_state + other.gdn_conv):
        np.testing.assert_allclose(a[0], b[0], atol=SAME_PROGRAM, rtol=0)
    _, want, _ = _decode(cfg, params, whole, 4)
    _, got, _ = _decode(cfg, params, other, 4)
    np.testing.assert_allclose(got[:, 0], want[:, 0], atol=SAME_PROGRAM,
                               rtol=0)


def test_two_slots_admitted_at_different_times(model):
    """Slot 1 is chunk-prefilled and admitted while slot 0 decodes: a
    decode step must not touch the state a slot holds mid-prefill, and
    each slot's logits are the reference's for its own sequence."""
    cfg, params, plain, _ = model
    p0, p1 = _tokens(4, 24), _tokens(5, 40)
    state = _admit(cfg, params, _fresh(cfg), 0, p0, pad_to=32)
    first0 = state.last_token[0]
    state = _chunk(cfg, params, state, 1, p1, 0, 16)
    state, lg_a, tk_a = _decode(cfg, params, state, 3)
    state = _chunk(cfg, params, state, 1, p1, 16, 16)
    state = _admit(cfg, params, state, 1, p1, start=32, pad_to=16)
    first1 = state.last_token[1]
    state, lg_b, tk_b = _decode(cfg, params, state, 5)
    _against_reference(plain, p0, first0,
                       jnp.concatenate([lg_a[:, 0], lg_b[:, 0]]),
                       jnp.concatenate([tk_a[:, 0], tk_b[:, 0]]))
    _against_reference(plain, p1, first1, lg_b[:, 1], tk_b[:, 1])


def test_other_models_hold_no_new_state(model):
    """What a layer caches follows its kind: pages for the two full
    layers of eight here and state for the six others; a model without
    such layers or routed experts has none of the new leaves, so its
    step programs take the operands they took."""
    cfg = model[0]
    mine = _fresh(cfg)
    assert len(mine.caches_k) == len(mine.caches_v) == 2
    assert len(mine.gdn_state) == len(mine.gdn_conv) == 6
    assert mine.gdn_state[0].shape == (SLOTS, 4, 16, 16)
    assert mine.gdn_state[0].dtype == jnp.float32
    assert mine.gdn_conv[0].shape == (SLOTS, 3, 2 * 2 * 16 + 4 * 16)
    dense = PRESETS["tiny"]
    theirs = init_slot_state(dense, SLOTS, CACHE, jnp.float32, 17, BS)
    assert theirs.gdn_state == theirs.gdn_conv == theirs.moe_stats == []
    assert len(jax.tree.leaves(theirs)) == 2 * dense.num_hidden_layers + 10


# --- the Gated DeltaNet forms ---------------------------------------------------


def _gdn_inputs(seed, B=2, T=150, H=4, D=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q, k = (jax.random.normal(ks[i], (B, T, H, D)) for i in (0, 1))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, D))
    g = -jax.random.uniform(ks[3], (B, T, H), minval=1e-3, maxval=0.7)
    beta = jax.random.uniform(ks[4], (B, T, H))
    state = jax.random.normal(ks[5], (B, H, D, D))
    return q * D ** -0.5, k, v, g, beta, state


@pytest.mark.parametrize("T", [1, 64, 150])
def test_chunked_scan_is_the_token_by_token_recurrence(T):
    """150 tokens are two whole chunks and a padded third; the state
    they start from is not zero. The chunk's triangular solve sums in
    another order: a few float32 ulps of values of size ~1."""
    q, k, v, g, beta, state = _gdn_inputs(0, T=T)
    want_o, want_s = gdn.gdn_recurrence(q, k, v, g, beta, state)
    got_o, got_s = gdn.gdn_chunk_scan(q, k, v, g, beta, state)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5, rtol=0)


def test_decode_kernel_is_the_recurrences_one_step():
    """The Pallas step in interpret mode (the chip's compile of it is
    tests/test_chip_compile.py's): in place, heads in blocks, an idle
    row (g = 0, beta = 0) left exactly as it was."""
    q, k, v, g, beta, state = _gdn_inputs(1, B=3, T=1, H=8, D=128)
    g = g.at[1].set(0.0)
    beta = beta.at[1].set(0.0)
    want_o, want_s = gdn.gdn_recurrence(q, k, v, g, beta, state)
    keep = np.asarray(state[1])
    got_o, got_s = gdn.gdn_decode_step(
        q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
        interpret=True)
    np.testing.assert_allclose(got_o, want_o[:, 0], atol=1e-5, rtol=0)
    np.testing.assert_allclose(got_s, want_s, atol=1e-5, rtol=0)
    assert np.array_equal(np.asarray(got_s[1]), keep)


# --- the experts ----------------------------------------------------------------


def _moe_params(seed, H=32, F=16, E=16, shared=True):
    p = moe.init_moe_params(jax.random.PRNGKey(seed), H, F, E)
    if shared:
        ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
        for name, k, shape in (("shared_gate_proj", ks[0], (H, F)),
                               ("shared_up_proj", ks[1], (H, F)),
                               ("shared_down_proj", ks[2], (F, H)),
                               ("shared_expert_gate", ks[3], (H, 1))):
            p[name] = 0.5 * jax.random.normal(k, shape)
    # spread the router so that the top k differ from row to row
    p["router"] = 50.0 * p["router"]
    return p


def _held(p, rank, size=4):
    """Rank ``rank``'s share: its experts, the whole router."""
    n = p["gate_proj"].shape[0] // size
    cut = {k: v[rank * n:(rank + 1) * n] if k in (
        "gate_proj", "up_proj", "down_proj") else v for k, v in p.items()}
    return cut, rank * n


def test_sparse_block_is_the_dense_oracle():
    p = _moe_params(0, shared=False)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 32))
    got, stats = moe.moe_forward(p, x, top_k=4)
    np.testing.assert_allclose(got, moe.moe_block(p, x, top_k=4),
                               atol=1e-6, rtol=0)
    routed, held, reached, busiest, calls = (int(s) for s in stats)
    assert (routed, held, calls) == (2 * 24 * 4, 2 * 24 * 4, 1)
    assert 1 <= reached <= 16 and held / 16 <= busiest <= 48


def test_the_shares_add_up_to_the_uncut_layer():
    """The four ranks' routed parts plus the shared expert counted once
    equal the layer with every expert held, and the ranks' held pairs
    are all the pairs."""
    p = _moe_params(2)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, 32))
    uncut, all_stats = moe.moe_forward(p, x, top_k=4)
    routed_only = {k: v for k, v in p.items() if "shared" not in k}
    parts, held = [], 0
    for rank in range(4):
        cut, first = _held(routed_only, rank)
        y, stats = moe.moe_forward(cut, x, top_k=4, expert_offset=first)
        parts.append(y)
        held += int(stats[1])
    shared_only, _ = moe.moe_forward(
        {**_held(p, 0)[0]}, x, top_k=4, expert_offset=16 * 100)
    np.testing.assert_allclose(sum(parts) + shared_only, uncut,
                               atol=1e-5, rtol=0)
    assert held == int(all_stats[0]) == 40 * 4


def test_rows_that_are_not_real_reach_no_expert():
    p = _moe_params(4)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 32))
    valid = jnp.arange(8)[None, :] < jnp.asarray([5, 0])[:, None]
    got, stats = moe.moe_forward(p, x, top_k=4, valid=valid)
    want, _ = moe.moe_forward(p, x[:1, :5], top_k=4)
    np.testing.assert_allclose(got[0, :5], want[0], atol=1e-6, rtol=0)
    assert int(stats[0]) == int(stats[1]) == 5 * 4


def test_grouped_matmul_kernel_is_a_matmul_per_group():
    """The Pallas kernel in interpret mode against ragged_dot: groups
    that straddle row tiles, empty groups, rows past the last group."""
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(ks[0], (384, 128)).astype(jnp.bfloat16)
    w = jax.random.normal(ks[1], (6, 128, 128)).astype(jnp.bfloat16)
    sizes = jnp.asarray([0, 130, 3, 0, 127, 1], jnp.int32)
    got = moe.grouped_matmul(x, w, sizes, interpret=True)
    want = jax.lax.ragged_dot(x, w, sizes)
    n = int(sizes.sum())
    np.testing.assert_allclose(np.asarray(got[:n], np.float32),
                               np.asarray(want[:n], np.float32),
                               atol=0.1, rtol=0.02)
    none = moe.grouped_matmul(x, w, jnp.zeros((6,), jnp.int32),
                              interpret=True)
    assert none.shape == (384, 128)


def test_mixtral_routes_as_before():
    """Softmax over all experts, top k, renormalised (the one routing
    function) is Mixtral's softmax over the top-k logits (the old one),
    and the preset's layer output is the same under both."""
    cfg = dataclasses.replace(
        PRESETS["tiny"], num_local_experts=4, num_experts_per_tok=2)
    params = init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    p = params["layers"][0]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 12, cfg.hidden_size))

    logits = x @ p["router"]
    top, _ = jax.lax.top_k(logits, 2)
    old = jax.nn.softmax(
        jnp.where(logits >= top[..., -1:], logits, -jnp.inf), axis=-1)
    np.testing.assert_allclose(moe._router_weights(p, x, 2), old,
                               atol=1e-6, rtol=0)
    gate = jax.nn.silu(jnp.einsum("bth,ehf->betf", x, p["gate_proj"]))
    up = jnp.einsum("bth,ehf->betf", x, p["up_proj"])
    y = jnp.einsum("betf,efh->beth", gate * up, p["down_proj"])
    want = jnp.einsum("beth,bte->bth", y, old)
    got, _ = moe.moe_forward(p, x, top_k=2)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=0)


# --- what such a model refuses ----------------------------------------------------


@pytest.mark.parametrize("kwargs, names", [
    ({"weight_dtype": "int8"}, "--weight-dtype int8"),
    ({"kv_dtype": "int8"}, "--kv-dtype int8"),
    ({"tp": 2}, "--tensor-parallel-size > 1"),
    # server.main builds an SPEngine beside the batcher when asked:
    # its ring-attention prefill knows no recurrent layer
    ({"sp": 2}, "--sequence-parallel-size > 1"),
    ({"speculation": True}, "a draft model or speculation"),
])
def test_configuration_refuses_by_name(model, kwargs, names):
    cfg = model[0]
    with pytest.raises(ValueError, match=names):
        cfg.check_serving(**kwargs)
    PRESETS["tiny"].check_serving(**kwargs)  # other models are not asked


@pytest.fixture(scope="module")
def engine(model):
    cfg, params, _, _ = model
    eng = ContinuousEngine(params, cfg, n_slots=SLOTS, cache_len=CACHE,
                           block_size=BS, prefill_chunk_blocks=1).start()
    yield eng
    eng.stop()


@pytest.mark.parametrize("what", [
    "int8 weights", "int8 pool", "draft", "migration", "import", "export"])
def test_engine_refuses_by_name(model, engine, what):
    cfg, params, _, _ = model
    build = {"int8 weights": {"weight_dtype": "int8"},
             "int8 pool": {"kv_dtype": "int8"},
             "draft": {"spec_draft": (params, cfg)}}
    if what in build:
        with pytest.raises(ValueError, match="linear-attention"):
            ContinuousEngine(params, cfg, n_slots=1, cache_len=CACHE,
                             block_size=BS, **build[what])
        return
    with pytest.raises(ValueError, match="recurrent state"):
        if what == "migration":
            engine.drain()
        elif what == "import":
            z = np.zeros((2, 1, BS, 2, 32), np.float32)
            engine.import_prefix(list(range(BS)), z, z)
        else:
            engine.submit(_tokens(0, 20), 2, export_kv=True)


def test_a_repeated_prompt_reuses_no_block_and_decodes_the_same(
        model, engine):
    """Through the scheduler loop: chunked admission (one block a
    chunk), K-step windows, retirement. The second time nothing is
    reused, the refusal is counted, the tokens are the same, and they
    are the reference's greedy choices."""
    _, _, plain, _ = model
    prompt = _tokens(6, 40)
    before = dict(engine.prefix_refused)
    first = engine.generate(prompt, max_new_tokens=12)
    again = engine.generate(prompt, max_new_tokens=12)
    assert first == again
    assert engine.prefill_tokens["cached"] == 0
    assert engine.kv_cache_stats()["hits"] == 0
    assert engine.prefix_refused["recurrent_state"] \
        == before["recurrent_state"] + 2
    want = _reference_rows(plain, prompt, first)
    assert [int(t) for t in jnp.argmax(want, -1)] == first
    counts = engine.scheduler_stats()["moe"]
    assert counts["calls"] > 0 and counts["routed_pairs"] > 0
    # rank 1 of 4 holds a quarter of the experts: about a quarter of
    # the pairs a uniform router would send, and never more than all
    assert 0 < counts["held_pairs"] < counts["routed_pairs"]
    assert engine.recurrent_state_bytes == 6 * SLOTS * (
        4 * 16 * 16 * 4 + 3 * (2 * 2 * 16 + 4 * 16) * 4)
