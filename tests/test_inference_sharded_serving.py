"""Tensor-parallel sharded serving: the EngineLayout contracts that
let the paged continuous batch run across the 8-device mesh without
anyone being able to tell from the token streams.

- **tp=1 is byte-for-byte degenerate.** The default layout carries no
  mesh and every shard_* hook is the identity — the engine's arrays,
  traces, and compile cache are exactly the pre-sharding engine's.

- **Token parity across layouts.** tp > 1 only PLACES arrays (params
  per the Megatron specs, the KV pool along n_kv, everything else
  replicated); GSPMD partitions the same programs. Streams must match
  tp=1 exactly — greedy and sampled, cold and warm admits, across
  preemption cycles — because sampling keys are position-folded and
  picks ride logit gaps (see EngineLayout's docstring on dominance).

- **Divisibility is a hard door.** Every device owns whole q and KV
  heads (heads % tp == 0 and n_kv % tp == 0); GQA ratios down to
  n_kv == tp (one KV head per device) are in-contract.

- **ICI ordering.** order_devices_ici snakes the chip grid so
  consecutive mesh ranks are one hop apart, and mesh_device_array puts
  tp (the per-step psum axis) on those adjacent positions; coordless
  devices (this suite's virtual CPU mesh) keep enumeration order.

- **Compile discipline.** One compiled shape per (window bucket,
  layout): repeating a seen workload under sharding registers zero
  fresh first-seens, and the pool placement visibly survives donation.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from kubeinfer_tpu.inference import PRESETS, init_params
from kubeinfer_tpu.inference.batching import (
    ContinuousEngine,
    PreemptionPolicy,
)
from kubeinfer_tpu.inference.config import ModelConfig
from kubeinfer_tpu.inference.sharding import (
    EngineLayout,
    mesh_device_array,
    order_devices_ici,
)

TINY = PRESETS["tiny"]  # heads=4, n_kv=2: supports tp in {1, 2}

# GQA shape where tp divides n_kv strictly (tp=2) and exactly
# (tp=4 -> one KV head per device, the contract's floor)
GQA = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=8,
    num_key_value_heads=4, max_position_embeddings=512,
)
# MHA shape that stretches to the full 8-device mesh
MHA8 = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128,
    num_hidden_layers=2, num_attention_heads=8,
    num_key_value_heads=8, max_position_embeddings=512,
)

AGGRESSIVE = PreemptionPolicy(
    threshold_s=0.0005, objective=0.5, burn_limit=0.5,
    cooldown_steps=1, min_progress=1,
)


@pytest.fixture(scope="module")
def params():
    return init_params(TINY, jax.random.PRNGKey(6))


def _engine(params, cfg=TINY, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("block_size", 8)
    return ContinuousEngine(params, cfg, **kw).start()


def _streams(eng, prompt, n=9):
    """Cold greedy + sampled, then a warm (radix-hit) readmit — the
    three admit paths parity must cover."""
    g = eng.generate(prompt, max_new_tokens=n)
    s = eng.generate(prompt, max_new_tokens=n,
                     temperature=0.8, seed=5, top_k=13)
    w = eng.generate(prompt, max_new_tokens=n)
    return g, s, w


class TestEngineLayout:
    def test_default_is_degenerate(self, params):
        lay = EngineLayout()
        assert lay.tp == 1 and lay.mesh is None
        assert not lay.sharded
        assert lay.mesh_devices == 1
        # identity, not a copy: tp=1 must not even touch the arrays
        assert lay.shard_params(params, TINY) is params
        sentinel = object()
        assert lay.shard_state(sentinel) is sentinel
        lay.check_model(TINY)  # no mesh -> nothing to check

    def test_build_tp1_stays_meshless(self):
        assert EngineLayout.build(1).mesh is None
        assert EngineLayout.build(0).mesh is None

    def test_build_makes_tp_mesh(self):
        lay = EngineLayout.build(2)
        assert lay.sharded and lay.mesh_devices == 2
        assert "tp" in lay.mesh.axis_names
        assert lay.pool_sharding().spec == P(None, "tp", None, None)

    def test_mesh_iff_sharded(self):
        with pytest.raises(ValueError, match="mesh"):
            EngineLayout(tp=2, mesh=None)
        with pytest.raises(ValueError, match="mesh"):
            EngineLayout(tp=1, mesh=EngineLayout.build(2).mesh)
        with pytest.raises(ValueError, match=">= 1"):
            EngineLayout(tp=0)

    def test_divisibility_is_a_hard_door(self):
        lay = EngineLayout.build(4)
        # tiny: n_kv=2 < tp=4 — a device would own zero KV heads
        with pytest.raises(ValueError, match="num_key_value_heads"):
            lay.check_model(TINY)
        with pytest.raises(ValueError, match="num_key_value_heads"):
            EngineLayout.build(8).check_model(GQA)  # n_kv=4 < tp=8
        with pytest.raises(ValueError, match="num_attention_heads"):
            EngineLayout.build(3).check_model(MHA8)  # 8 % 3 != 0
        lay.check_model(GQA)  # n_kv == tp is the in-contract floor

    def test_engine_constructor_enforces_the_door(self, params):
        with pytest.raises(ValueError, match="num_key_value_heads"):
            ContinuousEngine(params, TINY, n_slots=2, cache_len=64,
                            block_size=8, layout=EngineLayout.build(4))


class _FakeDev:
    """Stand-in with the three attrs the ordering reads; repr'd by id
    so mismatched walks show as readable sequences."""

    def __init__(self, i, coords, core=0):
        self.id = i
        self.coords = coords
        self.core_on_chip = core

    def __repr__(self):
        return f"d{self.id}"


class TestIciOrdering:
    def test_coordless_devices_keep_enumeration_order(self):
        devs = jax.devices()
        assert order_devices_ici(devs) == list(devs)

    def test_snake_walk_on_2d_grid(self):
        # 4x2 grid in row-major enumeration; the walk must flip
        # direction on odd rows so each step is one ICI hop
        grid = {(x, y): _FakeDev(4 * y + x, (x, y, 0))
                for y in range(2) for x in range(4)}
        walk = order_devices_ici(list(grid.values()))
        coords = [d.coords[:2] for d in walk]
        assert coords == [(0, 0), (1, 0), (2, 0), (3, 0),
                          (3, 1), (2, 1), (1, 1), (0, 1)]
        # every consecutive pair is manhattan-adjacent — the property
        # the walk exists for
        for a, b in zip(coords, coords[1:]):
            assert abs(a[0] - b[0]) + abs(a[1] - b[1]) == 1

    def test_megacore_sorts_chip_adjacent(self):
        devs = [
            _FakeDev(0, (0, 0, 0), core=1), _FakeDev(1, (1, 0, 0), core=0),
            _FakeDev(2, (0, 0, 0), core=0), _FakeDev(3, (1, 0, 0), core=1),
        ]
        assert [d.id for d in order_devices_ici(devs)] == [2, 0, 1, 3]

    def test_tp_ranks_are_chain_adjacent(self):
        grid = [_FakeDev(4 * y + x, (x, y, 0))
                for y in range(2) for x in range(4)]
        arr = mesh_device_array(grid, dp=1, tp=4, sp=2)
        assert arr.shape == (1, 4, 2)
        # fixed sp rank: the 4 tp ranks occupy 4 consecutive chain
        # positions (the snake walk), each one hop from the next
        for s in range(2):
            cs = [d.coords[:2] for d in arr[0, :, s]]
            for a, b in zip(cs, cs[1:]):
                assert abs(a[0] - b[0]) + abs(a[1] - b[1]) == 1

    def test_sp1_matches_historical_layout(self):
        devs = jax.devices()
        arr = mesh_device_array(devs, dp=2, tp=4, sp=1)
        assert arr.shape == (2, 4, 1)
        # sp==1 transpose is the identity: plain row-major fill
        assert list(arr.reshape(-1)) == list(devs)


class TestShardedParity:
    def test_tp2_matches_tp1_cold_and_warm(self, params):
        rng = np.random.default_rng(21)
        prompt = rng.integers(0, TINY.vocab_size, 7).tolist()
        ref = _engine(params, max_window=8)
        try:
            want = _streams(ref, prompt)
        finally:
            ref.stop()
        eng = _engine(params, max_window=8, layout=EngineLayout.build(2))
        try:
            got = _streams(eng, prompt)
            # the pool placement survived admits + donated windows
            # (semantic compare: GSPMD trims trailing None dims)
            pool_ok = eng._state.caches_k[0].sharding.is_equivalent_to(
                eng.layout.pool_sharding(), 4
            )
            stats = eng.stats_summary()
        finally:
            eng.stop()
        assert got == want
        assert pool_ok
        assert stats["tp_degree"] == 2 and stats["mesh_devices"] == 2

    def test_gqa_ratios_divide_and_equal(self):
        gparams = init_params(GQA, jax.random.PRNGKey(7))
        rng = np.random.default_rng(22)
        prompt = rng.integers(0, GQA.vocab_size, 6).tolist()
        want = None
        for tp in (1, 2, 4):  # tp=4: n_kv == tp, one KV head/device
            eng = _engine(gparams, cfg=GQA, max_window=4,
                          layout=EngineLayout.build(tp))
            try:
                got = _streams(eng, prompt, n=7)
            finally:
                eng.stop()
            if want is None:
                want = got
            else:
                assert got == want, f"tp={tp} diverged"

    def test_preemption_parity_under_sharding(self, params):
        """Park/resume cycles with the pool sharded: parks scatter KV
        out of a sharded pool and resumes gather back in — streams must
        still match the uncontended sharded engine."""
        rng = np.random.default_rng(23)
        prompts = [rng.integers(0, TINY.vocab_size, 5).tolist()
                   for _ in range(8)]
        kw = lambda i: dict(  # noqa: E731 - tiny per-index sampler knobs
            temperature=0.8 if i % 2 else 0.0,
            seed=50 + i, top_k=9 if i % 2 else 0,
        )
        solo = _engine(params, max_window=8, layout=EngineLayout.build(2))
        try:
            want = [solo.generate(p, max_new_tokens=8, **kw(i))
                    for i, p in enumerate(prompts)]
        finally:
            solo.stop()
        eng = _engine(params, max_window=8, preemption=AGGRESSIVE,
                      layout=EngineLayout.build(2))
        try:
            reqs = [eng.submit(p, max_new_tokens=8, **kw(i))
                    for i, p in enumerate(prompts)]
            for i, r in enumerate(reqs):
                assert r.done.wait(300), f"request {i} starved"
                assert not r.failed
            preempted = eng.preempted_total
        finally:
            eng.stop()
        assert preempted >= 1, "policy never parked anything"
        for i, r in enumerate(reqs):
            assert r.out_tokens == want[i], f"request {i}"

    @pytest.mark.slow
    def test_full_mesh_tp8(self):
        mparams = init_params(MHA8, jax.random.PRNGKey(8))
        rng = np.random.default_rng(24)
        prompt = rng.integers(0, MHA8.vocab_size, 6).tolist()
        ref = _engine(mparams, cfg=MHA8, max_window=4)
        try:
            want = _streams(ref, prompt, n=7)
        finally:
            ref.stop()
        eng = _engine(mparams, cfg=MHA8, max_window=4,
                      layout=EngineLayout.build(8))
        try:
            got = _streams(eng, prompt, n=7)
        finally:
            eng.stop()
        assert got == want

    @pytest.mark.slow
    def test_bf16_parity(self):
        """Same dominance argument at lower precision: both layouts
        quantize identically because placement never rewrites math."""
        import jax.numpy as jnp

        bparams = init_params(TINY, jax.random.PRNGKey(9),
                              dtype=jnp.bfloat16)
        rng = np.random.default_rng(25)
        prompt = rng.integers(0, TINY.vocab_size, 6).tolist()
        ref = _engine(bparams, max_window=4)
        try:
            want = _streams(ref, prompt, n=7)
        finally:
            ref.stop()
        eng = _engine(bparams, max_window=4,
                      layout=EngineLayout.build(2))
        try:
            got = _streams(eng, prompt, n=7)
        finally:
            eng.stop()
        assert got == want


class TestCompileDiscipline:
    @pytest.mark.slow
    def test_one_shape_per_bucket_per_layout(self, params):
        """Under sharding the compile key gains the layout, nothing
        else: the first pass pays one compile per shape, repeating the
        exact workload registers ZERO fresh (phase, bucket) first-seens
        — donation kept the carry shardings stable."""
        rng = np.random.default_rng(26)
        prompt = rng.integers(0, TINY.vocab_size, 9).tolist()
        eng = _engine(params, max_window=8, layout=EngineLayout.build(2))
        try:
            eng.generate(prompt, max_new_tokens=12)  # 11 post-admit: 8+2+1
            buckets = {r.bucket for r in eng.profiler.snapshot()
                       if r.phase == "decode"}
            assert buckets == {8, 2, 1}
            c0 = eng.profiler.compile_count
            eng.generate(prompt, max_new_tokens=12)
            assert eng.profiler.compile_count == c0
            # fresh bucket (4) is exactly one new first-seen
            eng.generate(prompt, max_new_tokens=6)
            assert eng.profiler.compile_count == c0 + 1
            eng.generate(prompt, max_new_tokens=6)
            assert eng.profiler.compile_count == c0 + 1
        finally:
            eng.stop()

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    def test_sharded_window_never_gathers_the_pool(self, kv_dtype):
        """A decode window over a pool sharded by KV head moves the
        step's own rows between devices (a token's K/V, B x n_kv x D)
        and nothing of the pool's size: each device writes and reads
        its own heads' slice of every page. The token write is spelled
        as a scatter over a reshape of the pool (kv_blocks.write_tokens)
        and a reshape that merged n_kv with the axis above it would be
        answered with an all-gather of the pool in every step."""
        import re

        import jax.numpy as jnp

        from kubeinfer_tpu.inference.stepper import (
            decode_window,
            init_slot_state,
        )

        lay = EngineLayout.build(4)
        gqa = init_params(GQA, jax.random.PRNGKey(3))
        state = init_slot_state(GQA, 4, 64, jnp.float32, 33, 16,
                                kv_dtype=kv_dtype)
        pool = state.caches_k[0]
        text = decode_window.lower(
            lay.shard_params(gqa, GQA), lay.shard_state(state), GQA, 1,
            sharded=True,
        ).compile().as_text()
        moved = [
            int(np.prod([int(d) for d in dims.split(",")]))
            for dims in re.findall(
                r"= \w+\[([\d,]+)\]\S* (?:all-gather|all-to-all|"
                r"collective-permute|all-reduce)\(", text)
        ]
        assert moved and max(moved) < pool.size // lay.tp
