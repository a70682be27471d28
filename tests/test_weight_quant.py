"""Quantized int8 weights: fused dequant-matmul kernel/twin
bit-identity, per-tile symmetric absmax round-trip bounds, and
end-to-end token parity of the int8 engine against bf16 — cold, warm
(radix readmit), chunked prefill, speculative verify, and tp=2.

The kernel runs in interpreter mode (CPU test mesh); the twin is the
contract — quant_matmul must match quant_matmul_jnp BIT-for-bit per
the repo's kernel/twin invariant. Engine parity uses the exact-grid
construction from the TP tests: the reference engine holds the
DEQUANTIZED f32 weights (so both engines see the same quantization
grid and the remaining difference is f32 ulp noise, orders below
random-init logit gaps), which makes greedy AND sampled streams
token-identical rather than tolerance-matched.
"""

import dataclasses
import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from kubeinfer_tpu.inference import PRESETS, init_params
from kubeinfer_tpu.inference.batching import (
    ContinuousEngine,
    EngineOverloadedError,
)
from kubeinfer_tpu.inference.sharding import EngineLayout
from kubeinfer_tpu.inference import weight_quant
from kubeinfer_tpu.inference.weight_quant import (
    QUANT_LEAVES,
    VMEM_BUDGET_BYTES,
    VMEM_LIMIT_BYTES,
    dequantize_params,
    dequantize_weight,
    params_weight_dtype,
    quant_matmul,
    quant_matmul_dense,
    quant_matmul_jnp,
    quant_matmul_tiles,
    quantize_params,
    quantize_weight,
)

TINY = PRESETS["tiny"]

# every (K, N) the qwen2-7b step programs issue (gate/up, down, q/o,
# k/v) and the row counts they issue them at: decode's 8 live rows, the
# 64/128/256/512-row admit buckets and the 512-row prefill chunk
QWEN2_PROJECTIONS = ((3584, 18944), (18944, 3584), (3584, 3584), (3584, 512))
QWEN2_ROWS = (8, 64, 128, 256, 512)
TINY_PROJECTIONS = ((64, 64), (64, 32), (64, 128), (128, 64))
TINY_ROWS = (1, 2, 16, 64)
# one call's grid; a qwen2-7b layer's seven (q, k, v, o, gate, up, down)
# were 14,224 steps at 128^3 tiles
CALL_STEP_CEILING = 64
LAYER_STEP_CEILING = 200


LAYER_CALLS = (2, 1, 2, 2)  # gate and up, down, q and o, k and v


def _layer_steps(M):
    return sum(
        n * quant_matmul_tiles(M, K, N).grid_steps
        for n, (K, N) in zip(LAYER_CALLS, QWEN2_PROJECTIONS))


class TestQuantMatmulTiles:
    """The chooser alone: a pure function of (M, K, N, activation
    width) that the kernel, the twin and the documents all read."""

    @pytest.mark.parametrize("x_bytes", (2, 4))
    @pytest.mark.parametrize(
        "M,K,N",
        [(M, K, N) for K, N in QWEN2_PROJECTIONS for M in QWEN2_ROWS]
        + [(M, K, N) for K, N in TINY_PROJECTIONS for M in TINY_ROWS],
    )
    def test_tiles_legal_small_grid_inside_vmem(self, M, K, N, x_bytes):
        t = quant_matmul_tiles(M, K, N, x_bytes)
        # legal for the dtypes: the activation's sublane pack (16 rows
        # of bf16, 8 of f32), 128 lanes, and int8's 32-row pack on k
        assert t.block_m % (32 // x_bytes) == 0
        assert t.block_k % 128 == 0 and t.block_n % 128 == 0
        # shapes_from_hlo reads a [1, K] operand as the scale row
        assert t.block_m >= 2
        mt, nt, kt = (-(-M // t.block_m), -(-N // t.block_n),
                      -(-K // t.block_k))
        assert t.grid_steps == mt * nt * kt <= CALL_STEP_CEILING
        assert t.vmem_bytes <= VMEM_BUDGET_BYTES < VMEM_LIMIT_BYTES
        # the k-split reads K and nothing else
        for other_m in (1, 8, 128, 256, 512, 2048):
            for other_n in (N, 128, 18944):
                for xb in (2, 4):
                    assert quant_matmul_tiles(
                        other_m, K, other_n, xb).block_k == t.block_k

    @pytest.mark.parametrize("K,N", QWEN2_PROJECTIONS)
    def test_served_weights_are_never_padded(self, K, N):
        # a tile that does not divide the extent makes _tile_operands
        # copy the whole int8 weight on every call
        for M in QWEN2_ROWS:
            t = quant_matmul_tiles(M, K, N)
            assert K % t.block_k == 0 and N % t.block_n == 0
            # the weight streams once: rows up to the prefill chunk
            # ride in one m tile
            assert t.block_m >= M

    @pytest.mark.parametrize("M", QWEN2_ROWS)
    def test_layer_takes_hundreds_of_steps_not_thousands(self, M):
        assert _layer_steps(M) <= LAYER_STEP_CEILING
        # what 128^3 tiles cost, for the record this PR is about
        old = sum(
            n * -(-max(M, 128) // 128) * (K // 128) * (N // 128)
            for n, (K, N) in zip(LAYER_CALLS, QWEN2_PROJECTIONS))
        assert old >= 14224 and _layer_steps(M) * 50 < old

    def test_split_prefers_a_divisor_and_pads_a_prime(self):
        # 18944 = 4 x 4736 (148 lanes; 3 parts would pad), whole below
        # the cap, and a prime lane count falls back to padding
        assert quant_matmul_tiles(8, 18944, 512).block_k == 4736
        assert quant_matmul_tiles(8, 3584, 512).block_k == 3584
        assert quant_matmul_tiles(8, 14336, 4096).block_k == 3584
        t = quant_matmul_tiles(8, 47 * 128, 512)
        assert t.block_k == 24 * 128 and t.grid_steps == 2
        # N of 37 tiles: 18944 = 37 x 512
        assert quant_matmul_tiles(8, 3584, 18944).block_n == 512
        # rows past the chunk split evenly instead of padding to 1024
        assert quant_matmul_tiles(520, 3584, 512).block_m == 272


class TestQuantMatmulKernelTwin:
    def _check(self, M, K, N, bm, bn, bk, dtype, tile=128, seed=31):
        kx, kw = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(kx, (M, K), jnp.float32).astype(dtype)
        # codes drawn directly: quantising a [18944, N] normal costs
        # more than the matmul under test
        if tile is None:
            d = {"qw": jax.random.randint(kw, (K, N), -127, 128, jnp.int8),
                 "scale": jax.random.uniform(
                     kx, (N,), jnp.float32, 1e-3, 3e-3)}
        else:
            d = quantize_weight(
                jax.random.normal(kw, (K, N), jnp.float32), tile=tile)
        got = quant_matmul(
            x, d["qw"], d["scale"],
            block_m=bm, block_n=bn, block_k=bk, interpret=True,
        )
        twin = quant_matmul_jnp(
            x, d["qw"], d["scale"], block_m=bm, block_n=bn, block_k=bk,
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(twin),
            err_msg="quant_matmul kernel/twin bit-identity",
        )
        # semantic cross-check against the engine's own GSPMD/CPU
        # fallback (whole-array dot): tolerance-class, because the
        # tiled accumulation order legitimately differs
        want = quant_matmul_dense(x, d["qw"], d["scale"])
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=3e-2, rtol=1e-1,
        )
        assert np.all(np.isfinite(np.asarray(got, np.float32)))

    def test_ragged_everything_f32(self):
        # M, K, N all off-grid: every pad path (m tail, k zero-fill,
        # n tail crossing a scale tile) is live in one shape
        self._check(7, 64, 200, 8, 128, 32, jnp.float32, tile=64)

    def test_aligned_bf16(self):
        # the clean serving shape: bf16 activations, everything on the
        # 128 grid, one tile per block_n
        self._check(16, 128, 128, 8, 128, 128, jnp.bfloat16)

    def test_prime_dims_multirow_grid(self):
        # prime-ish dims with a multi-row m grid and deep k loop: the
        # scratch accumulator must carry across 7 k-steps per (m, n)
        self._check(130, 100, 257, 16, 128, 16, jnp.float32)

    def test_single_row_small_tile(self):
        # decode shape (M=1) with tile smaller than block_n: one
        # kernel n-block spans two scale tiles
        self._check(1, 64, 64, 8, 64, 32, jnp.bfloat16, tile=32)

    def test_tile_deeper_than_one_slice_ragged_tail(self):
        # block_k 320 = two whole 128-deep slices and a 64-deep tail:
        # the slice walk inside one tile, then two k steps of it
        self._check(24, 600, 256, 8, 128, 320, jnp.float32)

    # the chooser's own tiles (block_* left to quant_matmul_tiles) on
    # shapes with the served divisibility pattern, one extent at
    # qwen2-7b's size and the others small enough for interpret mode
    @pytest.mark.parametrize("dtype", (jnp.bfloat16, jnp.float32))
    @pytest.mark.parametrize("M,K,N,why", [
        (8, 18944, 512, "k-splits-4x4736-n-4-lanes"),
        (8, 3584, 18944, "n-of-37-tiles"),
        (40, 3584, 512, "k-whole-28-slices"),
        (128, 3584, 1024, "two-n-tiles-128-rows"),
        (1040, 256, 256, "three-m-tiles"),
        (7, 6016, 200, "prime-lane-count-pads-k"),
        (130, 100, 257, "prime-everything"),
        (1, 64, 64, "single-row"),
        (7, 64, 200, "ragged-everything"),
    ])
    def test_chooser_tiles(self, M, K, N, why, dtype):
        t = quant_matmul_tiles(M, K, N, jnp.dtype(dtype).itemsize)
        if why.startswith("k-splits"):
            assert (t.block_k, t.block_n, t.grid_steps) == (4736, 512, 4)
        if why.startswith("n-of-37"):
            assert (t.block_n, t.grid_steps) == (512, 37)
        self._check(M, K, N, None, None, None, dtype, tile=None)


class TestRowInvariance:
    """A row's bits do not depend on how many rows ride beside it: the
    same prompt answered cold (a 512-row chunk, a 128/256/512-row admit
    bucket), from the radix cache or in a decode window of 1 or 8 live
    rows has to give the same tokens. block_m follows M, block_k and
    the slice walk inside a tile follow K alone."""

    @staticmethod
    def _operands(K, N, dtype):
        """Data on which only the program's own order can show. Within
        a 128-deep slice every product is an integer times that slice's
        power of two and the slice's sum stays under 2^18 of them: exact
        in f32 in ANY order, so the backend's gemm (XLA's CPU dot takes
        another path below 128 rows) cannot move a bit. Across slices
        the powers differ by up to 2^18, so the f32 sums of slices and
        of k steps round, and a different walk reads different bits
        (test_the_data_has_teeth)."""
        kx, kw = jax.random.split(jax.random.PRNGKey(K + N))
        ints = jax.random.randint(kx, (512, K), -15, 16).astype(jnp.float32)
        power = 2.0 ** (9 * ((jnp.arange(K) // 128) * 5 % 3))
        x = (ints * power[None, :]).astype(dtype)
        qw = jax.random.randint(kw, (K, N), -127, 128, jnp.int8)
        scale = jax.random.uniform(kx, (N,), jnp.float32, 1e-3, 3e-3)
        return x, qw, scale

    @pytest.mark.parametrize("path", ("kernel", "twin"))
    @pytest.mark.parametrize("K,N,dtype", [
        (18944, 256, jnp.bfloat16),  # k split in 4
        (3584, 1024, jnp.bfloat16),  # k whole, two n tiles
        (3584, 512, jnp.float32),
        (6016, 257, jnp.float32),  # a prime lane count: padded k and n
    ])
    def test_row_alone_among_8_among_512(self, K, N, dtype, path):
        x, qw, scale = self._operands(K, N, dtype)
        fn = (functools.partial(quant_matmul, interpret=True)
              if path == "kernel" else quant_matmul_jnp)
        among_512 = np.asarray(fn(x, qw, scale))
        among_8 = np.asarray(fn(x[:8], qw, scale))
        alone = np.asarray(fn(x[3:4], qw, scale))
        chunk = np.asarray(fn(x[:130], qw, scale))  # a padded m tile
        np.testing.assert_array_equal(among_8, among_512[:8])
        np.testing.assert_array_equal(alone, among_512[3:4])
        np.testing.assert_array_equal(chunk, among_512[:130])

    @pytest.mark.parametrize("K,dtype", [
        (18944, jnp.bfloat16), (3584, jnp.float32)])
    def test_the_data_has_teeth(self, K, dtype):
        # the same rows under another k-split read other bits: were
        # block_k to follow M, the test above would catch it
        x, qw, scale = self._operands(K, 256, dtype)
        ours = np.asarray(quant_matmul_jnp(x[:8], qw, scale))
        other = np.asarray(quant_matmul_jnp(
            x[:8], qw, scale,
            block_k=quant_matmul_tiles(8, K, 256).block_k // 2 + 64))
        assert (ours != other).any()
        np.testing.assert_allclose(
            ours.astype(np.float32), other.astype(np.float32), rtol=2e-2)


class TestQuantRoundTrip:
    def test_roundtrip_error_bound(self):
        # symmetric absmax: |w - deq(q(w))| <= scale/2 per element,
        # scale = amax/127 per (out-tile) — the PINNED bound the
        # engine-parity and bench accuracy gates lean on
        w = jax.random.normal(
            jax.random.PRNGKey(3), (96, 200), jnp.float32
        )
        d = quantize_weight(w, tile=64)
        deq = dequantize_weight(d, dtype=jnp.float32)
        err = jnp.abs(deq - w)
        bound = d["scale"][None, :] / 2.0 * (1.0 + 1e-5)
        assert bool(jnp.all(err <= bound)), float(jnp.max(err / bound))
        # scale really is per-column-constant-per-tile amax/127
        amax = jnp.max(jnp.abs(w[:, :64]), axis=None)
        np.testing.assert_allclose(
            float(d["scale"][0]), float(amax) / 127.0, rtol=1e-6
        )

    def test_zero_tile_scale_one(self):
        # all-zero tiles must quantize losslessly with scale 1.0 (not
        # 0, which would NaN nothing here but corrupt requant; not
        # amax=0/127)
        w = jnp.zeros((32, 64), jnp.float32)
        d = quantize_weight(w, tile=32)
        assert bool(jnp.all(d["qw"] == 0))
        np.testing.assert_array_equal(np.asarray(d["scale"]), 1.0)
        assert bool(jnp.all(dequantize_weight(d) == 0))

    def test_requant_exact(self):
        # dequant -> requant is EXACT: the amax element quantizes to
        # +-127, so the recovered scale round-trips — the invariant
        # that makes checkpoint restore + engine re-ingest lossless
        w = jax.random.normal(jax.random.PRNGKey(9), (48, 96))
        d1 = quantize_weight(w, tile=32)
        d2 = quantize_weight(dequantize_weight(d1, jnp.float32), tile=32)
        np.testing.assert_array_equal(np.asarray(d1["qw"]),
                                      np.asarray(d2["qw"]))
        np.testing.assert_array_equal(np.asarray(d1["scale"]),
                                      np.asarray(d2["scale"]))

    def test_double_quantize_guard(self):
        params = init_params(TINY, jax.random.PRNGKey(0),
                             weight_dtype="int8")
        assert params_weight_dtype(params) == "int8"
        with pytest.raises(ValueError, match="already weight-quantized"):
            quantize_params(params)
        # the engine-side guard: int8-held params + bf16 request is a
        # config error, never a silent dequant
        with pytest.raises(ValueError, match="weight-quantized"):
            ContinuousEngine(params, TINY, n_slots=2, cache_len=64,
                             block_size=8, weight_dtype="bf16")

    def test_quantized_tree_structure(self):
        params = init_params(TINY, jax.random.PRNGKey(0),
                             dtype=jnp.bfloat16, weight_dtype="int8")
        layer = params["layers"][0]
        for name in QUANT_LEAVES:
            leaf = layer[name]
            assert set(leaf) == {"qw", "scale"}
            assert leaf["qw"].dtype == jnp.int8
            assert leaf["scale"].dtype == jnp.float32
            assert leaf["scale"].shape == (leaf["qw"].shape[1],)
        # precision-critical leaves stay bf16
        assert params["embed_tokens"].dtype == jnp.bfloat16
        assert params["norm"].dtype == jnp.bfloat16

    def test_bf16_mode_is_untouched(self):
        # weight_dtype="bf16" must be byte-identical to the pre-quant
        # world: no dict leaves anywhere, and the degenerate layout
        # passes params through by identity (same compile cache)
        params = init_params(TINY, jax.random.PRNGKey(0))
        assert params_weight_dtype(params) == "bf16"
        assert all(
            not isinstance(v, dict)
            for layer in params["layers"] for v in layer.values()
        )
        eng = ContinuousEngine(params, TINY, n_slots=2, cache_len=64,
                               block_size=8)
        assert eng.weight_dtype == "bf16"
        assert eng.params is params


class TestEngineTokenParity:
    """int8 engine vs the SAME-grid f32 reference, token for token.

    The reference holds dequantize_params(quantize_params(w)) — both
    engines see identical quantized values, so the only divergence is
    dense-vs-scaled matmul ulp noise (~1e-7) against random-init logit
    gaps (~1e-2): greedy and sampled streams must match exactly, the
    same dominance argument EngineLayout's TP parity rests on.
    """

    CFG = TINY

    def _engines(self, tp=1, **kw):
        cfg = self.CFG
        params = init_params(cfg, jax.random.PRNGKey(6))
        qp = quantize_params(params)
        mk = dict(n_slots=2, cache_len=128, block_size=16,
                  prefill_chunk_blocks=0)
        mk.update(kw)
        if tp > 1:
            mk["layout"] = EngineLayout.build(tp)
        ref = ContinuousEngine(dequantize_params(qp, jnp.float32), cfg,
                               **mk)
        if tp > 1:
            mk["layout"] = EngineLayout.build(tp)
        got = ContinuousEngine(qp, cfg, weight_dtype="int8", **mk)
        assert got.weight_dtype == "int8"
        assert got.model_param_bytes < ref.model_param_bytes
        return cfg, ref, got

    def _run(self, eng, prompts, max_new, **samp):
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=max_new, **samp)
                    for p in prompts]
            for r in reqs:
                assert r.done.wait(timeout=120)
                assert not r.failed, r.failed
            return [list(r.out_tokens) for r in reqs]
        finally:
            eng.stop()

    def test_greedy_and_sampled_identity(self):
        cfg, ref, got = self._engines()
        rng = np.random.default_rng(11)
        prompts = [
            rng.integers(0, cfg.vocab_size, 5).tolist(),
            rng.integers(0, cfg.vocab_size, 37).tolist(),
        ]
        assert self._run(ref, prompts, 40) == self._run(got, prompts, 40)
        # fresh pair for the sampled streams: engines are one-shot
        # (stop() is terminal), and seeded sampling must match anyway
        cfg, ref, got = self._engines()
        samp = dict(temperature=0.8, seed=5, top_k=13)
        assert (self._run(ref, prompts, 24, **samp)
                == self._run(got, prompts, 24, **samp))

    def test_greedy_identity_warm_admit(self):
        # radix warm path: the second submit re-admits from cached KV
        # blocks computed BY the quantized forward — prefix reuse must
        # reproduce the cold path's tokens exactly on both engines
        cfg, ref, got = self._engines()
        rng = np.random.default_rng(12)
        prompt = rng.integers(0, cfg.vocab_size, 33).tolist()
        for eng in (ref, got):
            eng.start()
        try:
            outs = {}
            for name, eng in (("ref", ref), ("got", got)):
                r1 = eng.submit(prompt, max_new_tokens=24)
                assert r1.done.wait(timeout=120)
                r2 = eng.submit(prompt, max_new_tokens=24)
                assert r2.done.wait(timeout=120)
                assert list(r1.out_tokens) == list(r2.out_tokens)
                outs[name] = list(r1.out_tokens)
            assert outs["ref"] == outs["got"]
        finally:
            ref.stop()
            got.stop()

    def test_greedy_identity_chunked_prefill(self):
        cfg, ref, got = self._engines(prefill_chunk_blocks=2)
        rng = np.random.default_rng(13)
        prompts = [rng.integers(0, cfg.vocab_size, 89).tolist()]
        assert self._run(ref, prompts, 20) == self._run(got, prompts, 20)

    def test_greedy_identity_spec_verify(self):
        # speculative path: the int8 TARGET verifies draft proposals —
        # verify_window runs the quantized forward. The draft stays
        # plain (self-draft on the reference grid) in both engines so
        # proposal streams are identical and any divergence is the
        # verify matmuls.
        cfg = self.CFG
        params = init_params(cfg, jax.random.PRNGKey(6))
        qp = quantize_params(params)
        dq = dequantize_params(qp, jnp.float32)
        mk = dict(n_slots=2, cache_len=128, block_size=16,
                  prefill_chunk_blocks=0, spec_draft=(dq, cfg),
                  spec_k=4)
        ref = ContinuousEngine(dq, cfg, **mk)
        got = ContinuousEngine(qp, cfg, weight_dtype="int8", **mk)
        rng = np.random.default_rng(14)
        prompts = [rng.integers(0, cfg.vocab_size, 9).tolist()]
        want = self._run(ref, prompts, 24)
        have = self._run(got, prompts, 24)
        assert want == have
        assert got.scheduler_stats()["spec_draft_tokens"] > 0

    @pytest.mark.slow
    def test_greedy_identity_tp2(self):
        # tp=2 on the virtual mesh: quantized leaves shard via
        # expand_quant_specs (qw on the weight's spec, scale on the out
        # axis) and the forward takes the GSPMD-partitionable dense
        # dequant path — tokens must still match the same-grid ref
        cfg, ref, got = self._engines(tp=2, cache_len=64, block_size=8)
        rng = np.random.default_rng(15)
        prompts = [rng.integers(0, cfg.vocab_size, 12).tolist()]
        assert self._run(ref, prompts, 16) == self._run(got, prompts, 16)


class TestEngineTokenParityOnTheKernelGrid(TestEngineTokenParity):
    """The same exact-grid parity, restated for the grid the chip runs.

    On the CPU quant_matmul_auto takes the dense route, so the class
    above never walks the kernel's tiles. Here every int8 projection of
    every step program goes through quant_matmul itself (interpret
    mode) at the tiles quant_matmul_tiles picks, as on the chip: admit
    buckets, prefill chunks, decode windows and verify windows issue
    different row counts, and cold, warm, chunked and verified streams
    must still equal the same-grid f32 reference token for token. The
    config is this class's own (down_proj's K = 6144 splits 2 x 3072,
    gate/up's N takes three 2048-wide tiles), so its step programs are
    traced here, on this route, and not fetched from the jit cache of
    a dense-route test."""

    CFG = dataclasses.replace(TINY, intermediate_size=6144)

    @pytest.fixture(autouse=True)
    def _kernel_route(self, monkeypatch):
        t = quant_matmul_tiles(8, 6144, 64, 4)
        assert (t.block_k, t.grid_steps) == (3072, 2)
        monkeypatch.setattr(
            weight_quant, "quant_matmul_available", lambda: True)
        monkeypatch.setattr(
            weight_quant, "quant_matmul",
            functools.partial(weight_quant.quant_matmul, interpret=True))


class TestCheckpointWeightDtype:
    def test_save_restore_quantized_lossless(self, tmp_path):
        ocp = pytest.importorskip("orbax.checkpoint")  # noqa: F841
        from kubeinfer_tpu.inference.checkpoint import (
            restore_checkpoint, save_checkpoint,
        )

        params = init_params(TINY, jax.random.PRNGKey(2),
                             weight_dtype="int8")
        save_checkpoint(str(tmp_path / "ck"), params, TINY, step=7)
        import json
        meta = json.loads((tmp_path / "ck" / "meta.json").read_text())
        assert meta["weight_dtype"] == "int8"
        back, cfg, step = restore_checkpoint(str(tmp_path / "ck"))
        assert step == 7
        # bit-lossless: identical int8 codes and f32 scales — restore
        # must NEVER re-quantize (that would re-derive scales from the
        # codes and corrupt silently)
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored tree drops straight into an int8 engine: the held
        # dtype matches the request, so the double-quantize guard is
        # a no-op, not a trip
        eng = ContinuousEngine(back, cfg, n_slots=2, cache_len=64,
                               block_size=8, weight_dtype="int8")
        assert eng.weight_dtype == "int8"

    def test_bf16_meta_default(self, tmp_path):
        ocp = pytest.importorskip("orbax.checkpoint")  # noqa: F841
        from kubeinfer_tpu.inference.checkpoint import (
            restore_checkpoint, save_checkpoint,
        )

        params = init_params(TINY, jax.random.PRNGKey(2))
        save_checkpoint(str(tmp_path / "ck"), params, TINY)
        import json
        meta = json.loads((tmp_path / "ck" / "meta.json").read_text())
        assert meta["weight_dtype"] == "bf16"
        back, _, _ = restore_checkpoint(str(tmp_path / "ck"))
        assert params_weight_dtype(back) == "bf16"


class TestQueueDepthShedding:
    def test_submit_sheds_past_limit(self):
        params = init_params(TINY, jax.random.PRNGKey(0))
        # engine deliberately NOT started: submits queue up, which is
        # exactly the state the limit exists to refuse at
        eng = ContinuousEngine(params, TINY, n_slots=2, cache_len=64,
                               block_size=8, queue_depth_limit=2)
        assert eng.queue_depth_limit == 2
        eng.submit([1, 2, 3], max_new_tokens=4)
        eng.submit([1, 2, 3], max_new_tokens=4)
        with pytest.raises(EngineOverloadedError) as ei:
            eng.submit([1, 2, 3], max_new_tokens=4)
        assert ei.value.retry_after_s > 0
        # the refusal is ledgered as the SPEC's queued self-loop then
        # the terminal: submit -> backpressure -> fail(shed)
        evs = eng.flight.snapshot()
        kinds = [e.kind for e in evs]
        i = kinds.index("backpressure")
        bp = evs[i]
        assert bp.detail["reason"] == "queue_depth_limit"
        assert bp.detail["limit"] == 2
        fail = next(e for e in evs[i:] if e.kind == "fail")
        assert fail.detail["reason"] == "shed"
        assert eng.stats_summary()["weight_dtype"] == "bf16"

    def test_server_responds_503_with_retry_after(self):
        import urllib.error
        import urllib.request

        from kubeinfer_tpu.inference.engine import Engine
        from kubeinfer_tpu.inference.server import InferenceServer

        params = init_params(TINY, jax.random.PRNGKey(0))
        eng = ContinuousEngine(params, TINY, n_slots=2, cache_len=64,
                               block_size=8, queue_depth_limit=1)
        # one queued request fills the depth budget (engine not
        # started, so it stays queued); the HTTP request must then be
        # refused fast with the backoff hint, not enqueued behind it
        eng.submit([1, 2, 3], max_new_tokens=4)
        srv = InferenceServer(Engine(params, TINY), model_id="tiny",
                              port=0, continuous=eng).start()
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{srv.port}/v1/completions",
                data=b'{"prompt": [1, 2, 3], "max_tokens": 2}',
                method="POST",
                headers={"Content-Type": "application/json"},
            )
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 503
            assert int(ei.value.headers["Retry-After"]) >= 1
            import json
            body = json.loads(ei.value.read())
            assert body["error"]["type"] == "overloaded"
            out = srv.registry.render()
            assert ('kubeinfer_requests_shed_total'
                    '{reason="queue_depth_limit"} 1') in out
        finally:
            srv.stop()
            eng.stop()
