"""Quantized int8 KV pool: q8 kernel/twin bit-identity, the symmetric
absmax round-trip bounds, and end-to-end greedy parity vs the bf16
engine.

The kernel runs in interpreter mode (CPU test mesh); the twin is the
contract — decode_attention_blocks_q8 must match
decode_attention_blocks_q8_jnp BIT-for-bit per the repo's kernel/twin
invariant (the int8 path vs bf16 is tolerance-pinned instead: see
test_quant_roundtrip_error_bound for the pinned bound). Pools carry
junk outside the live table entries, tables are permuted and
null-padded, and zero-length rows ride along, so any read that escapes
the table or the tail clip breaks parity loudly.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

import kubeinfer_tpu.inference.flash_attention as fa
from kubeinfer_tpu.inference.kv_blocks import (
    dequantize_blocks,
    pool_shape,
    quantize_blocks,
)
from kubeinfer_tpu.inference.model import attention as dense_attention


def _paged_q8(key, B, max_blocks, block_size, n_heads, n_kv, D, lens,
              T=1):
    """Random quantized-pool operands with adversarial layout: permuted
    non-contiguous tables, null-padded dead entries, junk in every
    un-referenced pool page, and bf16 tails independent of the pool (the
    engine guarantees the tail is the truth for tiles >= tail_base; the
    kernel must source exactly those tiles from it)."""
    kq, kk, kv, ks1, ks2, kt1, kt2 = jax.random.split(key, 7)
    q = jax.random.normal(kq, (B, T, n_heads, D), jnp.float32).astype(
        jnp.bfloat16
    )
    num_blocks = 1 + B * max_blocks + 3
    kp = jax.random.randint(
        kk, pool_shape(num_blocks, block_size, n_kv, D), -127, 128,
        jnp.int32
    ).astype(jnp.int8)
    vp = jax.random.randint(
        kv, pool_shape(num_blocks, block_size, n_kv, D), -127, 128,
        jnp.int32
    ).astype(jnp.int8)
    # positive, spread over two orders of magnitude like real absmax
    ksc = jnp.exp(jax.random.normal(ks1, (num_blocks, n_kv))) * 0.01
    vsc = jnp.exp(jax.random.normal(ks2, (num_blocks, n_kv))) * 0.01
    kt = jax.random.normal(
        kt1, (B, *pool_shape(2, block_size, n_kv, D)), jnp.float32
    ).astype(jnp.bfloat16)
    vt = jax.random.normal(
        kt2, (B, *pool_shape(2, block_size, n_kv, D)), jnp.float32
    ).astype(jnp.bfloat16)
    rng = np.random.default_rng(17)
    perm = rng.permutation(np.arange(1, num_blocks))
    tables = perm[: B * max_blocks].reshape(B, max_blocks)
    tables = np.ascontiguousarray(tables, np.int32)
    lens = np.asarray(lens, np.int64)
    for b in range(B):
        live = -(-int(lens[b]) // block_size)
        tables[b, live:] = 0
    return (q, kp, vp, ksc, vsc, kt, vt, jnp.asarray(tables),
            jnp.asarray(lens, jnp.int32))


class TestQ8KernelTwin:
    def _check(self, B, max_blocks, block_size, n_heads, n_kv, D, lens,
               T=1, seed=31):
        ops = _paged_q8(
            jax.random.PRNGKey(seed), B, max_blocks, block_size,
            n_heads, n_kv, D, lens, T=T,
        )
        q, kp, vp, ksc, vsc, kt, vt, tables, lengths = ops
        got = fa.decode_attention_blocks_q8(
            q, kp, vp, ksc, vsc, kt, vt, tables, lengths,
            interpret=True,
        )
        twin = fa.decode_attention_blocks_q8_jnp(
            q, kp, vp, ksc, vsc, kt, vt, tables, lengths
        )
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(twin),
            err_msg="q8 kernel/twin bit-identity",
        )
        # semantic cross-check against an independent composition: the
        # engine's own CPU fallback (dequant-gather overlay + dense).
        # tail_base derived the same way everywhere: (lens - T) // bs.
        tb = jnp.maximum(lengths - T, 0) // block_size
        kg = fa.dequant_gather_block_kv(kp, ksc, kt, tables, tb)
        vg = fa.dequant_gather_block_kv(vp, vsc, vt, tables, tb)
        S = max_blocks * block_size
        pos = jnp.arange(S)[None, None, :]
        qpos = (lengths[:, None] - T + jnp.arange(T))[:, :, None]
        mask = pos <= qpos
        want = dense_attention(q, kg, vg, mask)
        # live rows only: retired (length-0) rows have no defined
        # output — the twin's penalty fold and dense's all-masked
        # convention legitimately differ there, and the engine never
        # reads them. Their defined-and-finite-ness is still pinned by
        # the bit-identity gate above.
        live = np.asarray(lengths) > 0
        np.testing.assert_allclose(
            np.asarray(twin, np.float32)[live],
            np.asarray(want, np.float32)[live],
            atol=3e-2, rtol=1e-1,
        )
        assert np.all(np.isfinite(np.asarray(twin, np.float32)))

    @pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2), (8, 1)])
    def test_gqa_ratios_mixed_lengths(self, n_heads, n_kv):
        # lengths straddle the tail boundary every way a live row can:
        # mid-block (tail half full), exact block edge, full table,
        # single token, and a retired zero-length row over null entries
        self._check(5, 3, 16, n_heads, n_kv, 16, [17, 16, 48, 1, 0])

    @pytest.mark.parametrize("n_heads,n_kv", [(8, 2), (8, 1)])
    def test_verify_window_spill(self, n_heads, n_kv):
        # T=5 verify windows: rows whose window straddles a block edge
        # read BOTH tail slots (rel 0 and the spill at rel 1) — plus a
        # row fully inside one block and a zero row
        self._check(4, 3, 16, n_heads, n_kv, 16, [18, 33, 5, 0], T=5)

    def test_large_head_dim(self):
        # D=64: the smallest kernel-eligible head dim on real TPUs
        self._check(2, 2, 16, 4, 2, 64, [23, 32])


class TestQuantRoundTrip:
    def test_roundtrip_error_bound(self):
        # symmetric absmax: |x - deq(q(x))| <= scale/2 per element,
        # scale = amax/127 per (block, head) — the PINNED bound the
        # tolerance-based parity gates lean on
        x = jax.random.normal(
            jax.random.PRNGKey(3), pool_shape(8, 16, 4, 32), jnp.float32
        ).astype(jnp.bfloat16)
        q, s = quantize_blocks(x)
        deq = dequantize_blocks(q, s, dtype=jnp.float32)
        err = jnp.abs(deq - x.astype(jnp.float32))
        bound = s[:, :, None, None] / 2.0 * (1.0 + 1e-5)
        assert bool(jnp.all(err <= bound)), float(jnp.max(err / bound))
        amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-2, -1))
        np.testing.assert_allclose(
            np.asarray(s), np.asarray(amax) / 127.0, rtol=1e-6
        )

    def test_zero_block_scale_one(self):
        # all-zero blocks must quantize losslessly with scale 1.0 (not
        # 0, which would NaN the dequant; not amax=0/127)
        x = jnp.zeros(pool_shape(2, 8, 2, 4), jnp.bfloat16)
        q, s = quantize_blocks(x)
        assert bool(jnp.all(q == 0))
        np.testing.assert_array_equal(np.asarray(s), 1.0)
        assert bool(jnp.all(dequantize_blocks(q, s) == 0))

    def test_requant_exact(self):
        # dequant -> requant is EXACT: the amax element quantizes to
        # +-127, so the recovered scale round-trips — the invariant
        # that lets chunked prefill re-scatter already-committed blocks
        x = jax.random.normal(
            jax.random.PRNGKey(9), pool_shape(6, 16, 2, 16), jnp.float32
        ).astype(jnp.bfloat16)
        q1, s1 = quantize_blocks(x)
        q2, s2 = quantize_blocks(dequantize_blocks(q1, s1))
        np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
        np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))


class TestEngineGreedyParity:
    """int8 engine vs bf16 engine, token for token, on non-degenerate
    prompts (f32 params: random-init logit gaps sit well above the
    dequant perturbation, so greedy argmax is stable — bench.py's
    kv_quant phase documents why bf16 random weights are not)."""

    def _engines(self, model="tiny", **kw):
        from kubeinfer_tpu.inference import PRESETS, init_params
        from kubeinfer_tpu.inference.batching import ContinuousEngine

        cfg = PRESETS[model]
        params = init_params(cfg, jax.random.PRNGKey(6))
        mk = dict(
            n_slots=2, cache_len=128, block_size=16,
            prefill_chunk_blocks=0,
        )
        mk.update(kw)
        ref = ContinuousEngine(params, cfg, kv_dtype="bf16", **mk)
        got = ContinuousEngine(params, cfg, kv_dtype="int8", **mk)
        return cfg, ref, got

    def _run(self, eng, prompts, max_new):
        eng.start()
        try:
            reqs = [eng.submit(p, max_new_tokens=max_new)
                    for p in prompts]
            for r in reqs:
                assert r.done.wait(timeout=120)
                assert not r.failed, r.failed
            return [list(r.out_tokens) for r in reqs]
        finally:
            eng.stop()

    def test_greedy_identity_tiny(self):
        cfg, ref, got = self._engines()
        rng = np.random.default_rng(11)
        # 40 new tokens from a 5-token prompt cross two block edges:
        # admit-quantize, decode-commit, and tail-shift all in-window
        prompts = [
            rng.integers(0, cfg.vocab_size, 5).tolist(),
            rng.integers(0, cfg.vocab_size, 37).tolist(),
        ]
        want = self._run(ref, prompts, 40)
        have = self._run(got, prompts, 40)
        assert want == have
        assert got.quant_blocks_total > 0
        assert ref.quant_blocks_total == 0

    def test_greedy_identity_warm_admit(self):
        # radix warm path: the second submit re-admits from quantized
        # cached blocks — dequant-gather at admit must reproduce the
        # cold path's tokens exactly on both engines
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

    @pytest.mark.slow
    def test_greedy_identity_bench_model(self):
        # the bench model (280M, GQA 16:8, D=64): the scale the paper's
        # capacity claim is benchmarked at
        cfg, ref, got = self._engines(
            model="bench-280m", cache_len=256, block_size=64,
        )
        rng = np.random.default_rng(14)
        prompts = [
            rng.integers(0, cfg.vocab_size, 7).tolist(),
            rng.integers(0, cfg.vocab_size, 70).tolist(),
        ]
        want = self._run(ref, prompts, 24)
        have = self._run(got, prompts, 24)
        assert want == have
        assert got.quant_blocks_total > 0
