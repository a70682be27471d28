"""Parity tests: Pallas flash attention vs the dense jnp path.

Runs the kernel in interpreter mode (works on the CPU test mesh); the
real-TPU path is exercised by bench.py's engine benchmark. Parity target:
model.attention (same inputs -> same outputs within dtype tolerance),
including GQA grouping, multi-tile accumulation, ragged masks, and
fully-masked (padding) rows.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from kubeinfer_tpu.inference.flash_attention import (
    attention_auto,
    flash_attention,
)
from kubeinfer_tpu.inference.kv_blocks import pool_shape, rows_to_pages
from kubeinfer_tpu.inference.model import attention as dense_attention


def _rand(key, B, T, S, n_heads, n_kv, D, dtype):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, T, n_heads, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (B, S, n_kv, D), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (B, S, n_kv, D), jnp.float32).astype(dtype)
    return q, k, v


class TestFlashParity:
    def _check(self, B, T, S, n_heads, n_kv, D, mask, dtype=jnp.float32,
               tile_t=8, tile_s=16, atol=2e-5):
        q, k, v = _rand(jax.random.PRNGKey(0), B, T, S, n_heads, n_kv, D,
                        dtype)
        want = dense_attention(q, k, v, mask)
        got = flash_attention(
            q, k, v, mask, tile_t=tile_t, tile_s=tile_s, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            atol=atol, rtol=1e-4,
        )

    def test_causal_multi_tile(self):
        # 4 query tiles x 4 kv tiles exercises the cross-tile recurrence
        T = S = 64
        mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((T, S), bool))[None], (2, T, S)
        )
        self._check(2, T, S, 4, 4, 16, mask)

    def test_gqa_groups_fold(self):
        T, S = 16, 32
        mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((T, S), bool), k=S - T)[None], (2, T, S)
        )
        self._check(2, T, S, 8, 2, 16, mask)

    def test_ragged_cache_mask(self):
        # prefill-chunk shape: T queries against a longer cache with
        # per-row valid lengths (the engine's actual mask pattern)
        B, T, S = 3, 8, 48
        lens = jnp.asarray([5, 48, 17])
        pos = jnp.arange(S)
        q_pos = 40 + jnp.arange(T)  # chunk offset 40
        mask = (pos[None, None, :] <= q_pos[None, :, None]) & (
            pos[None, None, :] < lens[:, None, None]
        )
        self._check(B, T, S, 4, 2, 8, jnp.broadcast_to(mask, (B, T, S)))

    def test_fully_masked_rows_match_dense(self):
        # rows with nothing attendable: dense softmax of a constant row
        # is uniform; flash must reproduce that (p == 1 everywhere)
        B, T, S = 1, 8, 16
        mask = jnp.zeros((B, T, S), bool)
        self._check(B, T, S, 2, 2, 8, mask)

    def test_bf16_inputs(self):
        T = S = 32
        mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((T, S), bool))[None], (1, T, S)
        )
        self._check(1, T, S, 4, 2, 16, mask, dtype=jnp.bfloat16, atol=2e-2)

    def test_single_tile_equals_multi_tile(self):
        T = S = 32
        mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((T, S), bool))[None], (1, T, S)
        )
        q, k, v = _rand(jax.random.PRNGKey(1), 1, T, S, 4, 4, 8,
                        jnp.float32)
        one = flash_attention(q, k, v, mask, tile_t=32, tile_s=32,
                              interpret=True)
        many = flash_attention(q, k, v, mask, tile_t=8, tile_s=8,
                               interpret=True)
        np.testing.assert_allclose(
            np.asarray(one), np.asarray(many), atol=2e-5, rtol=1e-4
        )

    def test_auto_falls_back_off_tpu(self):
        # CPU test env: attention_auto must route to the dense path and
        # still be exact
        T, S = 8, 16
        mask = jnp.broadcast_to(
            jnp.tril(jnp.ones((T, S), bool), k=S - T)[None], (1, T, S)
        )
        q, k, v = _rand(jax.random.PRNGKey(2), 1, T, S, 2, 2, 8,
                        jnp.float32)
        np.testing.assert_allclose(
            np.asarray(attention_auto(q, k, v, mask)),
            np.asarray(dense_attention(q, k, v, mask)),
        )

    def test_rejects_unaligned(self):
        with pytest.raises(ValueError, match="divisible"):
            q, k, v = _rand(jax.random.PRNGKey(3), 1, 24, 24, 2, 2, 8,
                            jnp.float32)
            flash_attention(q, k, v, jnp.ones((1, 24, 24), bool),
                            tile_t=16, tile_s=16, interpret=True)


class TestRaggedKernel:
    """flash_attention_ragged derives the engine's prefill mask from
    (chunk offset, row lengths) in-kernel; parity target is the dense
    path fed the equivalently constructed bool mask."""

    def _mask(self, B, T, S, c0, lens):
        pos = jnp.arange(S)
        q_pos = c0 + jnp.arange(T)
        m = (pos[None, None, :] <= q_pos[None, :, None]) & (
            pos[None, None, :] < jnp.asarray(lens)[:, None, None]
        )
        return jnp.broadcast_to(m, (B, T, S))

    @pytest.mark.parametrize("c0", [0, 8, 40])
    def test_matches_dense_with_equivalent_mask(self, c0):
        from kubeinfer_tpu.inference.flash_attention import (
            flash_attention_ragged,
        )

        B, T, S = 3, 8, 48
        lens = [5, 48, 17]
        q, k, v = _rand(jax.random.PRNGKey(4), B, T, S, 4, 2, 8,
                        jnp.float32)
        want = dense_attention(q, k, v, self._mask(B, T, S, c0, lens))
        got = flash_attention_ragged(
            q, k, v, jnp.int32(c0), jnp.asarray(lens, jnp.int32),
            tile_t=8, tile_s=16, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4
        )

    def test_multi_tile_gqa(self):
        from kubeinfer_tpu.inference.flash_attention import (
            flash_attention_ragged,
        )

        B, T, S = 2, 32, 64
        lens = [64, 20]
        q, k, v = _rand(jax.random.PRNGKey(5), B, T, S, 8, 2, 16,
                        jnp.float32)
        want = dense_attention(q, k, v, self._mask(B, T, S, 16, lens))
        got = flash_attention_ragged(
            q, k, v, jnp.int32(16), jnp.asarray(lens, jnp.int32),
            tile_t=8, tile_s=16, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4
        )

    def test_engine_prefill_unchanged_on_cpu(self):
        # CPU: flash_available is False, so generate must behave exactly
        # as before the ragged wiring (the dense path is untouched)
        from kubeinfer_tpu.inference import PRESETS, init_params
        from kubeinfer_tpu.inference.engine import Engine

        params = init_params(PRESETS["tiny"], jax.random.PRNGKey(0))
        out = Engine(params, PRESETS["tiny"]).generate(
            [[1, 2, 3, 4, 5]], max_new_tokens=4
        )
        assert out.tokens.shape == (1, 4)

    def test_engine_flash_branch_parity_via_interpret(self, monkeypatch):
        # The engine's use_flash branch (closure-captured scan carry c0,
        # prompt_len as row_lens) is TPU-only in production; route it
        # through the interpreted ragged kernel on CPU and pin generate()
        # token-equality against the dense path (r2 review: this wiring
        # was otherwise unreachable by the suite).
        import functools

        import kubeinfer_tpu.inference.engine as eng_mod
        from kubeinfer_tpu.inference import PRESETS, init_params
        from kubeinfer_tpu.inference.engine import Engine
        from kubeinfer_tpu.inference.flash_attention import (
            flash_attention_ragged,
        )

        params = init_params(PRESETS["tiny"], jax.random.PRNGKey(0))
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10, 11]]
        ref = Engine(params, PRESETS["tiny"]).generate(
            prompts, max_new_tokens=6
        )

        monkeypatch.setattr(eng_mod, "flash_available", lambda *a: True)
        monkeypatch.setattr(
            eng_mod, "flash_attention_ragged",
            functools.partial(
                flash_attention_ragged, tile_t=8, tile_s=16, interpret=True
            ),
        )
        eng_mod._generate_jit._clear_cache()
        try:
            got = Engine(params, PRESETS["tiny"]).generate(
                prompts, max_new_tokens=6
            )
        finally:
            eng_mod._generate_jit._clear_cache()  # drop patched traces
        np.testing.assert_array_equal(got.tokens, ref.tokens)
        np.testing.assert_array_equal(got.lengths, ref.lengths)


class TestCausalAuto:
    """The no-cache causal path's in-kernel mask (r2 verdict item 8):
    flash_attention_ragged at q_offset=0, row_lens=S must equal both the
    dense causal reference and the relegated mask-tensor kernel."""

    def test_causal_kernel_matches_dense(self):
        import numpy as np
        from kubeinfer_tpu.inference.flash_attention import (
            flash_attention_ragged,
        )
        from kubeinfer_tpu.inference.model import attention, causal_mask

        rng = np.random.default_rng(3)
        B, T, H, KV, D = 2, 256, 4, 2, 64
        q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, T, KV, D)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, T, KV, D)), jnp.float32)
        mask = jnp.broadcast_to(causal_mask(T)[None], (B, T, T))
        ref = attention(q, k, v, mask)
        got = flash_attention_ragged(
            q, k, v, 0, jnp.full((B,), T, jnp.int32),
            tile_t=128, tile_s=128, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    def test_forward_no_mask_unchanged_numerics(self):
        """model.forward's no-mask path now routes through
        causal_attention_auto — on CPU (flash unavailable) that is the
        dense path bit-for-bit."""
        import numpy as np
        from kubeinfer_tpu.inference import PRESETS, init_params
        from kubeinfer_tpu.inference.model import (
            attention,
            causal_mask,
            forward,
        )

        cfg = PRESETS["tiny"]
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)))
        auto_logits, _ = forward(params, toks, cfg)
        B, T = toks.shape
        explicit_mask = jnp.broadcast_to(causal_mask(T)[None], (B, T, T))
        ref_logits, _ = forward(
            params, toks, cfg, attn_mask=explicit_mask, attn_fn=attention
        )
        np.testing.assert_array_equal(
            np.asarray(auto_logits), np.asarray(ref_logits)
        )


class TestFlashBackward:
    """The recompute-based custom_vjp (r3 verdict item 6): gradients of
    the flash path must match the dense path's at tolerance, across
    multi-tile grids, GQA grouping, ragged lengths, and bf16 inputs."""

    def _grads(self, B, T, n_heads, n_kv, D, lens, dtype=jnp.float32,
               tile_t=8, tile_s=16):
        import kubeinfer_tpu.inference.flash_attention as fa

        q, k, v = _rand(jax.random.PRNGKey(3), B, T, T, n_heads, n_kv, D,
                        dtype)
        row_lens = jnp.asarray(lens, jnp.int32)
        w = jax.random.normal(
            jax.random.PRNGKey(7), (B, T, n_heads, D), jnp.float32
        )

        t_pos = jnp.arange(T)
        mask = (
            (t_pos[None, :, None] >= t_pos[None, None, :])
            & (t_pos[None, None, :] < row_lens[:, None, None])
        )

        def loss_dense(q, k, v):
            o = dense_attention(q, k, v, mask)
            return jnp.sum(o.astype(jnp.float32) * w)

        mp = pytest.MonkeyPatch()
        mp.setattr(fa, "TILE_T", tile_t)
        mp.setattr(fa, "TILE_S", tile_s)
        try:
            def loss_flash(q, k, v):
                o = fa.flash_attention_causal_diff(
                    True, q, k, v, 0, row_lens
                )
                return jnp.sum(o.astype(jnp.float32) * w)

            gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
            gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        finally:
            mp.undo()
        return gd, gf

    def _assert_close(self, gd, gf, atol):
        for want, got, name in zip(gd, gf, "qkv"):
            np.testing.assert_allclose(
                np.asarray(got, np.float32), np.asarray(want, np.float32),
                atol=atol, rtol=5e-3, err_msg=f"d{name}",
            )

    def test_grad_parity_multi_tile(self):
        gd, gf = self._grads(2, 32, 4, 4, 16, [32, 20])
        self._assert_close(gd, gf, 2e-4)

    def test_grad_parity_gqa(self):
        gd, gf = self._grads(1, 32, 4, 2, 16, [25])
        self._assert_close(gd, gf, 2e-4)

    def test_grad_parity_bf16(self):
        gd, gf = self._grads(1, 32, 2, 2, 16, [32], dtype=jnp.bfloat16)
        self._assert_close(gd, gf, 5e-2)

    def test_grad_zero_for_empty_rows(self):
        """row_len == 0 rows must contribute NO gradient. The naive
        recompute would give p == 1 per slot there (s and lse both
        saturate at -1e30 in f32, so exp(s - lse) == 1); _recompute_p
        gates those rows to 0. Note this deliberately diverges from the
        dense path's dv, which leaks a uniform 1/S spread into v for
        fully-masked rows (softmax-of-constant artifact) — zero is the
        right semantics for padding rows. Non-empty rows still match
        dense."""
        gd, gf = self._grads(2, 32, 4, 4, 16, [20, 0])
        for got, name in zip(gf, "qkv"):
            np.testing.assert_array_equal(
                np.asarray(got, np.float32)[1],
                np.zeros_like(np.asarray(got, np.float32)[1]),
                err_msg=f"d{name} row_len=0",
            )
        # row 0 (live) still matches dense; dense dv row 1 carries the
        # 1/S leak so only q/k rows and the live dv row are compared
        for want, got, name in zip(gd, gf, "qkv"):
            np.testing.assert_allclose(
                np.asarray(got, np.float32)[0],
                np.asarray(want, np.float32)[0],
                atol=2e-4, rtol=5e-3, err_msg=f"d{name} live row",
            )

    def test_primal_value_unchanged(self):
        """The custom_vjp primal must equal the plain ragged kernel
        bit-for-bit (custom_vjp contract: fwd reproduces the primal)."""
        import kubeinfer_tpu.inference.flash_attention as fa

        q, k, v = _rand(
            jax.random.PRNGKey(1), 1, 16, 16, 2, 2, 16, jnp.float32
        )
        lens = jnp.asarray([16], jnp.int32)
        a = fa.flash_attention_ragged(
            q, k, v, 0, lens, tile_t=8, tile_s=16, interpret=True
        )
        mp = pytest.MonkeyPatch()
        mp.setattr(fa, "TILE_T", 8)
        mp.setattr(fa, "TILE_S", 16)
        try:
            b = fa.flash_attention_causal_diff(True, q, k, v, 0, lens)
            # the fwd-with-lse variant's primal output (what callers see
            # under differentiation) must also be bit-identical
            c, _ = jax.vjp(
                lambda q, k, v: fa.flash_attention_causal_diff(
                    True, q, k, v, 0, lens
                ),
                q, k, v,
            )
        finally:
            mp.undo()
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert np.array_equal(np.asarray(a), np.asarray(c))

    def test_train_loss_differentiates_with_flash(self):
        """causal_lm_loss's default binding differentiates end to end
        when the flash path engages (forced here via interpret-mode
        attn_fn); loss and grads match the dense-pinned variant."""
        from kubeinfer_tpu.inference import PRESETS, init_params
        from kubeinfer_tpu.inference.train import causal_lm_loss
        import kubeinfer_tpu.inference.flash_attention as fa

        cfg = PRESETS["tiny"]
        params = init_params(cfg, jax.random.PRNGKey(0))
        rng = np.random.default_rng(0)
        tokens = jnp.asarray(
            rng.integers(1, cfg.vocab_size, (1, 17)), jnp.int32
        )

        mp = pytest.MonkeyPatch()
        mp.setattr(fa, "TILE_T", 8)
        mp.setattr(fa, "TILE_S", 16)
        try:
            def flash_fn(q, k, v, mask):
                B, S = q.shape[0], k.shape[1]
                return fa.flash_attention_causal_diff(
                    True, q, k, v, 0, jnp.full((B,), S, jnp.int32)
                )

            lf, gf = jax.value_and_grad(causal_lm_loss)(
                params, tokens, cfg, flash_fn
            )
            ld, gd = jax.value_and_grad(causal_lm_loss)(
                params, tokens, cfg, None
            )
        finally:
            mp.undo()
        np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)
        flat_f = jax.tree.leaves(gf)
        flat_d = jax.tree.leaves(gd)
        for a, b in zip(flat_f, flat_d):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=3e-4, rtol=5e-3,
            )


class TestDecodeKernel:
    """The batched decode kernel (T == 1, per-row live lengths) vs its
    jnp twin: BIT-identical (np.array_equal) per the repo's kernel/twin
    invariant — both run _fold_tile_math over the same tile sweep — and
    the twin vs the dense path at dtype tolerance. Edge lengths cover
    a row at offset 0 (length 1), a row at the full cache, and the
    degenerate all-masked (length 0) row whose defined output is the
    uniform average over the padded cache."""

    def _decode_rand(self, key, B, S, n_heads, n_kv, D, dtype):
        return _rand(key, B, 1, S, n_heads, n_kv, D, dtype)

    def _check(self, B, S, n_heads, n_kv, D, lens, dtype=jnp.float32,
               tile_s=16, dense_atol=2e-5, dense_rtol=1e-4):
        import kubeinfer_tpu.inference.flash_attention as fa

        q, k, v = self._decode_rand(
            jax.random.PRNGKey(11), B, S, n_heads, n_kv, D, dtype
        )
        lengths = jnp.asarray(lens, jnp.int32)
        got = fa.decode_attention(
            q, k, v, lengths, tile_s=tile_s, interpret=True
        )
        twin = fa.decode_attention_jnp(q, k, v, lengths, tile_s=tile_s)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(twin),
            err_msg="kernel/twin bit-identity",
        )
        mask = (
            jnp.arange(S)[None, None, :] < lengths[:, None, None]
        )
        want = dense_attention(q, k, v, jnp.broadcast_to(mask, (B, 1, S)))
        np.testing.assert_allclose(
            np.asarray(twin, np.float32), np.asarray(want, np.float32),
            atol=dense_atol, rtol=dense_rtol,
        )

    @pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2), (8, 1)])
    def test_gqa_ratios_mixed_lengths(self, n_heads, n_kv):
        # per-row lengths straddling tile boundaries: mid-tile, exactly
        # one tile, full cache, length 1
        self._check(4, 48, n_heads, n_kv, 16, [17, 16, 48, 1])

    @pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2)])
    def test_bf16(self, n_heads, n_kv):
        self._check(
            3, 48, n_heads, n_kv, 16, [5, 48, 33], dtype=jnp.bfloat16,
            dense_atol=3e-2, dense_rtol=1e-1,
        )

    def test_edge_lengths(self):
        # offset-0 row (one live slot), full-cache row, zero-length row
        self._check(3, 32, 4, 2, 8, [1, 32, 0])

    def test_all_done_batch(self):
        # every row degenerate (the all-slots-retired batcher shape):
        # the kernel must keep the zero-length rows' tiles live and
        # reproduce the dense uniform average bit-for-bit vs the twin
        self._check(3, 32, 4, 2, 8, [0, 0, 0])

    def test_single_tile_equals_multi_tile(self):
        import kubeinfer_tpu.inference.flash_attention as fa

        q, k, v = self._decode_rand(
            jax.random.PRNGKey(12), 3, 32, 4, 2, 8, jnp.float32
        )
        lengths = jnp.asarray([7, 32, 0], jnp.int32)
        one = fa.decode_attention(
            q, k, v, lengths, tile_s=32, interpret=True
        )
        many = fa.decode_attention(
            q, k, v, lengths, tile_s=8, interpret=True
        )
        np.testing.assert_allclose(
            np.asarray(one), np.asarray(many), atol=2e-5, rtol=1e-4
        )

    def test_rejects_multi_token_and_unaligned(self):
        import kubeinfer_tpu.inference.flash_attention as fa

        q, k, v = _rand(
            jax.random.PRNGKey(13), 1, 8, 16, 2, 2, 8, jnp.float32
        )
        with pytest.raises(ValueError, match="T == 1"):
            fa.decode_attention(
                q, k, v, jnp.asarray([8], jnp.int32), interpret=True
            )
        q1 = q[:, :1]
        with pytest.raises(ValueError, match="divisible"):
            fa.decode_attention(
                q1, k, v, jnp.asarray([8], jnp.int32), tile_s=12,
                interpret=True,
            )

    def test_auto_falls_back_off_tpu(self):
        # CPU test env: decode_attention_auto must take the dense path
        import kubeinfer_tpu.inference.flash_attention as fa

        q, k, v = self._decode_rand(
            jax.random.PRNGKey(14), 2, 16, 2, 2, 8, jnp.float32
        )
        lengths = jnp.asarray([3, 16], jnp.int32)
        mask = jnp.broadcast_to(
            jnp.arange(16)[None, None, :] < lengths[:, None, None],
            (2, 1, 16),
        )
        np.testing.assert_array_equal(
            np.asarray(fa.decode_attention_auto(q, k, v, lengths, mask)),
            np.asarray(dense_attention(q, k, v, mask)),
        )

    def test_engine_decode_route_token_parity(self, monkeypatch):
        # Route the engine's decode steps through the interpreted kernel
        # (production wiring is TPU-only) and pin generate() token
        # equality against the unpatched dense route — same harness as
        # the prefill flash-branch test above.
        import functools

        import kubeinfer_tpu.inference.engine as eng_mod
        import kubeinfer_tpu.inference.flash_attention as fa
        import kubeinfer_tpu.inference.stepper as stepper
        from kubeinfer_tpu.inference import PRESETS, init_params
        from kubeinfer_tpu.inference.engine import Engine

        params = init_params(PRESETS["tiny"], jax.random.PRNGKey(0))
        prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9, 10, 11], [9]]
        ref = Engine(params, PRESETS["tiny"]).generate(
            prompts, max_new_tokens=6
        )

        kern = functools.partial(
            fa.decode_attention, tile_s=8, interpret=True
        )
        # the decode route resolves its attention in stepper (the one
        # module all three decode paths share), not engine
        monkeypatch.setattr(
            stepper, "decode_attention_auto",
            lambda q, k, v, lengths, mask: kern(q, k, v, lengths),
        )
        eng_mod._generate_jit._clear_cache()
        try:
            got = Engine(params, PRESETS["tiny"]).generate(
                prompts, max_new_tokens=6
            )
        finally:
            eng_mod._generate_jit._clear_cache()  # drop patched traces
        np.testing.assert_array_equal(got.tokens, ref.tokens)
        np.testing.assert_array_equal(got.lengths, ref.lengths)


# the served geometries of the paged kernel, (n_kv, group, head_dim):
# qwen2-7b, and Qwen3-Next's gated full-attention layers
SERVED_HEADS = [(4, 7, 128), (2, 8, 256)]


class TestBlockDecodeKernel:
    """Block-table decode (paged KV) vs its jnp twin: BIT-identical per
    the kernel/twin invariant — both resolve every KV tile through the
    same scalar-prefetched block table and fold with _fold_tile_math.
    Pools are junk-filled outside the scattered logical blocks and the
    tables deliberately non-contiguous, so any read that escapes the
    table (or depends on dead table entries) breaks parity loudly."""

    def _paged(self, key, B, max_blocks, block_size, n_heads, n_kv, D,
               lens, dtype=jnp.float32, extra_blocks=3):
        """Scatter a logical [B, S] KV into a junk-initialised pool at
        permuted (non-contiguous, interleaved-across-rows) block ids.
        Returns the paged operands plus the gathered dense KV."""
        import kubeinfer_tpu.inference.flash_attention as fa

        S = max_blocks * block_size
        q, k, v = _rand(key, B, 1, S, n_heads, n_kv, D, dtype)
        num_blocks = 1 + B * max_blocks + extra_blocks
        jk, jv = jax.random.split(jax.random.fold_in(key, 7))
        kp = jax.random.normal(
            jk, pool_shape(num_blocks, block_size, n_kv, D)
        ).astype(dtype)
        vp = jax.random.normal(
            jv, pool_shape(num_blocks, block_size, n_kv, D)
        ).astype(dtype)
        rng = np.random.default_rng(17)
        perm = rng.permutation(np.arange(1, num_blocks))
        tables = perm[: B * max_blocks].reshape(B, max_blocks)
        tables = np.ascontiguousarray(tables, np.int32)
        kp = kp.at[tables.reshape(-1)].set(rows_to_pages(
            k.reshape(B * max_blocks, block_size, n_kv, D)
        ))
        vp = vp.at[tables.reshape(-1)].set(rows_to_pages(
            v.reshape(B * max_blocks, block_size, n_kv, D)
        ))
        # dead entries (beyond each row's live blocks) point at the
        # null block, as the engine pads them — output must not care
        lens = np.asarray(lens, np.int64)
        for b in range(B):
            live = -(-int(lens[b]) // block_size)
            tables[b, live:] = 0
        tables = jnp.asarray(tables)
        lengths = jnp.asarray(lens, jnp.int32)
        kg = fa.gather_block_kv(kp, tables)
        vg = fa.gather_block_kv(vp, tables)
        return q, kp, vp, tables, lengths, kg, vg

    def _check(self, B, max_blocks, block_size, n_heads, n_kv, D, lens,
               dtype=jnp.float32, dense_atol=2e-5, dense_rtol=1e-4):
        import kubeinfer_tpu.inference.flash_attention as fa

        q, kp, vp, tables, lengths, kg, vg = self._paged(
            jax.random.PRNGKey(21), B, max_blocks, block_size, n_heads,
            n_kv, D, lens, dtype,
        )
        got = fa.decode_attention_blocks(
            q, kp, vp, tables, lengths, interpret=True
        )
        twin = fa.decode_attention_blocks_jnp(q, kp, vp, tables, lengths)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(twin),
            err_msg="block kernel/twin bit-identity",
        )
        S = max_blocks * block_size
        mask = jnp.broadcast_to(
            jnp.arange(S)[None, None, :] < lengths[:, None, None],
            (B, 1, S),
        )
        want = dense_attention(q, kg, vg, mask)
        np.testing.assert_allclose(
            np.asarray(twin, np.float32), np.asarray(want, np.float32),
            atol=dense_atol, rtol=dense_rtol,
        )

    @pytest.mark.slow
    @pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2), (8, 1)])
    def test_gqa_ratios_mixed_lengths(self, n_heads, n_kv):
        # lengths straddle block boundaries: mid-block, exactly one
        # block, full table, single token
        self._check(4, 3, 16, n_heads, n_kv, 16, [17, 16, 48, 1])

    @pytest.mark.slow
    def test_bf16(self):
        self._check(
            3, 3, 16, 8, 2, 16, [5, 48, 33], dtype=jnp.bfloat16,
            dense_atol=3e-2, dense_rtol=1e-1,
        )

    @pytest.mark.parametrize("n_kv,G,D", SERVED_HEADS)
    def test_served_heads_at_page_edges(self, n_kv, G, D):
        # one token, a page less one, a page, a page and one, the whole
        # table: the walk's length on each side of every page edge
        bs, M = 16, 4
        self._check(
            5, M, bs, n_kv * G, n_kv, D, [1, bs - 1, bs, bs + 1, M * bs],
            dtype=jnp.bfloat16, dense_atol=3e-2, dense_rtol=1e-1,
        )

    @pytest.mark.parametrize("T", [1, 3])
    @pytest.mark.parametrize("n_kv,G,D", SERVED_HEADS)
    def test_dead_pages_are_never_read(self, n_kv, G, D, T):
        # every dead table entry names a page of NaN: one read of it
        # would poison the row. The output is finite and is the twin's
        # on the null-padded table (whose dead pages fold exactly 0)
        import kubeinfer_tpu.inference.flash_attention as fa

        bs, M = 16, 4
        lens = [T, bs - 1, bs + 1, 2 * bs + T, M * bs]
        q, kp, vp, tables, lengths, _, _ = TestKQueryBlockDecode()._paged(
            jax.random.PRNGKey(26), len(lens), T, M, bs, n_kv * G, n_kv,
            D, lens, jnp.bfloat16,
        )
        nan = kp.shape[0]
        kp = jnp.concatenate([kp, jnp.full_like(kp[:1], jnp.nan)])
        vp = jnp.concatenate([vp, jnp.full_like(vp[:1], jnp.nan)])
        live = jnp.arange(M)[None, :] < -(-lengths[:, None] // bs)
        poisoned = jnp.where(live, tables, nan)
        got = fa.decode_attention_blocks(
            q, kp, vp, poisoned, lengths, interpret=True
        )
        assert np.isfinite(np.asarray(got, np.float32)).all()
        twin = fa.decode_attention_blocks_jnp(q, kp, vp, tables, lengths)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(twin))

    def test_zero_length_rows(self):
        # retired-slot rows (length 0, table all null) must stay dense
        # over the junk they point at — defined output, never NaN —
        # alongside live rows
        self._check(3, 2, 16, 4, 2, 8, [0, 32, 0])

    def test_twin_matches_linear_twin(self):
        # the block twin over a gathered-contiguous pool must equal the
        # linear decode twin with tile_s == block_size bit-for-bit:
        # same tile sweep, same fold math, only the addressing differs
        import kubeinfer_tpu.inference.flash_attention as fa

        q, kp, vp, tables, lengths, kg, vg = self._paged(
            jax.random.PRNGKey(22), 3, 3, 16, 8, 2, 16, [17, 48, 0]
        )
        twin = fa.decode_attention_blocks_jnp(q, kp, vp, tables, lengths)
        linear = fa.decode_attention_jnp(q, kg, vg, lengths, tile_s=16)
        np.testing.assert_array_equal(
            np.asarray(twin), np.asarray(linear),
            err_msg="block twin vs linear twin bit-identity",
        )

    @pytest.mark.parametrize("n_kv,G,D", [(2, 2, 8), *SERVED_HEADS])
    def test_shared_prefix_blocks(self, n_kv, G, D):
        # radix reuse aliases one physical block into several rows'
        # tables; the kernel only ever reads KV, so aliased tables must
        # behave exactly like their gathered-dense expansion
        import kubeinfer_tpu.inference.flash_attention as fa

        B, bs = 3, 16
        q, _, _ = _rand(
            jax.random.PRNGKey(23), B, 1, 2 * bs, n_kv * G, n_kv, D,
            jnp.float32,
        )
        jk, jv = jax.random.split(jax.random.PRNGKey(24))
        kp = jax.random.normal(jk, pool_shape(6, bs, n_kv, D))
        vp = jax.random.normal(jv, pool_shape(6, bs, n_kv, D))
        tables = jnp.asarray(
            [[5, 2], [5, 4], [5, 1]], jnp.int32  # block 5 shared 3-ways
        )
        lengths = jnp.asarray([32, 20, 16], jnp.int32)
        got = fa.decode_attention_blocks(
            q, kp, vp, tables, lengths, interpret=True
        )
        twin = fa.decode_attention_blocks_jnp(q, kp, vp, tables, lengths)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(twin))
        mask = jnp.broadcast_to(
            jnp.arange(2 * bs)[None, None, :] < lengths[:, None, None],
            (B, 1, 2 * bs),
        )
        want = dense_attention(
            q, fa.gather_block_kv(kp, tables),
            fa.gather_block_kv(vp, tables), mask,
        )
        np.testing.assert_allclose(
            np.asarray(twin), np.asarray(want), atol=2e-5, rtol=1e-4
        )

    def test_auto_falls_back_off_tpu(self):
        # CPU test env: blocks_auto must take the gathered dense path
        import kubeinfer_tpu.inference.flash_attention as fa

        q, kp, vp, tables, lengths, kg, vg = self._paged(
            jax.random.PRNGKey(25), 2, 2, 16, 4, 2, 8, [9, 32]
        )
        mask = jnp.broadcast_to(
            jnp.arange(32)[None, None, :] < lengths[:, None, None],
            (2, 1, 32),
        )
        np.testing.assert_array_equal(
            np.asarray(
                fa.decode_attention_blocks_auto(
                    q, kp, vp, tables, lengths, mask
                )
            ),
            np.asarray(dense_attention(q, kg, vg, mask)),
        )


class TestKQueryBlockDecode:
    """Speculative verify window: the block kernel's T > 1 path vs its
    jnp twin (bit-identical) and the dense reference under the window's
    causal rule (query t admits s <= lengths[b] - T + t). Fixtures keep
    the TestBlockDecodeKernel hostility — junk-filled pools, permuted
    non-contiguous tables, null-padded dead entries — plus the
    verify-specific edges: rows shorter than the window and retired
    rows (length 0, all-null table) riding the same dispatch."""

    def _paged(self, key, B, T, max_blocks, block_size, n_heads, n_kv,
               D, lens, dtype=jnp.float32):
        import kubeinfer_tpu.inference.flash_attention as fa

        S = max_blocks * block_size
        q, k, v = _rand(key, B, T, S, n_heads, n_kv, D, dtype)
        num_blocks = 1 + B * max_blocks + 3
        jk, jv = jax.random.split(jax.random.fold_in(key, 7))
        kp = jax.random.normal(
            jk, pool_shape(num_blocks, block_size, n_kv, D)
        ).astype(dtype)
        vp = jax.random.normal(
            jv, pool_shape(num_blocks, block_size, n_kv, D)
        ).astype(dtype)
        rng = np.random.default_rng(29)
        perm = rng.permutation(np.arange(1, num_blocks))
        tables = perm[: B * max_blocks].reshape(B, max_blocks)
        tables = np.ascontiguousarray(tables, np.int32)
        kp = kp.at[tables.reshape(-1)].set(rows_to_pages(
            k.reshape(B * max_blocks, block_size, n_kv, D)
        ))
        vp = vp.at[tables.reshape(-1)].set(rows_to_pages(
            v.reshape(B * max_blocks, block_size, n_kv, D)
        ))
        lens = np.asarray(lens, np.int64)
        for b in range(B):
            live = -(-int(lens[b]) // block_size)
            tables[b, live:] = 0
        tables = jnp.asarray(tables)
        lengths = jnp.asarray(lens, jnp.int32)
        kg = fa.gather_block_kv(kp, tables)
        vg = fa.gather_block_kv(vp, tables)
        return q, kp, vp, tables, lengths, kg, vg

    def _window_mask(self, lengths, T, S):
        q_pos = lengths[:, None] - T + jnp.arange(T, dtype=jnp.int32)
        return (
            jnp.arange(S, dtype=jnp.int32)[None, None, :]
            <= q_pos[:, :, None]
        )

    def _check(self, B, T, max_blocks, block_size, n_heads, n_kv, D,
               lens, dtype=jnp.float32, dense=True, dense_atol=2e-5,
               dense_rtol=1e-4, seed=31):
        import kubeinfer_tpu.inference.flash_attention as fa

        q, kp, vp, tables, lengths, kg, vg = self._paged(
            jax.random.PRNGKey(seed), B, T, max_blocks, block_size,
            n_heads, n_kv, D, lens, dtype,
        )
        got = fa.decode_attention_blocks(
            q, kp, vp, tables, lengths, interpret=True
        )
        twin = fa.decode_attention_blocks_jnp(q, kp, vp, tables, lengths)
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(twin),
            err_msg="K-query block kernel/twin bit-identity",
        )
        assert np.isfinite(np.asarray(twin, np.float32)).all()
        if dense:
            S = max_blocks * block_size
            want = dense_attention(
                q, kg, vg, self._window_mask(lengths, T, S)
            )
            np.testing.assert_allclose(
                np.asarray(twin, np.float32),
                np.asarray(want, np.float32),
                atol=dense_atol, rtol=dense_rtol,
            )

    def test_window_smoke(self):
        # T=2 window, lengths straddling block boundaries — the
        # un-slow sentinel for the sweep below
        self._check(3, 2, 2, 16, 4, 2, 8, [17, 32, 2])

    @pytest.mark.slow
    @pytest.mark.parametrize("n_heads,n_kv", [(4, 4), (8, 2), (8, 1)])
    @pytest.mark.parametrize("T", [2, 5])
    def test_gqa_ratios(self, n_heads, n_kv, T):
        # window end mid-block, at a block edge, at the table's end,
        # and the minimum live row (offset 0: length == T)
        self._check(4, T, 3, 16, n_heads, n_kv, 16, [17, 32, 48, T])

    @pytest.mark.slow
    def test_bf16(self):
        self._check(
            3, 3, 3, 16, 8, 2, 16, [19, 48, 3], dtype=jnp.bfloat16,
            dense_atol=3e-2, dense_rtol=1e-1,
        )

    def test_short_and_zero_rows(self):
        # rows the engine never produces but the fused dispatch must
        # survive: length 0 (retired slot, all-null table) and
        # 0 < length < T (every query below the window floor fully
        # masked) — twin bit-identity and finite output are the
        # contract; the dense reference has no defined answer for a
        # fully-masked query row, so it sits this one out
        self._check(3, 4, 2, 16, 4, 2, 8, [0, 2, 30], dense=False)

    @pytest.mark.parametrize("n_kv,G,D", SERVED_HEADS)
    def test_served_heads_with_rows_shorter_than_the_window(self, n_kv, G,
                                                            D):
        # rows under the window walk the whole null-padded table (the
        # kernel's dense fallback) beside rows at and past page edges
        self._check(
            6, 3, 4, 16, n_kv * G, n_kv, D, [0, 2, 3, 16, 17, 64],
            dtype=jnp.bfloat16, dense=False,
        )

    def test_reduces_to_single_query(self):
        # the T=1 window through the generalized path must stay
        # bit-identical to the twin on the decode shapes the engine
        # ran before the verify path existed (pen s <= rl - 1 is the
        # old s < rl)
        self._check(3, 1, 2, 16, 4, 2, 8, [9, 32, 0])

    def test_auto_routes_window_to_dense_on_cpu(self):
        # CPU test env: the auto router's gather+dense branch under
        # the window mask must agree with the twin (same live-set
        # contract the T=1 router already keeps)
        import kubeinfer_tpu.inference.flash_attention as fa

        q, kp, vp, tables, lengths, kg, vg = self._paged(
            jax.random.PRNGKey(37), 2, 3, 2, 16, 4, 2, 8, [19, 32]
        )
        mask = self._window_mask(lengths, 3, 32)
        np.testing.assert_allclose(
            np.asarray(
                fa.decode_attention_blocks_auto(
                    q, kp, vp, tables, lengths, mask
                ), np.float32,
            ),
            np.asarray(
                fa.decode_attention_blocks_jnp(
                    q, kp, vp, tables, lengths
                ), np.float32,
            ),
            atol=2e-5, rtol=1e-4,
        )


def _pool_transposes(jaxpr, num_blocks):
    """Every ``transpose`` in ``jaxpr`` (sub-jaxprs included: scan
    bodies, pallas_call kernels, conds) whose operand leads with
    ``num_blocks``: a whole-pool relayout."""
    found = []
    for eqn in jaxpr.eqns:
        shape = getattr(eqn.invars[0].aval, "shape", ()) \
            if eqn.invars else ()
        if eqn.primitive.name == "transpose" and shape[:1] == (
                num_blocks,):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pool_transposes(sub, num_blocks)
    return found


class TestStoredLayout:
    """The pool is stored head-major, the layout the block kernels
    read: nothing transposes a pool in front of a call or inside a
    step, and a token written through the tables is the token the
    dense cache path writes."""

    NB = 37  # a prime no other axis of these shapes has

    def _operands(self, T, quantized):
        B, M, bs, nq, nkv, D = 2, 2, 16, 4, 2, 8
        pages = pool_shape(self.NB, bs, nkv, D)
        tail = (B, *pool_shape(2, bs, nkv, D))
        q = jnp.zeros((B, T, nq, D), jnp.bfloat16)
        tables = jnp.ones((B, M), jnp.int32)
        lens = jnp.full((B,), bs + T, jnp.int32)
        if not quantized:
            pool = jnp.zeros(pages, jnp.bfloat16)
            return q, pool, pool, tables, lens
        pool = jnp.zeros(pages, jnp.int8)
        scales = jnp.ones((self.NB, nkv), jnp.float32)
        tails = jnp.zeros(tail, jnp.bfloat16)
        return q, pool, pool, scales, scales, tails, tails, tables, lens

    @pytest.mark.parametrize("T", [1, 3])
    @pytest.mark.parametrize("fn", [
        "decode_attention_blocks", "decode_attention_blocks_jnp",
        "decode_attention_blocks_q8", "decode_attention_blocks_q8_jnp",
    ])
    def test_no_pool_transpose_in_front_of_the_kernel(self, fn, T):
        import kubeinfer_tpu.inference.flash_attention as fa

        ops = self._operands(T, quantized="q8" in fn)
        jaxpr = jax.make_jaxpr(getattr(fa, fn))(*ops).jaxpr
        assert _pool_transposes(jaxpr, self.NB) == []
        # the check can see one: the parent's spelling of the read
        seen = jax.make_jaxpr(lambda p: p.transpose(0, 2, 1, 3))(ops[1])
        assert len(_pool_transposes(seen.jaxpr, self.NB)) == 1

    @pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
    @pytest.mark.parametrize("route", ["kernel", "dense"])
    def test_no_pool_transpose_in_a_decode_window(self, route, kv_dtype,
                                                  monkeypatch):
        import kubeinfer_tpu.inference.flash_attention as fa
        from kubeinfer_tpu.inference import PRESETS, init_params
        from kubeinfer_tpu.inference.stepper import (
            decode_window,
            init_slot_state,
        )

        # trace the step both ways a server takes it: the Pallas
        # branch (TPU) and the gather + dense branch (CPU, GSPMD)
        monkeypatch.setattr(fa, "decode_blocks_available",
                            lambda bs, D: route == "kernel")
        cfg = PRESETS["tiny"]
        params = init_params(cfg, jax.random.PRNGKey(0))
        state = init_slot_state(cfg, 2, 64, jnp.float32, self.NB, 16,
                                kv_dtype=kv_dtype)
        assert state.caches_k[0].shape[0] == self.NB
        jaxpr = jax.make_jaxpr(
            lambda p, s: decode_window.__wrapped__(p, s, cfg, 1)
        )(params, state).jaxpr
        assert _pool_transposes(jaxpr, self.NB) == []

    @pytest.mark.parametrize("T", [1, 3])
    def test_paged_write_reads_back_as_the_dense_write(self, T):
        """T tokens a row written through the tables into head-major
        pages, then read back through the tables, are the same tokens
        ``forward`` writes into a dense [B, S, n_kv, D] cache at the
        same positions, and attention over either gives the same
        logits (T = 1 the decode step, T = 3 the verify window)."""
        import kubeinfer_tpu.inference.flash_attention as fa
        from kubeinfer_tpu.inference import PRESETS, init_params
        from kubeinfer_tpu.inference.model import forward

        cfg = PRESETS["tiny"]
        params = init_params(cfg, jax.random.PRNGKey(1))
        B, M, bs = 3, 4, 16
        S = M * bs
        nkv, D = cfg.num_key_value_heads, cfg.head_dim
        L = cfg.num_hidden_layers
        keys = iter(jax.random.split(jax.random.PRNGKey(2), 4 * L + 1))
        rng = np.random.default_rng(5)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, 1 + B * M)).reshape(B, M),
            jnp.int32)
        nb = 1 + B * M + 2
        dense, paged = [], []
        for _ in range(L):
            pair_d, pair_p = [], []
            for _ in range(2):
                rows = jax.random.normal(next(keys), (B, S, nkv, D))
                pool = jax.random.normal(
                    next(keys), pool_shape(nb, bs, nkv, D))
                pool = pool.at[tables.reshape(-1)].set(rows_to_pages(
                    rows.reshape(B * M, bs, nkv, D)))
                pair_d.append(rows)
                pair_p.append(pool)
            dense.append(tuple(pair_d))
            paged.append(tuple(pair_p))
        # offsets that cross a block edge inside the window
        offset = jnp.asarray([bs - 1, 2 * bs - 2, 5], jnp.int32)
        toks = jax.random.randint(next(keys), (B, T), 0, cfg.vocab_size)
        pos = offset[:, None] + jnp.arange(T)[None, :]
        mask = jnp.arange(S)[None, None, :] <= pos[:, :, None]

        def attn(q, kp, vp, m):
            return fa.decode_attention_blocks_auto(
                q, kp, vp, tables, offset + T, m)

        kw = dict(positions=pos, attn_mask=mask, cache_offset=offset)
        want, dense = forward(params, toks, cfg, kv_caches=dense, **kw)
        got, paged = forward(params, toks, cfg, kv_caches=paged,
                             block_tables=tables, attn_fn=attn, **kw)
        for (dk, dv), (pk, pv) in zip(dense, paged):
            np.testing.assert_array_equal(
                np.asarray(fa.gather_block_kv(pk, tables)), np.asarray(dk))
            np.testing.assert_array_equal(
                np.asarray(fa.gather_block_kv(pv, tables)), np.asarray(dv))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
