"""Speculative verify windows on the paged continuous batch: the
contracts that let the batcher run a draft model ahead of the target
without anyone being able to tell.

- **Token identity for ANY draft.** The acceptance rule
  (stepper.spec_accept) only ever emits the target's own samples — the
  draft gates how MANY land per window, never WHICH — so greedy and
  sampled streams must be bit-identical to the plain engine's for a
  self-draft (acceptance ~1.0) and an unrelated random draft
  (acceptance ~chance, every window rolling back) alike.

- **Rollback never leaks.** Boundary truncation coincides with
  retirement, parked rows drop their draft state and re-arm on warm
  readmit, and partially-accepted windows never reach the radix trie —
  so identity holds across warm admits and preemption cycles too.

- **Shape discipline.** One compiled verify shape per (spec_k, layout):
  every decode-phase advance routes through the fused verify dispatch
  (phase "verify", bucket == spec_k) and repeating a seen workload
  registers zero fresh first-seen shapes.

- **The request surface.** The window is the only speculator
  (``--draft-model``), so what the dense speculators' tests held moves
  here: every depth x draft, EOS at each place in a window, budgets
  below the depth, sampling knobs and seeds, repetition penalty, and a
  mixed burst in one window — each against the plain engine.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from kubeinfer_tpu.inference import PRESETS, init_params
from kubeinfer_tpu.inference.batching import (
    ContinuousEngine,
    PreemptionPolicy,
)
from kubeinfer_tpu.inference.sharding import EngineLayout

TINY = PRESETS["tiny"]
DRAFT_CFG = dataclasses.replace(TINY, num_hidden_layers=1)

AGGRESSIVE = PreemptionPolicy(
    threshold_s=0.0005, objective=0.5, burn_limit=0.5,
    cooldown_steps=1, min_progress=1,
)

SAMPLED = dict(temperature=0.8, seed=5, top_k=13)


@pytest.fixture(scope="module")
def params():
    return init_params(TINY, jax.random.PRNGKey(6))


@pytest.fixture(scope="module")
def draft():
    # unrelated 1-layer draft: same vocabulary, useless guesses —
    # the adversarial end of the acceptance spectrum
    return (init_params(DRAFT_CFG, jax.random.PRNGKey(7)), DRAFT_CFG)


def _engine(params, cfg=TINY, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("block_size", 8)
    return ContinuousEngine(params, cfg, **kw).start()


def _bigram(params):
    """0-layer draft (embed/norm/lm_head only): the prompt-lookup /
    n-gram end of the draft spectrum, with no draft KV at all."""
    dcfg = dataclasses.replace(TINY, num_hidden_layers=0)
    return ({
        "embed_tokens": params["embed_tokens"],
        "layers": [],
        "norm": params["norm"],
        "lm_head": params["lm_head"],
    }, dcfg)


def _run(params, requests, **engine_kw):
    """Each request dict through one engine, one at a time; returns
    (token lists, scheduler_stats)."""
    eng = _engine(params, **engine_kw)
    try:
        out = [eng.generate(**r) for r in requests]
        return out, eng.scheduler_stats()
    finally:
        eng.stop()


# the greedy stream of this prompt under ``params`` has twelve distinct
# tokens, so an EOS chosen at index i stops the request at length i + 1
DISTINCT_PROMPT = [38, 212, 167, 92]


class TestVerifyIdentity:
    def test_cold_identity_random_draft(self, params, draft):
        """The fast tier-1 pin: an unrelated draft (near-zero
        acceptance, rollbacks every window) must still emit the plain
        engine's exact streams, greedy AND sampled."""
        rng = np.random.default_rng(41)
        prompt = rng.integers(0, TINY.vocab_size, 9).tolist()
        ref = _engine(params, max_window=1)
        try:
            want_g = ref.generate(prompt, max_new_tokens=9)
            want_s = ref.generate(prompt, max_new_tokens=9, **SAMPLED)
        finally:
            ref.stop()
        eng = _engine(params, spec_draft=draft, spec_k=4)
        try:
            got_g = eng.generate(prompt, max_new_tokens=9)
            got_s = eng.generate(prompt, max_new_tokens=9, **SAMPLED)
            stats = eng.scheduler_stats()
        finally:
            eng.stop()
        assert got_g == want_g
        assert got_s == want_s
        # the verify path actually ran, and the useless draft actually
        # rolled back — identity above wasn't a fallback to plain decode
        assert stats["spec_draft_tokens"] > 0
        assert stats["spec_rollbacks"] > 0
        assert (
            stats["spec_accepted_tokens"] <= stats["spec_draft_tokens"]
        )

    def test_self_draft_full_acceptance(self, params):
        """Draft == target: every greedy draft token matches the draw
        it guesses, so acceptance is total and no window rolls back —
        the throughput end of the spectrum, same identity."""
        rng = np.random.default_rng(42)
        prompt = rng.integers(0, TINY.vocab_size, 7).tolist()
        ref = _engine(params, max_window=1)
        try:
            want = ref.generate(prompt, max_new_tokens=8)
        finally:
            ref.stop()
        eng = _engine(params, spec_draft=(params, TINY), spec_k=4)
        try:
            got = eng.generate(prompt, max_new_tokens=8)
            stats = eng.scheduler_stats()
        finally:
            eng.stop()
        assert got == want
        assert stats["spec_draft_tokens"] > 0
        assert (
            stats["spec_accepted_tokens"] == stats["spec_draft_tokens"]
        )
        assert stats["spec_rollbacks"] == 0

    def test_bigram_draft_identity(self, params):
        """0-layer draft (embed/norm/lm_head only — the prompt-lookup /
        n-gram end of the draft spectrum, and what the bench pair
        uses): no draft KV exists, so the repair forward and propose
        scan run cache-free, and admit installs only ``prev``. Identity
        must hold like any other draft."""
        rng = np.random.default_rng(47)
        prompt = rng.integers(0, TINY.vocab_size, 8).tolist()
        ref = _engine(params, max_window=1)
        try:
            want_g = ref.generate(prompt, max_new_tokens=8)
            want_s = ref.generate(prompt, max_new_tokens=8, **SAMPLED)
        finally:
            ref.stop()
        eng = _engine(params, spec_draft=_bigram(params), spec_k=4)
        try:
            got_g = eng.generate(prompt, max_new_tokens=8)
            got_s = eng.generate(prompt, max_new_tokens=8, **SAMPLED)
            stats = eng.scheduler_stats()
        finally:
            eng.stop()
        assert got_g == want_g
        assert got_s == want_s
        assert stats["spec_draft_tokens"] > 0

    def test_warm_admit_identity(self, params, draft):
        """Radix reuse under speculation: the second admit of a prompt
        prefills from cached blocks, and the draft side re-prefills its
        dense cache over the FULL prompt — streams stay identical and
        the rollback rule (toks[:-1] at retire) kept partially-accepted
        tails out of the trie."""
        rng = np.random.default_rng(43)
        prompt = rng.integers(0, TINY.vocab_size, 9).tolist()
        ref = _engine(params, max_window=1)
        try:
            want_g = ref.generate(prompt, max_new_tokens=8)
            want_s = ref.generate(prompt, max_new_tokens=8, **SAMPLED)
        finally:
            ref.stop()
        eng = _engine(params, spec_draft=draft, spec_k=4)
        try:
            assert eng.generate(prompt, max_new_tokens=8) == want_g
            hits0 = eng.kv_cache_stats()["hits"]
            got_g = eng.generate(prompt, max_new_tokens=8)
            got_s = eng.generate(prompt, max_new_tokens=8, **SAMPLED)
            warm_hits = eng.kv_cache_stats()["hits"] - hits0
        finally:
            eng.stop()
        assert got_g == want_g
        assert got_s == want_s
        assert warm_hits >= 1, "second admit never reused the trie"

    @pytest.mark.slow
    def test_identity_across_preemption_cycles(self, params, draft):
        """Park/resume cycles against verify windows: parks drop the
        row's draft state and spec slack, readmits re-arm both — every
        request still emits the uncontended plain-engine stream."""
        rng = np.random.default_rng(44)
        prompts = [
            rng.integers(0, TINY.vocab_size, 5).tolist()
            for _ in range(12)
        ]
        kw = lambda i: dict(  # noqa: E731 - tiny per-index sampler knobs
            temperature=0.8 if i % 2 else 0.0,
            seed=70 + i, top_k=9 if i % 2 else 0,
        )
        ref = _engine(params, max_window=1)
        try:
            want = [ref.generate(p, max_new_tokens=8, **kw(i))
                    for i, p in enumerate(prompts)]
        finally:
            ref.stop()
        eng = _engine(params, spec_draft=draft, spec_k=4,
                      preemption=AGGRESSIVE)
        try:
            reqs = [eng.submit(p, max_new_tokens=8, **kw(i))
                    for i, p in enumerate(prompts)]
            for i, r in enumerate(reqs):
                assert r.done.wait(300), f"request {i} starved"
                assert not r.failed
            preempted = eng.preempted_total
            stats = eng.scheduler_stats()
        finally:
            eng.stop()
        assert preempted >= 1, "policy never parked anything"
        assert stats["spec_draft_tokens"] > 0
        for i, r in enumerate(reqs):
            assert r.out_tokens == want[i], f"request {i}"

    @pytest.mark.slow
    def test_tp2_identity(self, params, draft):
        """Sharded verify: the draft replicates onto the mesh and the
        fused verify partitions over tp — streams match the unsharded
        plain engine, and the verify shape set stays one bucket."""
        rng = np.random.default_rng(45)
        prompt = rng.integers(0, TINY.vocab_size, 7).tolist()
        ref = _engine(params, max_window=1)
        try:
            want_g = ref.generate(prompt, max_new_tokens=8)
            want_s = ref.generate(prompt, max_new_tokens=8, **SAMPLED)
        finally:
            ref.stop()
        eng = _engine(params, spec_draft=draft, spec_k=4,
                      layout=EngineLayout.build(2))
        try:
            got_g = eng.generate(prompt, max_new_tokens=8)
            got_s = eng.generate(prompt, max_new_tokens=8, **SAMPLED)
            stats = eng.scheduler_stats()
            buckets = {r.bucket for r in eng.profiler.snapshot()
                       if r.phase == "verify"}
        finally:
            eng.stop()
        assert got_g == want_g
        assert got_s == want_s
        assert stats["spec_draft_tokens"] > 0
        assert buckets == {4}


class TestVerifyShapes:
    def test_one_compiled_shape_per_k(self, params, draft):
        """Every decode-phase advance routes through the verify
        dispatch (no plain decode records at all), the bucket is
        spec_k, and a repeated workload registers zero fresh
        first-seen shapes."""
        rng = np.random.default_rng(46)
        prompt = rng.integers(0, TINY.vocab_size, 9).tolist()
        eng = _engine(params, spec_draft=draft, spec_k=4)
        try:
            eng.generate(prompt, max_new_tokens=9)
            recs = eng.profiler.snapshot()
            assert {r.bucket for r in recs if r.phase == "verify"} == {4}
            assert not [r for r in recs if r.phase == "decode"]
            c0 = eng.profiler.compile_count
            eng.generate(prompt, max_new_tokens=9)
            assert eng.profiler.compile_count == c0
        finally:
            eng.stop()
        eng2 = _engine(params, spec_draft=draft, spec_k=2)
        try:
            eng2.generate(prompt, max_new_tokens=9)
            buckets = {r.bucket for r in eng2.profiler.snapshot()
                       if r.phase == "verify"}
        finally:
            eng2.stop()
        assert buckets == {2}

    @pytest.mark.parametrize("vocab,spec_k,cache_len,match", [
        (128, 4, 64, "vocabulary"),
        (TINY.vocab_size, 0, 64, "spec_k must be >= 1"),
        (TINY.vocab_size, 8, 8, "leaves no room"),
    ], ids=["vocab-mismatch", "depth-below-one", "depth-over-cache"])
    def test_constructor_validation(self, params, draft, vocab, spec_k,
                                    cache_len, match):
        dparams, dcfg = draft
        dcfg = dataclasses.replace(dcfg, vocab_size=vocab)
        with pytest.raises(ValueError, match=match):
            ContinuousEngine(params, TINY, n_slots=2, cache_len=cache_len,
                             block_size=8, spec_draft=(dparams, dcfg),
                             spec_k=spec_k)


class TestVerifyRequestSurface:
    """What a request can ask of a ``--draft-model`` server, each held
    to the plain engine's stream (the contract the dense speculators'
    tests held before the window became the only speculator)."""

    @pytest.fixture(scope="class")
    def plain(self, params):
        """Memoised plain-engine streams: one engine for the class."""
        eng = _engine(params, max_window=1)
        seen: dict[str, list[int]] = {}

        def want(**req):
            key = repr(sorted(req.items()))
            if key not in seen:
                seen[key] = eng.generate(**req)
            return seen[key]

        yield want
        eng.stop()

    @pytest.mark.parametrize("spec_k", [1, 2, 3, 4])
    @pytest.mark.parametrize("kind", ["self", "random", "bigram"])
    def test_greedy_identity_any_depth_any_draft(self, params, draft,
                                                  plain, kind, spec_k):
        spec_draft = {"self": (params, TINY), "random": draft,
                      "bigram": _bigram(params)}[kind]
        req = dict(prompt=[5, 4, 3, 2], max_new_tokens=10)
        (got,), stats = _run(params, [req], spec_draft=spec_draft,
                             spec_k=spec_k)
        assert got == plain(**req)
        assert stats["spec_draft_tokens"] > 0
        if kind == "self":
            assert stats["spec_rollbacks"] == 0

    @pytest.mark.parametrize("at", [0, 3, 5],
                             ids=["first-token", "mid-window",
                                  "window-last"])
    def test_eos_position_in_window(self, params, plain, at):
        """Self-draft, spec_k=4: the admit emits token 0 and the first
        window tokens 1..5, so index 3 is mid-window and index 5 the
        window's last position."""
        stream = plain(prompt=DISTINCT_PROMPT, max_new_tokens=12)
        req = dict(prompt=DISTINCT_PROMPT, max_new_tokens=12,
                   eos_id=stream[at])
        want = plain(**req)
        assert want == stream[:at + 1]
        (got,), _ = _run(params, [req], spec_draft=(params, TINY),
                         spec_k=4)
        assert got == want

    @pytest.mark.parametrize("max_new", [1, 2, 3])
    def test_budget_below_depth(self, params, draft, plain, max_new):
        req = dict(prompt=[1, 2], max_new_tokens=max_new)
        (got,), _ = _run(params, [req], spec_draft=draft, spec_k=4)
        assert got == plain(**req)
        assert len(got) == max_new

    @pytest.mark.parametrize("knobs", [
        dict(temperature=0.8),
        dict(temperature=1.2, top_k=7),
        dict(temperature=0.6, top_k=40, top_p=0.85),
    ], ids=["temperature", "top-k", "top-k-top-p"])
    def test_sampled_identity_and_seed_determinism(self, params, draft,
                                                   plain, knobs):
        req = dict(prompt=[5, 6, 7], max_new_tokens=9, seed=7, **knobs)
        (a, b, other), stats = _run(
            params, [req, req, {**req, "seed": 8}],
            spec_draft=draft, spec_k=3,
        )
        assert a == b == plain(**req)
        assert other == plain(**{**req, "seed": 8})
        assert stats["spec_draft_tokens"] > 0

    def test_sampled_self_draft_is_accepted(self, params, plain):
        """The draft proposes under the key and counter of the target
        draw it guesses, so a draft with the target's distribution is
        accepted when sampling too: correlated noise moves the
        acceptance rate, never the stream."""
        req = dict(prompt=[5, 6, 7], max_new_tokens=12, **SAMPLED)
        (got,), stats = _run(params, [req], spec_draft=(params, TINY),
                             spec_k=4)
        assert got == plain(**req)
        assert stats["spec_accepted_tokens"] > 0

    def test_request_without_room_for_the_window_decodes_plain(
            self, params, draft, plain):
        """prompt + max_new fits the slot but not the +spec_k slack:
        the request is served by plain decode, not refused."""
        req = dict(prompt=list(range(1, 51)), max_new_tokens=12)
        eng = _engine(params, spec_draft=draft, spec_k=4)
        try:
            got = eng.generate(**req)
            phases = {r.phase for r in eng.profiler.snapshot()}
            stats = eng.scheduler_stats()
        finally:
            eng.stop()
        assert got == plain(**req)
        assert "verify" not in phases and "decode" in phases
        assert stats["spec_draft_tokens"] == 0

    def test_repetition_penalty_speculates(self, params, plain):
        # unpenalised, this prompt's greedy stream cycles 39, 201: the
        # penalty must bite inside the window, not only between windows
        req = dict(prompt=[207, 21, 45, 60], max_new_tokens=10,
                   repetition_penalty=1.3)
        (got,), stats = _run(params, [req], spec_draft=(params, TINY),
                             spec_k=4)
        assert got == plain(**req)
        assert got != plain(prompt=req["prompt"], max_new_tokens=10)
        assert stats["spec_draft_tokens"] > 0
        assert stats["spec_accepted_tokens"] > 0

    def test_mixed_burst_shares_one_window(self, params, draft, plain):
        """Greedy, sampled and penalised rows queued together decode
        in the same verify windows, each as if alone."""
        reqs = [
            dict(prompt=[5, 6, 7], max_new_tokens=9),
            dict(prompt=[2, 3], max_new_tokens=7, temperature=0.9,
                 seed=3, top_k=11),
            dict(prompt=[9, 1, 4, 8], max_new_tokens=8,
                 repetition_penalty=1.3),
        ]
        eng = ContinuousEngine(params, TINY, n_slots=4, cache_len=64,
                               block_size=8, spec_draft=draft, spec_k=4)
        subs = [eng.submit(**r) for r in reqs]
        eng.start()
        try:
            for r in subs:
                assert r.done.wait(120)
                assert not r.failed
            rows = {r.live_rows for r in eng.profiler.snapshot()
                    if r.phase == "verify"}
        finally:
            eng.stop()
        assert 3 in rows, rows
        for sub, req in zip(subs, reqs):
            assert sub.out_tokens == plain(**req), req
