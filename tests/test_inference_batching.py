"""Continuous batching correctness: slot-shared decode must equal the
per-request engine exactly (greedy), under concurrent ragged arrivals."""

from __future__ import annotations

import threading

import jax
import pytest

from kubeinfer_tpu.inference import PRESETS, init_params
from kubeinfer_tpu.inference.batching import ContinuousEngine
from kubeinfer_tpu.inference.engine import Engine

TINY = PRESETS["tiny"]


@pytest.fixture(scope="module")
def engines():
    params = init_params(TINY, jax.random.PRNGKey(6))
    cont = ContinuousEngine(params, TINY, n_slots=4, cache_len=64).start()
    ref = Engine(params, TINY, max_cache_len=64)
    yield cont, ref
    cont.stop()


def ref_tokens(ref: Engine, prompt, max_new, eos_id=-1):
    out = ref.generate([prompt], max_new_tokens=max_new, eos_id=eos_id)
    return out.tokens[0, : out.lengths[0]].tolist()


class TestContinuousBatching:
    def test_single_request_matches_engine(self, engines):
        cont, ref = engines
        prompt = [3, 14, 15, 9, 2]
        assert cont.generate(prompt, 6) == ref_tokens(ref, prompt, 6)

    def test_concurrent_ragged_requests_all_exact(self, engines):
        cont, ref = engines
        prompts = [
            ([1, 2, 3], 5),
            ([7, 7, 7, 7, 7, 7, 7], 4),
            ([42], 6),
            ([9, 8, 7, 6], 3),
            ([5, 4, 3, 2, 1, 0], 5),
            ([11, 13], 7),
        ]
        results: dict[int, list[int]] = {}

        def run(i, p, n):
            results[i] = cont.generate(p, n)

        threads = [
            threading.Thread(target=run, args=(i, p, n))
            for i, (p, n) in enumerate(prompts)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        for i, (p, n) in enumerate(prompts):
            assert results[i] == ref_tokens(ref, p, n), f"request {i}"

    def test_more_requests_than_slots(self, engines):
        cont, ref = engines
        # 10 requests through 4 slots: retirement must free slots for
        # the queued tail
        reqs = [cont.submit([i + 1, i + 2, i + 3], max_new_tokens=4)
                for i in range(10)]
        for i, r in enumerate(reqs):
            assert r.done.wait(300), f"request {i} never finished"
            assert r.out_tokens == ref_tokens(ref, [i + 1, i + 2, i + 3], 4), i

    def test_eos_retires_slot_early(self, engines):
        cont, ref = engines
        prompt = [5, 17, 42]
        free = ref_tokens(ref, prompt, 8)
        eos = free[1]  # stop at the 2nd token
        got = cont.generate(prompt, 8, eos_id=eos)
        assert got == free[:2]

    def test_per_slot_sampling(self, engines):
        cont, _ = engines
        prompt = [8, 6, 4, 2]
        # same seed -> deterministic; different seeds -> diverge
        a = cont.generate(prompt, 12, temperature=3.0, seed=7)
        b = cont.generate(prompt, 12, temperature=3.0, seed=7)
        c = cont.generate(prompt, 12, temperature=3.0, seed=8)
        assert a == b
        assert a != c
        # sampled and greedy requests coexist in the same batch
        import threading as th

        results, errors = {}, {}

        def run(tag, **kw):
            try:
                results[tag] = cont.generate(prompt, 6, **kw)
            except Exception as e:  # surfaced below, not swallowed
                errors[tag] = e

        t1 = th.Thread(target=run, args=("g",))
        t2 = th.Thread(target=run, args=("s",),
                       kwargs=dict(temperature=3.0, seed=1))
        t1.start(); t2.start(); t1.join(300); t2.join(300)
        assert not errors, errors
        assert len(results["g"]) == 6 and len(results["s"]) == 6

    def test_capacity_rejection(self, engines):
        cont, _ = engines
        with pytest.raises(ValueError, match="slot capacity"):
            cont.submit(list(range(1, 60)), max_new_tokens=30)


class TestMixedLengthSingleDispatch:
    """The ragged-decode contract (r6 tentpole): Engine.generate solves
    a length-ragged batch in ONE jit invocation — per-row cache offsets
    replaced the per-length micro-batching — and every row stays
    token-identical to its solo generation at temperature 0."""

    def test_one_dispatch_token_exact(self, monkeypatch):
        import kubeinfer_tpu.inference.engine as eng_mod

        params = init_params(TINY, jax.random.PRNGKey(6))
        ref = Engine(params, TINY, max_cache_len=64)
        prompts = [
            [1, 2, 3],
            [7, 7, 7, 7, 7, 7, 7],
            [42],
            [9, 8, 7, 6, 5],
        ]
        solo = [ref_tokens(ref, p, 6) for p in prompts]

        calls: list[tuple] = []
        inner = eng_mod._generate_jit

        def counting(params_, prompt, *args, **kw):
            calls.append(tuple(prompt.shape))
            return inner(params_, prompt, *args, **kw)

        monkeypatch.setattr(eng_mod, "_generate_jit", counting)
        out = Engine(params, TINY, max_cache_len=64).generate(
            prompts, max_new_tokens=6
        )
        # 4 distinct prompt lengths, ONE dispatch carrying all rows in
        # the shared 16-wide prompt bucket (the grouped engine made 4
        # calls here)
        assert calls == [(len(prompts), 16)], calls
        for i, s in enumerate(solo):
            assert out.tokens[i, : out.lengths[i]].tolist() == s, i


class TestSpecTelemetry:
    def test_spec_counters_accumulate(self):
        """The verify path's monotonic counters: proposed and accepted
        draft tokens grow with traffic (a self-draft accepts all)."""
        cfg = PRESETS["tiny"]
        params = init_params(cfg, jax.random.PRNGKey(0))
        eng = ContinuousEngine(
            params, cfg, n_slots=2, cache_len=256,
            spec_draft=(params, cfg), spec_k=2,
        ).start()
        try:
            eng.generate([5, 6, 7], max_new_tokens=8)
            first = eng.scheduler_stats()
            eng.generate([5, 6, 7], max_new_tokens=8)
            second = eng.scheduler_stats()
        finally:
            eng.stop()
        assert first["spec_draft_tokens"] > 0
        assert first["spec_accepted_tokens"] == first["spec_draft_tokens"]
        assert second["spec_draft_tokens"] > first["spec_draft_tokens"]
        assert second["spec_rollbacks"] == 0
