"""utils/compile_cache.watch_compiles: JAX's own compile events as
process totals, the numbers behind the server's start-up counters."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from kubeinfer_tpu.observability import tracing
from kubeinfer_tpu.utils import compile_cache


def _fresh():
    """A jitted function no cache has seen: its constant is drawn anew."""
    salt = float(int.from_bytes(os.urandom(6), "little"))

    @jax.jit
    def f(x):
        return x * 3.0 + salt

    return f


def _delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


@pytest.fixture
def watch():
    return compile_cache.watch_compiles()


@pytest.fixture
def nothing_kept():
    """The persistent cache writes nothing: every compile is unkept."""
    prev = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    try:
        yield
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev)


@pytest.fixture
def private_cache(tmp_path):
    """A persistent cache of this test's own that keeps every program."""
    from jax.experimental.compilation_cache import compilation_cache

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_min = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compilation_cache.reset_cache()
    try:
        yield tmp_path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          prev_min)
        compilation_cache.reset_cache()


def test_first_call_counts_every_stage_and_a_second_call_nothing(
        watch, nothing_kept):
    f, x = _fresh(), jnp.ones((8,), jnp.float32)
    t0 = watch.totals()
    f(x).block_until_ready()
    t1 = watch.totals()
    d = _delta(t0, t1)
    assert d["trace_s"] > 0 and d["lower_s"] > 0 and d["backend_s"] > 0
    assert (d["unkept"], d["kept"], d["fetched"]) == (1, 0, 0)
    f(x).block_until_ready()
    assert watch.totals() == t1


def test_a_program_from_the_persistent_cache_counts_as_fetched(
        watch, private_cache):
    f, x = _fresh(), jnp.ones((8,), jnp.float32)
    t0 = watch.totals()
    f(x).block_until_ready()
    t1 = watch.totals()
    assert (t1["kept"] - t0["kept"], t1["fetched"] - t0["fetched"]) == (1, 0)
    jax.clear_caches()
    f(x).block_until_ready()
    d = _delta(t1, watch.totals())
    assert (d["fetched"], d["kept"], d["unkept"]) == (1, 0, 0)
    assert d["fetch_s"] > 0
    assert d["trace_s"] > 0 and d["backend_s"] >= d["fetch_s"]


def test_nested_jits_count_their_seconds_once(watch, nothing_kept):
    inner = _fresh()

    @jax.jit
    def outer(x):
        return inner(x) * inner(x + 1.0)

    x = jnp.ones((8,), jnp.float32)
    t0 = watch.totals()
    start = tracing.now()
    outer(x).block_until_ready()
    wall = tracing.now() - start
    d = _delta(t0, watch.totals())
    # the inner trace runs inside the outer one and is not added again
    assert 0 < d["trace_s"] + d["lower_s"] + d["backend_s"] <= wall
    assert d["unkept"] == 1  # one program: the inner jit is inlined


def test_each_backend_compile_is_a_span_naming_its_function(
        watch, nothing_kept):
    f = _fresh()
    f(jnp.ones((3,), jnp.float32)).block_until_ready()
    mine = [s for s in tracing.RECORDER.snapshot()
            if s.name == "compile.backend"
            and s.attrs["fun_name"] == "jit(f)"]
    assert mine, "no compile.backend span for jit(f)"
    span = mine[-1]
    assert span.attrs["fetched"] is False
    assert span.end >= span.start
    # every compile span of the process shares one trace
    assert len({s.trace_id for s in tracing.RECORDER.snapshot()
                if s.name == "compile.backend"}) == 1


def test_watch_compiles_registers_its_listeners_once(monkeypatch):
    import jax.monitoring as mon

    calls = []
    for name in ("register_scalar_listener",
                 "register_event_duration_secs_listener",
                 "register_event_listener"):
        monkeypatch.setattr(mon, name,
                            lambda cb, name=name: calls.append(name))
    monkeypatch.setattr(compile_cache, "_WATCH", None)
    first = compile_cache.watch_compiles()
    assert compile_cache.watch_compiles() is first
    assert compile_cache.compile_watch() is first
    assert sorted(calls) == ["register_event_duration_secs_listener",
                             "register_event_listener",
                             "register_scalar_listener"]
