"""What the serving loop writes into the device profile, and the
counters beside it (docs/OBSERVABILITY.md, "Device profile").

Three things are pinned here, on the CPU at the tiny preset:

- the three step programs keep the module names
  ``stepprof.STEP_PROGRAMS`` lists (the Pallas kernel names exist only
  on the TPU route: tests/test_chip_compile.py holds them), and
  ``annotate`` builds nothing while no profiler session records;
- under a profiler session the scheduler thread leaves its ``engine.*``
  spans on one line of the host plane, nested and with their arguments;
- the counters of steps, prompt tokens and the two halves of queue
  wait add up exactly, under a SimulatedClock and with the scheduler
  driven pass by pass from the test.
"""

from __future__ import annotations

import glob
import json
import time
import urllib.request

import jax
import numpy as np
import pytest

from kubeinfer_tpu.inference import PRESETS, init_params
from kubeinfer_tpu.inference.batching import (
    ContinuousEngine, _admit_slot, _prefill_chunk,
)
from kubeinfer_tpu.inference.stepper import decode_window
from kubeinfer_tpu.observability import stepprof, tracing
from kubeinfer_tpu.utils.clock import SimulatedClock

TINY = PRESETS["tiny"]


@pytest.fixture(scope="module")
def params():
    return init_params(TINY, jax.random.PRNGKey(0))


# --- names in the compiled programs ------------------------------------------


@pytest.fixture(scope="module")
def program_text(params):
    """The lowered text of the three step programs."""
    eng = ContinuousEngine(params, TINY, n_slots=2, cache_len=128,
                           block_size=8, prefill_chunk_blocks=2)
    state = eng._state
    i32, f32 = jax.numpy.int32, jax.numpy.float32
    table = np.zeros(state.tables.shape[1], np.int32)
    own = np.zeros(state.tables.shape[1], bool)
    admit = _admit_slot.lower(
        eng.params, state, np.zeros((1, 16), np.int32), i32(9), i32(16),
        i32(25), TINY, i32(0), table, own, f32(0.0), i32(0), f32(1.0),
        f32(1.0), np.zeros(2, np.uint32),
        np.zeros((1, TINY.vocab_size), bool), wq_gspmd=False,
    )
    chunk = _prefill_chunk.lower(
        eng.params, state, np.zeros((1, 16), np.int32), i32(0), TINY,
        table, own, wq_gspmd=False,
    )
    window = decode_window.lower(eng.params, state, TINY, 2,
                                 sharded=False)
    return {"jit__admit_slot": admit.as_text(),
            "jit__prefill_chunk": chunk.as_text(),
            "jit_decode_window": window.as_text()}


def test_names_tuple_is_the_union_of_its_parts():
    assert stepprof.PROFILE_NAMES == (
        stepprof.STEP_PROGRAMS + stepprof.KERNEL_NAMES
        + stepprof.HOST_SPANS)
    assert len(set(stepprof.PROFILE_NAMES)) == len(stepprof.PROFILE_NAMES)
    assert set(stepprof.PHASES) >= {"decode", "verify", "chunk"}


def test_annotate_builds_nothing_without_a_session():
    assert not stepprof.profiling()
    span = stepprof.annotate("engine.pass", decode_rows=1)
    assert span is stepprof.annotate("engine.admit")
    with span as inside:
        inside.set_metadata(queue_depth=0)


@pytest.mark.parametrize("program", stepprof.STEP_PROGRAMS)
def test_step_program_keeps_its_module_name(program, program_text):
    assert f"module @{program} " in program_text[program]


# --- host spans under a profiler session -------------------------------------


def _host_lines(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for n, line in enumerate(plane.lines):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                    dict(e.stats)) for e in line.events
                   if e.name.startswith("engine.")]
            if evs:
                out[(plane.name, n)] = sorted(evs, key=lambda e: e[1])
    return out


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """Two requests through a ContinuousEngine under one profiler
    session; the spans of every host line that has any."""
    tmp = str(tmp_path_factory.mktemp("profile"))
    eng = ContinuousEngine(params, TINY, n_slots=2, cache_len=64).start()
    try:
        assert eng._thread.name == "continuous-batcher"
        eng.generate([1, 2, 3, 4], max_new_tokens=3)  # compiles
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # what benchmarks/serve.py asks for
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            a = eng.submit([1, 2, 3, 4, 5], max_new_tokens=6)
            b = eng.submit([9, 8, 7], max_new_tokens=4)
            assert a.done.wait(120) and b.done.wait(120)
            # the pass that retired the last row is still open; a span
            # cut by the session's end is dropped, its children kept
            time.sleep(0.3)
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.stop()
    lines = _host_lines(tmp)
    return {"lines": lines, "rids": {a.rid, b.rid}}


def _engine_line(traced):
    (line,) = [evs for evs in traced["lines"].values()
               if any(name == "engine.pass" for name, *_ in evs)]
    return line


def test_engine_spans_share_one_host_line(traced):
    # the scheduler thread's line; the profiler names it after the OS
    # thread, which Python 3.12 does not rename, so it is found by
    # what it holds
    with_engine = [evs for evs in traced["lines"].values()
                   if any(n.startswith("engine.") for n, *_ in evs)]
    assert len(with_engine) == 1


def test_every_recorded_span_is_a_listed_name(traced):
    assert {name for name, *_ in _engine_line(traced)} <= set(
        stepprof.HOST_SPANS)


SPAN_ARGS = {
    "engine.pass": {"decode_rows", "queue_depth"},
    "engine.admit": {"rid", "slot", "bucket", "suffix_tokens",
                     "cached_tokens"},
    "engine.decode.dispatch": {"k", "rows"},
    "engine.decode.readback": set(),
    "engine.decode.emit": {"tokens"},
    "engine.admit_pending": {"placed"},
    "engine.plan_admissions": {"staged"},
}


@pytest.mark.parametrize("name", sorted(SPAN_ARGS))
def test_span_is_recorded_with_its_arguments(name, traced):
    found = [e for e in _engine_line(traced) if e[0] == name]
    assert found, f"no {name} span on the scheduler's line"
    # a pass that was cut short (idle, nothing to decode) has no
    # arguments yet; one span with all of them is what a reader needs
    assert any(SPAN_ARGS[name] <= set(e[3]) for e in found)


def test_admit_spans_name_both_requests(traced):
    admits = [e for e in _engine_line(traced) if e[0] == "engine.admit"]
    assert {e[3]["rid"] for e in admits} == traced["rids"]
    assert [e[3]["suffix_tokens"] for e in admits] == [5, 3]
    assert all(e[3]["cached_tokens"] == 0 for e in admits)


NESTING = {
    "engine.admit.host_prep": "engine.admit",
    "engine.admit.dispatch": "engine.admit",
    "engine.admit.readback": "engine.admit",
    "engine.admit": "engine.admit_pending",
    "engine.decode.dispatch": "engine.pass",
    "engine.plan_admissions": "engine.pass",
    "engine.decode.readback": "engine.pass",
    "engine.decode.emit": "engine.pass",
}


@pytest.mark.parametrize("child", sorted(NESTING))
def test_span_nests_in_its_parent(child, traced):
    line = _engine_line(traced)
    parents = [e for e in line if e[0] == NESTING[child]]
    children = [e for e in line if e[0] == child]
    assert children
    for _, start, end, _ in children:
        assert any(ps <= start and end <= pe for _, ps, pe, _ in parents)


def test_decode_dispatches_carry_the_tokens_of_the_trace(traced):
    line = _engine_line(traced)
    emitted = sum(e[3]["tokens"] for e in line
                  if e[0] == "engine.decode.emit")
    # 6 + 4 tokens asked, the first of each comes from its admit
    assert emitted == (6 - 1) + (4 - 1)
    steps = sum(e[3]["k"] for e in line
                if e[0] == "engine.decode.dispatch")
    assert steps >= 5  # the longer request's decode steps


# --- counters, driven pass by pass under a SimulatedClock --------------------


def _run_pass(eng, clock, dt=0.125):
    clock.advance(dt)
    with stepprof.annotate("engine.pass") as span:
        eng._pass(span)


@pytest.fixture(scope="module")
def counted(params):
    """One slot, chunking on, three requests: A cold and chunked, B
    queued behind A with A's prefix, C into the idle engine. The
    scheduler is never started: the test is its thread."""
    clock = SimulatedClock(start=100.0)
    prev = tracing.set_clock(clock)
    eng = ContinuousEngine(params, TINY, n_slots=1, cache_len=128,
                           block_size=8, prefill_chunk_blocks=2)
    try:
        rng = np.random.default_rng(5)
        prefix = rng.integers(0, TINY.vocab_size, 24).tolist()
        pa = prefix + rng.integers(0, TINY.vocab_size, 17).tolist()
        pb = prefix + rng.integers(0, TINY.vocab_size, 10).tolist()
        pc = rng.integers(0, TINY.vocab_size, 5).tolist()
        a = eng.submit(pa, max_new_tokens=5)
        _run_pass(eng, clock)  # idle path: admits A, queues its chunks
        b = eng.submit(pb, max_new_tokens=3)
        for _ in range(40):
            if a.done.is_set() and b.done.is_set():
                break
            _run_pass(eng, clock)
        assert a.done.is_set() and b.done.is_set()
        # a real clock never stamps a submit and the last window's
        # boundary alike
        clock.advance(0.001)
        c = eng.submit(pc, max_new_tokens=2)
        for _ in range(10):
            if c.done.is_set():
                break
            _run_pass(eng, clock)
        assert c.done.is_set()
        return {
            "reqs": {"a": a, "b": b, "c": c},
            "prompts": {"a": pa, "b": pb, "c": pc},
            "sched": eng.scheduler_stats(),
            "records": eng.profiler.snapshot(),
            "admits": [e for e in eng.flight.snapshot()
                       if e.kind == "admit"],
            "spans": [s for s in tracing.RECORDER.snapshot()
                      if s.name == "engine.queue_wait"
                      and s.attrs.get("req") in (a.rid, b.rid, c.rid)],
            "block_size": eng.block_size,
        }
    finally:
        tracing.set_clock(prev)
        eng.stop()


def test_computed_plus_cached_is_the_admitted_prompts(counted):
    tok = counted["sched"]["prefill_tokens"]
    assert tok["computed"] + tok["cached"] == sum(
        len(p) for p in counted["prompts"].values())


def test_cached_is_reuse_times_block_size(counted):
    reuse = sum(e.detail["reuse_blocks"] for e in counted["admits"])
    assert reuse == 3  # B's 24 shared tokens, three blocks of 8
    assert counted["sched"]["prefill_tokens"]["cached"] == \
        reuse * counted["block_size"]


def test_padded_is_the_bucket_tail_of_each_admit(counted):
    pre = [r for r in counted["records"] if r.phase == "prefill"]
    assert len(pre) == 3
    assert counted["sched"]["prefill_tokens"]["padded"] == sum(
        r.padded_tokens for r in pre)
    # A's two 16-token chunks run no padding and count as computed
    chunks = [r for r in counted["records"] if r.phase == "chunk"]
    assert [r.live_tokens for r in chunks] == [16, 16]


def test_decode_steps_is_the_sum_of_the_decode_records(counted):
    dec = [r for r in counted["records"] if r.phase == "decode"]
    assert counted["sched"]["decode_steps"] == sum(r.steps for r in dec)
    assert counted["sched"]["decode_steps"] >= 4 + 2 + 1


def test_decode_row_steps_is_the_rows_of_each_step(counted):
    dec = [r for r in counted["records"] if r.phase == "decode"]
    assert counted["sched"]["decode_row_steps"] == sum(
        r.live_rows * r.steps for r in dec)
    # never fewer rows than tokens that reached a request
    assert counted["sched"]["decode_row_steps"] >= sum(
        r.live_tokens for r in dec)


def test_dispatches_count_the_records_of_each_phase(counted):
    by_phase = {}
    for r in counted["records"]:
        by_phase[r.phase] = by_phase.get(r.phase, 0) + 1
    assert counted["sched"]["dispatches"] == by_phase
    assert set(by_phase) == {"prefill", "chunk", "decode"}


@pytest.mark.parametrize("who", ["a", "b", "c"])
def test_window_plus_backlog_is_the_queue_wait(who, counted):
    req = counted["reqs"][who]
    assert req.t_admit > req.t_submit
    assert req.wait_window_s + req.wait_backlog_s == pytest.approx(
        req.t_admit - req.t_submit, abs=1e-12)
    assert req.wait_window_s >= 0.0 and req.wait_backlog_s >= 0.0


@pytest.mark.parametrize("who", ["a", "c"])
def test_idle_engine_admission_is_all_window(who, counted):
    assert counted["reqs"][who].wait_backlog_s == 0.0


def test_a_request_behind_a_full_batch_waits_in_both_stages(counted):
    b = counted["reqs"]["b"]
    # four passes of 0.125 s to the first boundary after its submit
    # (A's two chunks, A's admit, A's first decode window: only a
    # decode window is a boundary), then A's other three steps and the
    # pass that places B
    assert b.wait_window_s == pytest.approx(0.5)
    assert b.wait_backlog_s == pytest.approx(0.5)


def test_queue_wait_span_carries_both_halves(counted):
    spans = {s.attrs["req"]: s for s in counted["spans"]}
    for req in counted["reqs"].values():
        attrs = spans[req.rid].attrs
        assert attrs["window_s"] == req.wait_window_s
        assert attrs["backlog_s"] == req.wait_backlog_s


# --- the series on /metrics ---------------------------------------------------


@pytest.fixture(scope="module")
def serving(params):
    from kubeinfer_tpu.inference.engine import Engine
    from kubeinfer_tpu.inference.server import InferenceServer

    eng = ContinuousEngine(params, TINY, n_slots=2, cache_len=64).start()
    srv = InferenceServer(Engine(params, TINY), model_id="prof-tiny",
                          port=0, continuous=eng).start()
    try:
        yield srv
    finally:
        srv.stop()
        eng.stop()


def _scrape(srv) -> str:
    with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
        return r.read().decode()


def _complete(srv, body: dict) -> dict:
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/completions",
        data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


ZERO_SERIES = [
    'kubeinfer_engine_decode_steps_total 0',
    'kubeinfer_engine_decode_row_steps_total 0',
    *(f'kubeinfer_engine_dispatches_total{{phase="{p}"}} 0'
      for p in stepprof.PHASES),
    *(f'kubeinfer_engine_prefill_tokens_total{{kind="{k}"}} 0'
      for k in ("computed", "cached", "padded")),
    *(f'kubeinfer_engine_admission_wait_seconds_count{{stage="{s}"}} 0'
      for s in ("window", "backlog")),
    *(f'kubeinfer_engine_step_duration_seconds_count{{phase="{p}"}} 0'
      for p in ("verify", "chunk")),
]


@pytest.fixture(scope="module")
def first_scrape(serving):
    return _scrape(serving).splitlines()


@pytest.mark.parametrize("line", ZERO_SERIES)
def test_series_exists_at_zero_from_the_first_scrape(line, first_scrape):
    assert line in first_scrape


def test_series_follow_one_request(serving):
    _scrape(serving)
    m = serving.metrics
    before = {k: m["prefill_tokens"].value(k)
              for k in ("computed", "cached", "padded")}
    steps0 = m["decode_steps"].value()
    wait0 = m["queue_wait"].sum("continuous")
    split0 = sum(m["admission_wait"].sum(s)
                 for s in ("window", "backlog"))
    _complete(serving, {"prompt": [5, 4, 3, 2, 1], "max_tokens": 4})
    _scrape(serving)
    assert m["prefill_tokens"].value("computed") \
        + m["prefill_tokens"].value("cached") \
        - before["computed"] - before["cached"] == 5
    assert m["prefill_tokens"].value("padded") - before["padded"] == \
        16 - 5  # the smallest prompt bucket
    assert m["decode_steps"].value() - steps0 >= 3
    assert m["dispatches"].value("prefill") >= 1
    assert m["dispatches"].value("decode") >= 1
    # both halves are observed where queue_wait is: same requests
    assert m["admission_wait"].count("window") == \
        m["admission_wait"].count("backlog") == \
        m["queue_wait"].count("continuous")
    split = sum(m["admission_wait"].sum(s)
                for s in ("window", "backlog"))
    assert split - split0 == pytest.approx(
        m["queue_wait"].sum("continuous") - wait0, abs=1e-9)


def test_step_duration_has_buckets_around_the_decode_step(serving):
    _scrape(serving)
    assert {0.1, 0.15, 0.2, 0.25} <= set(
        serving.metrics["step_duration"].buckets)
