"""Idle time by host span: the arithmetic on hand-made spans, then the
same code on a small sample recorded on the chip
(data/hostspans_small.json: the scheduler's spans and the device's busy
stretches over fourteen passes of qwen2-7b-w8.docs, one admit among
them, PR 27), and the program reader over the trace summary's modules."""

import json
import os

import pytest

from lib import hostspans as hs
from lib.context import Context
from readers import trace_modules

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# two passes: a decode window, then an admit; times in ns
SPANS = [
    ("engine.pass", 0, 100, {"decode_rows": 2}),
    ("engine.decode.dispatch", 10, 20, {"k": 4, "rows": 2}),
    ("engine.decode.readback", 20, 90, {}),
    ("engine.decode.emit", 90, 95, {"tokens": 8}),
    ("engine.pass", 110, 200, {}),
    ("engine.admit_pending", 115, 185, {"placed": 1}),
    ("engine.admit", 120, 180, {"rid": 7}),
    ("engine.admit.host_prep", 120, 140, {}),
    ("engine.admit.dispatch", 140, 150, {}),
    ("engine.admit.readback", 150, 180, {}),
]
# the window runs 15-88 on the device, the admit 145-178
BUSY = [(15, 88), (145, 178)]
MODULES = [("jit_decode_window(3)", 15, 88), ("jit__admit_slot(5)", 145, 178)]


def test_merge_joins_what_lies_closer_than_a_gap():
    assert hs.merge([(0, 10), (10, 20), (25, 30), (2000, 2100)],
                    min_gap_ns=1000) == [[0, 30], [2000, 2100]]
    assert hs.merge([(5, 9), (0, 20)], min_gap_ns=1) == [[0, 20]]
    assert hs.gaps([[0, 30], [2000, 2100]]) == [(30, 2000)]


def test_each_instant_goes_to_the_innermost_span():
    pieces = hs.innermost(SPANS)
    # no overlap, in order, and the whole of both passes is covered
    assert all(a[1] <= b[0] for a, b in zip(pieces, pieces[1:]))
    assert sum(e - s for s, e, _, _ in pieces) == 100 + 90
    by = {}
    for s, e, name, _ in pieces:
        by[name] = by.get(name, 0) + (e - s)
    assert by["engine.pass"] == 10 + 5 + 5 + 15
    assert by["engine.admit_pending"] == 5 + 5
    assert "engine.admit" not in by  # its children cover all of it
    assert by["engine.admit.host_prep"] == 20
    assert {idx for *_, idx in pieces} == {0, 1}


def test_a_gap_is_split_among_the_spans_that_cover_it():
    rows = {r[0]: r for r in hs.idle_by_span(BUSY, SPANS)}
    # the one gap, 88-145: readback 2, emit 5, pass 5 + 5, nothing
    # between the passes 10, admit_pending 5, host_prep 20 (a span that
    # starts inside the gap), dispatch 5
    assert rows["engine.decode.readback"][1] == pytest.approx(2e-9)
    assert rows["engine.admit.host_prep"][1] == pytest.approx(20e-9)
    assert rows["engine.admit.dispatch"][1] == pytest.approx(5e-9)
    assert rows["engine.pass"][1] == pytest.approx(10e-9)
    assert rows[hs.UNATTRIBUTED][1] == pytest.approx(10e-9)
    assert sum(r[1] for r in rows.values()) == pytest.approx(57e-9)
    assert sum(r[2] for r in rows.values()) == pytest.approx(1.0)
    assert hs.longest_gaps(BUSY, SPANS)[0][:2] == (
        pytest.approx(57e-9), "engine.admit.host_prep")


def test_a_gap_no_span_covers_is_unattributed():
    rows = hs.idle_by_span([(0, 10), (300, 310)], SPANS[:4])
    assert rows[0][0] == hs.UNATTRIBUTED
    assert rows[0][1] == pytest.approx(200e-9)  # 100-300: no span


def test_only_gaps_over_the_limit_are_counted_when_asked():
    busy = [(0, 10), (12, 88), (145, 178)]
    assert sum(r[1] for r in hs.idle_by_span(busy, SPANS)) == \
        pytest.approx(59e-9)
    assert sum(r[1] for r in hs.idle_by_span(
        busy, SPANS, longer_than_ns=5)) == pytest.approx(57e-9)


def test_self_time_is_per_pass():
    st = hs.self_time_per_pass(SPANS)
    assert st["engine.pass"][0] == 2  # ran in both passes
    assert st["engine.decode.readback"] == (1, pytest.approx(70e-6),
                                            pytest.approx(70e-6))
    assert "engine.admit" not in st


def test_clock_check_counts_windows_out_of_order():
    ok = hs.clock_check(SPANS, MODULES, tolerance_ns=0)
    assert (ok["windows"], ok["violations"]) == (1, 0)
    assert ok["worst_module_start_before_dispatch_us"] == \
        pytest.approx(-0.005)
    assert ok["worst_module_end_after_readback_us"] == \
        pytest.approx(-0.002)
    # a device clock 12 ns behind the host's: the module seems to start
    # before its dispatch
    early = [(n, s - 12, e - 12) for n, s, e in MODULES]
    bad = hs.clock_check(SPANS, early, tolerance_ns=0)
    assert bad["violations"] == 1
    assert bad["worst_module_start_before_dispatch_us"] == \
        pytest.approx(0.007)
    assert hs.clock_check(SPANS, early, tolerance_ns=10)["violations"] == 0
    # the admit's module is never taken for a window's
    assert hs.clock_check(SPANS, MODULES[1:])["windows"] == 0


def test_scheduler_line_is_found_by_what_it_holds():
    http = ("python", [["engine.idle_wait", 0, 5, {}]])
    sched = ("python", [list(s) for s in SPANS])
    assert hs.scheduler_line([http, sched]) == sched[1]
    assert hs.scheduler_line([http]) == []
    with pytest.raises(ValueError):
        hs.scheduler_line([sched, sched])


def _recorded():
    with open(os.path.join(DATA, "hostspans_small.json")) as f:
        return json.load(f)


def test_recorded_sample_reduces_to_its_known_numbers():
    rec = _recorded()
    rep = hs.report(rec)
    (dev,) = rep["devices"].values()
    want = rec["expect"]
    assert dev["clock_check"]["windows"] == want["windows"]
    assert dev["clock_check"]["violations"] == 0
    assert dev["idle_s"] == pytest.approx(want["idle_s"], rel=1e-9)
    assert dev["longest_gaps"][0][1] == want["longest_gap_owner"]
    assert dev["named_share_of_idle_in_gaps_over_1ms"] >= 0.95
    # every idle nanosecond is charged exactly once
    assert sum(r[2] for r in dev["idle_by_span"]) == pytest.approx(1.0)
    names = {s[0] for s in rec["spans"]}
    assert {"engine.pass", "engine.admit", "engine.decode.dispatch",
            "engine.decode.readback"} <= names
    assert set(rep["self_time_ms_per_pass"]) <= names


def test_recorded_sample_holds_an_uncovered_stretch_and_a_late_span():
    rec = _recorded()
    (dev,) = rec["devices"].values()
    busy = [tuple(b) for b in dev["busy"]]
    spans = [tuple(s) for s in rec["spans"]]
    rows = dict((r[0], r[1]) for r in hs.idle_by_span(busy, spans))
    assert rows.get(hs.UNATTRIBUTED, 0) > 0  # between two passes
    # a span that starts inside a gap takes only its part of it
    inside = [(s, g) for g in hs.gaps(busy) for s in spans
              if g[0] < s[1] < g[1]]
    assert inside
    total = sum(g1 - g0 for g0, g1 in hs.gaps(busy))
    assert sum(rows.values()) == pytest.approx(total / 1e9)


def _summary():
    return {"devices": {"/device:TPU:0": {
        "busy_s": 4.0, "window_s": 5.0, "ops": [], "gaps": [],
        "modules": [["jit_decode_window(11)", 3.0, 20],
                    ["jit__admit_slot(12)", 0.6, 3],
                    ["jit__prefill_chunk(13)", 0.2, 1]]}}, "planes": []}


def test_trace_modules_gives_the_programs_share_of_busy_time():
    ctx = Context(seconds=1, setup_s=1, trace=_summary())
    args = {"stat": "share_of_busy",
            "match": "jit__admit_slot|jit__prefill_chunk"}
    assert trace_modules.read(args, ctx) == pytest.approx(0.8 / 4.0)
    assert trace_modules.read(
        {"stat": "share_of_busy", "match": "jit_decode"}, ctx) == \
        pytest.approx(0.75)
    with pytest.raises(ValueError):
        trace_modules.read({"stat": "x", "match": "."}, ctx)


def test_trace_modules_reads_nothing_where_there_is_nothing():
    assert trace_modules.read({"stat": "share_of_busy", "match": "."},
                              Context(seconds=1, setup_s=1)) is None
    s = _summary()
    s["devices"]["/device:TPU:0"]["modules"] = []
    assert trace_modules.read({"stat": "share_of_busy", "match": "."},
                              Context(seconds=1, setup_s=1, trace=s)) is None
