"""The trace reduction: arithmetic on hand-made events, then the same
code on a small trace recorded on the chip (data/trace_small.json, an
excerpt of one decode window of qwen2-7b-w8.chat, PR 25)."""

import json
import os

import pytest

from lib import trace
from lib.context import Context
from lib.peaks import peaks_for
from opcount import quant_matmul
from readers import trace_ops, trace_roofline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# a loop that spans two kernels and a gap, then a lone op after an idle
# stretch: (name, start ns, duration ns)
K = ("%k.1 = bf16[8,512]{1,0} custom-call(bf16[8,256]{1,0} %x, "
     "s8[256,512]{1,0} %w), custom_call_target=\"tpu_custom_call\"")
EVENTS = [("while", 0, 100), (K, 0, 30), ("m", 40, 50),
          ("c", 120, 10), (K, 130, 5)]


def test_union_counts_covered_time_once():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace.union_ns([]) == 0
    assert trace.union_ns((s, s + d) for _, s, d in EVENTS) == 115


def test_self_time_charges_each_nanosecond_to_the_innermost_event():
    got = {}
    for key, ns in trace.self_times(EVENTS):
        got[key] = got.get(key, 0) + ns
    assert got == {"while": 20, K: 35, "m": 50, "c": 10}
    assert sum(got.values()) == 115  # == the union: nothing twice


def test_gaps_are_named_by_what_ended_them():
    assert trace.gaps(EVENTS) == [("before:c", 20e-9)]


def test_reduce_plane_gives_busy_window_and_ops_by_time():
    r = trace.reduce_plane(EVENTS, [("jit_step", 0, 100)])
    assert r["window_s"] == pytest.approx(135e-9)
    assert r["busy_s"] == pytest.approx(115e-9)
    assert [row[0] for row in r["ops"]] == ["m", K, "while", "c"]
    assert r["ops"][1] == [K, pytest.approx(35e-9), 2]
    assert r["modules"] == [["jit_step", pytest.approx(100e-9), 1]]
    assert trace.reduce_plane([]) is None


def _summary():
    return {"devices": {"/device:TPU:0": trace.reduce_plane(EVENTS)},
            "planes": []}


QMM = r"custom-call\(.*\bs8\[\d+,\d+\].*tpu_custom_call"


def test_label_drops_layouts_names_and_serials():
    assert trace.label(K) == "custom-call(bf16[8,256], s8[256,512])" \
        "->bf16[8,512]"
    assert trace.by_label([[K, 2.0, 1], [K.replace("%k.1", "%k.2"), 1.0, 1],
                           ["m", 0.5, 1]])[0][1] == 3.0


def test_readers_on_a_summary():
    ctx = Context(seconds=1, setup_s=1, trace=_summary(),
                  peaks=peaks_for("TPU v5 lite"))
    idle = trace_ops.read({"stat": "idle_share"}, ctx)
    assert idle == pytest.approx(1 - 115 / 135)
    share = trace_ops.read({"stat": "share_of_busy",
                            "match": QMM}, ctx)
    assert share == pytest.approx(35 / 115)
    # two calls of [8,256]x[256,512]: memory bound, 35 ns measured
    ops, moved = quant_matmul.count(8, 256, 512)
    least = 2 * max(ops / 197e12, moved / 819e9)
    got = trace_roofline.read({"match": QMM,
                               "opcount": "quant_matmul"}, ctx)
    assert got == pytest.approx(100 * least / 35e-9)
    assert any("memory" in n for n in ctx.notes)


def test_roofline_reports_nothing_when_a_shape_cannot_be_read():
    ctx = Context(seconds=1, setup_s=1, trace=_summary(),
                  peaks=peaks_for("TPU v5 lite"))
    assert trace_roofline.read({"match": "^m$",
                                "opcount": "quant_matmul"}, ctx) is None


def test_quant_matmul_counts():
    ops, moved = quant_matmul.count(128, 3584, 18944)
    assert ops == 2 * 128 * 3584 * 18944
    assert moved == 128 * 3584 * 2 + 3584 * 18944 + 4 * 18944 \
        + 128 * 18944 * 2


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA,
                                                    "trace_small.json")),
                    reason="no recorded trace")
def test_recorded_trace_reduces_to_its_known_numbers():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        rec = json.load(f)
    r = trace.reduce_plane([tuple(e) for e in rec["events"]])
    assert r["busy_s"] == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(rec["expect"]["window_s"],
                                          rel=1e-9)
    assert r["busy_s"] <= r["window_s"]
    total_self = sum(row[1] for row in r["ops"])
    assert total_self == pytest.approx(r["busy_s"], rel=1e-6)
    assert r["ops"][0][0] == rec["expect"]["top_op"]
