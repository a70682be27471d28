import json
import os

import pytest

from lib import cell as cells
from lib import schedule

TRAFFIC = os.path.join(cells.ROOT, "traffic")
OPEN = sorted(f[:-5] for f in os.listdir(TRAFFIC)
              if json.load(open(os.path.join(TRAFFIC, f)))["loop"] == "open")
BIG_SEED = 2**31 + 12345  # more than 32 signed bits hold


def _traffic(name):
    return cells.load_json("traffic", name + ".json")


@pytest.mark.parametrize("mix", OPEN)
def test_same_seed_same_bytes(mix):
    t = _traffic(mix)
    a, pa = schedule.open_schedule(t, 0.7, 30)
    b, pb = schedule.open_schedule(t, 0.7, 30)
    assert a == b and pa == pb
    assert schedule.checksum(a, BIG_SEED, 1000) == \
        schedule.checksum(b, BIG_SEED, 1000)


@pytest.mark.parametrize("mix", OPEN)
def test_seed_changes_tokens_never_the_work(mix):
    t = _traffic(mix)
    reqs, _ = schedule.open_schedule(t, 0.7, 30)
    assert schedule.checksum(reqs, 1, 1000) != \
        schedule.checksum(reqs, 2, 1000)
    r = reqs[0]
    assert len(schedule.prompt_tokens(r, 1, 1000)) == r.prompt_len == \
        len(schedule.prompt_tokens(r, 2, 1000))


@pytest.mark.parametrize("mix", OPEN)
def test_longer_window_keeps_the_shorter_as_prefix(mix):
    t = _traffic(mix)
    short, _ = schedule.open_schedule(t, 0.7, 20)
    long_, _ = schedule.open_schedule(t, 0.7, 51)
    if not t.get("sharing"):
        assert long_[:len(short)] == short
    else:  # asks of later documents may interleave: compare as sets
        key = lambda r: (round(r.due_s, 9), r.doc)  # noqa: E731
        assert {key(r) for r in short} <= {key(r) for r in long_}


@pytest.mark.parametrize("mix", OPEN)
def test_lengths_stay_inside_the_mix(mix):
    t = _traffic(mix)
    reqs, _ = schedule.open_schedule(t, 1.0, 200)
    p, o = t["prompt_len"], t["output_len"]
    assert all(p["min"] <= r.private_len <= p["max"] for r in reqs)
    assert all(o["min"] <= r.max_tokens <= o["max"] for r in reqs)
    assert all(-t["ramp_s"] <= r.due_s <= 200 + t["drain_s"] for r in reqs)
    assert reqs == sorted(reqs, key=lambda r: r.due_s)


def test_rate_scales_the_same_pattern():
    t = _traffic("chat")
    a, _ = schedule.open_schedule(t, 0.5, 1000)
    n = sum(1 for r in a if 0 <= r.due_s < 1000)
    assert n / 1000 == pytest.approx(0.5, rel=0.1)


def test_documents_share_whole_prefixes_and_preload_is_real():
    t = _traffic("docs")
    reqs, preload = schedule.open_schedule(t, 0.4, 51)
    by_doc = {}
    for r in reqs:
        by_doc.setdefault(r.doc, []).append(r)
    assert max(len(v) for v in by_doc.values()) <= \
        t["sharing"]["asks_per_doc"]
    doc, asks = max(by_doc.items(), key=lambda kv: len(kv[1]))
    a, b = (schedule.prompt_tokens(r, 5, 1000) for r in asks[:2])
    n = asks[0].doc_len
    assert a[:n] == b[:n] and a[n:] != b[n:]
    assert a[:n] == schedule.doc_tokens(5, doc, n, 1000)
    for d, n in preload:
        assert d in by_doc and by_doc[d][0].doc_len == n


def test_closed_loop_turns_are_distinct_and_deterministic():
    t = _traffic("batch")
    a = schedule.closed_request(t, 128, 3, 0)
    assert a == schedule.closed_request(t, 128, 3, 0)
    b = schedule.closed_request(t, 128, 3, 1)
    assert a.index != b.index
    p = t["prompt_len"]
    assert p["min"] <= a.private_len <= p["max"]


def test_unknown_distribution_is_an_error():
    import random
    with pytest.raises(ValueError):
        schedule.draw({"dist": "zipf"}, random.Random(0))
