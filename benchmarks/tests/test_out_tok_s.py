"""out_tok_s counts the requests due in the window, whenever they were
answered: one schedule served slowly and five times faster reads the
same (ledger, PR 28: the faster server read 12.53 against 15.53 and was
refused), and less only when a due request is lost."""

import pytest

from lib import cell as cells
from lib import client, schedule
from lib.context import Context
from readers import client_records

SECONDS = 51.0
TRAFFIC = cells.load_json("traffic", "docs.json")
REQUESTS, _ = schedule.open_schedule(TRAFFIC, 0.64, SECONDS)
READER = cells.load_json("end_to_end", "out_tok_s.json")["reader"]


def serve(service_s: float, lose=()):
    """Records of one server that answers the schedule in order, one
    request at a time, ``service_s`` each: a backlog builds in the ramp
    when that is slow. Requests in ``lose`` get no reply."""
    free, out = -TRAFFIC["ramp_s"], []
    for q in REQUESTS:
        start = max(free, q.due_s)
        free = start + service_s
        rec = client.Record(q.index, q.due_s, send_s=q.due_s,
                            max_tokens=q.max_tokens)
        if q.index not in lose:
            rec.done_s, rec.status = free, 200
            rec.tokens = [0] * q.max_tokens
        out.append(rec)
    # what the drain has not seen by its end has no reply
    limit = SECONDS + TRAFFIC["drain_s"]
    for rec in out:
        if rec.done_s > limit:
            rec.done_s, rec.status, rec.tokens = 0.0, 0, []
    return out


def out_tok_s(records):
    window = client.judged(records, SECONDS, REQUESTS)
    ctx = Context(seconds=SECONDS, setup_s=1.0, window=window)
    return client_records.read(dict(READER, kind=None), ctx)


def test_a_faster_server_reads_the_same():
    slow, fast = serve(1.5), serve(0.3)
    # the slow one has a backlog at the opening and replies into the window
    late = [r for r in slow if r.due_s < 0 < r.done_s]
    assert late and not [r for r in fast if r.due_s < 0 < r.done_s]
    due = [q for q in REQUESTS if 0 <= q.due_s < SECONDS]
    want = sum(q.max_tokens for q in due) / SECONDS
    assert out_tok_s(slow) == pytest.approx(want)
    assert out_tok_s(fast) == pytest.approx(want)
    # what the metric was before: replies inside the window, whenever due
    old = [sum(len(r.tokens) for r in recs
               if r.done_s and 0 <= r.done_s <= SECONDS) / SECONDS
           for recs in (slow, fast)]
    assert old[0] != pytest.approx(old[1])


def test_a_lost_request_reads_less():
    due = [q for q in REQUESTS if 0 <= q.due_s < SECONDS]
    lost = due[len(due) // 2]
    whole, less = out_tok_s(serve(0.3)), out_tok_s(serve(0.3, {lost.index}))
    assert less == pytest.approx(whole - lost.max_tokens / SECONDS)


def test_a_failed_reply_reads_less():
    records = serve(0.3)
    due = [q for q in REQUESTS if 0 <= q.due_s < SECONDS]
    by_index = {r.index: r for r in records}
    by_index[due[0].index].status = 503
    assert out_tok_s(records) == pytest.approx(
        out_tok_s(serve(0.3)) - due[0].max_tokens / SECONDS)


def test_a_request_outside_the_window_counts_nowhere():
    before = [r for r in serve(0.3) if r.due_s < 0]
    assert before and client.judged(before, SECONDS, REQUESTS) == []


def test_a_closed_loop_counts_replies_inside_the_window():
    records = serve(0.3)
    inside = client.judged(records, SECONDS)
    assert inside and all(0 <= r.done_s <= SECONDS for r in inside)
    assert len(inside) < len([r for r in records if r.done_s])
