import pytest

from lib import client


def _rec(**kw):
    base = dict(index=0, due_s=1.0, send_s=1.002, done_s=3.002, status=200,
                max_tokens=11, tokens=list(range(11)), route="continuous",
                server_ttft_ms=500.0, server_tpot_ms=140.0)
    base.update(kw)
    return client.Record(**base)


def test_first_token_counts_from_the_due_time():
    r = _rec()
    assert r.lateness_ms == pytest.approx(2.0)
    assert r.ttft_ms == pytest.approx(502.0)
    assert r.req_ms == pytest.approx(2002.0)
    assert r.server_span_ms == pytest.approx(500 + 140 * 10)
    assert r.overhead_ms == pytest.approx(2000 - 1900)


def test_stamps_inside_the_clients_time_pass():
    assert client.check_reply(_rec(), vocab=100) == ""


def test_stamps_longer_than_the_clients_time_fail():
    bad = _rec(server_tpot_ms=151.0)  # 500 + 1510 = 2010 > 2000 + 1
    assert "stamps span" in client.check_stamps(bad)
    edge = _rec(server_tpot_ms=150.05)  # 2000.5: inside the 1 ms slack
    assert client.check_stamps(edge) == ""


@pytest.mark.parametrize("kw,word", [
    (dict(status=503), "HTTP 503"),
    (dict(tokens=[1, 2]), "asked for 11"),
    (dict(tokens=[0] * 10 + [100]), "outside the vocabulary"),
    (dict(route="engine"), "route"),
    (dict(error="URLError: refused"), "refused"),
])
def test_a_wrong_reply_is_named(kw, word):
    assert word in client.check_reply(_rec(**kw), vocab=100)


def test_one_token_has_no_decode_span():
    r = _rec(max_tokens=1, tokens=[5], server_tpot_ms=9.0)
    assert r.server_span_ms == 500.0


def test_a_connection_cut_mid_reply_is_a_failed_record(monkeypatch):
    # http.client.HTTPException, which ``http`` the function shadowed:
    # a request cut off by the server's SIGTERM raised AttributeError
    from http.client import IncompleteRead

    def cut(url, body=None, timeout=60.0):
        raise IncompleteRead(b"")

    monkeypatch.setattr(client, "http", cut)
    rec = client.post("http://x", client.Record(0, 0.0, max_tokens=4),
                      [1, 2, 3], t_open=0.0, timeout=1.0)
    assert not rec.ok and rec.error.startswith("IncompleteRead")
