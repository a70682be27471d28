"""The client side: one HTTP call timed on the client's clock, an open
loop that sends on a schedule whether or not earlier requests have
finished, and a closed loop of clients that each wait for their reply.

``observability/loadgen.replay`` timed a request from when it was sent
and trusted the server's stamps alone. Here a request is timed from when
it was DUE, so the wait a stall imposes on later arrivals counts, and
the server's stamps are pinned to the client's clock by ``check_stamps``.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field
from http.client import HTTPException

from lib import schedule as sched

# the server rounds its stamps to a microsecond and the two clocks are
# read a few instructions apart: allow this much before calling a
# server-side time longer than the client's own a contradiction
STAMP_SLACK_MS = 1.0


def http(url: str, body: dict | None = None, timeout: float = 60.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, resp.read().decode()


@dataclass
class Record:
    """One request as the client saw it. Times are seconds on the
    client's monotonic clock, relative to the window's opening."""
    index: int
    due_s: float
    send_s: float = 0.0
    done_s: float = 0.0
    status: int = 0
    error: str = ""
    prompt_len: int = 0
    max_tokens: int = 0
    tokens: list = field(default_factory=list)
    route: str = ""
    server_ttft_ms: float = 0.0
    server_tpot_ms: float = 0.0

    @property
    def ok(self) -> bool:
        return self.status == 200 and not self.error

    @property
    def lateness_ms(self) -> float:
        return (self.send_s - self.due_s) * 1e3

    @property
    def wall_ms(self) -> float:
        return (self.done_s - self.send_s) * 1e3

    @property
    def req_ms(self) -> float:
        """Due time to the whole reply, all on the client's clock."""
        return (self.done_s - self.due_s) * 1e3

    @property
    def ttft_ms(self) -> float:
        """Due time to first token: how late the request left the
        client, plus the server's own submit-to-first-token stamp. The
        server does not stream, so this is the nearest a client gets."""
        return self.lateness_ms + self.server_ttft_ms

    @property
    def tpot_ms(self) -> float:
        return self.server_tpot_ms

    @property
    def server_span_ms(self) -> float:
        """Submit to last token by the server's stamps."""
        return self.server_ttft_ms + self.server_tpot_ms * max(
            0, len(self.tokens) - 1)

    @property
    def overhead_ms(self) -> float:
        """What the client waited beyond the server's own span: HTTP
        parse, JSON, thread hand-offs, the loopback."""
        return self.wall_ms - self.server_span_ms


def check_stamps(rec: Record) -> str:
    """'' when the server's stamps fit inside the client's own
    send-to-reply time, else what is wrong."""
    if rec.server_span_ms > rec.wall_ms + STAMP_SLACK_MS:
        return (f"request {rec.index}: the server's stamps span "
                f"{rec.server_span_ms:.3f} ms, the client waited "
                f"{rec.wall_ms:.3f} ms")
    return ""


def check_reply(rec: Record, vocab: int) -> str:
    """'' when the reply is what was asked for, else what is wrong."""
    if rec.error:
        return f"request {rec.index}: {rec.error}"
    if rec.status != 200:
        return f"request {rec.index}: HTTP {rec.status}"
    if len(rec.tokens) != rec.max_tokens:
        return (f"request {rec.index}: asked for {rec.max_tokens} "
                f"tokens, got {len(rec.tokens)}")
    if not all(isinstance(t, int) and 0 <= t < vocab for t in rec.tokens):
        return f"request {rec.index}: a token id outside the vocabulary"
    if rec.route != "continuous":
        return f"request {rec.index}: served by route {rec.route!r}"
    return check_stamps(rec)


def post(url: str, rec: Record, prompt: list[int], t_open: float,
         timeout: float) -> Record:
    """Send one completion and fill ``rec`` in. Never raises: a failed
    request is a record with an error, counted by the caller."""
    rec.prompt_len, body = len(prompt), {
        "prompt": prompt, "max_tokens": rec.max_tokens}
    rec.send_s = time.monotonic() - t_open
    try:
        rec.status, text = http(url + "/v1/completions", body, timeout)
        rec.done_s = time.monotonic() - t_open
        doc = json.loads(text)
        rec.tokens = doc["choices"][0]["tokens"]
        ext = doc["kubeinfer"]
        rec.route = ext["route"]
        rec.server_ttft_ms = float(ext["ttft_ms"])
        rec.server_tpot_ms = float(ext["tpot_ms"])
    except urllib.error.HTTPError as e:
        rec.done_s = time.monotonic() - t_open
        rec.status, rec.error = e.code, e.read().decode()[:200]
    except (OSError, ValueError, KeyError, HTTPException) as e:
        rec.done_s = time.monotonic() - t_open
        rec.error = f"{type(e).__name__}: {e}"[:200]
    return rec


def judged(records, seconds: float, due=None) -> list[Record]:
    """The replied Records a cell judges. Open loop (``due``: the
    schedule's requests): those due in ``[0, seconds)``, whenever the
    reply came, so that a backlog draining into the window is not
    credited to it and a request answered after its close is not lost.
    Closed loop (no ``due``): those replied inside the window, its only
    population."""
    if due is None:
        return [r for r in records if r.done_s and 0 <= r.done_s <= seconds]
    by_index = {r.index: r for r in records if r.done_s}
    return [by_index[q.index] for q in due
            if 0 <= q.due_s < seconds and q.index in by_index]


class OpenLoop:
    """Sends each request of a schedule at its due time from a thread of
    its own. Tokens are made ahead of the due time, off the clock."""

    def __init__(self, url: str, requests, seed: int, vocab: int,
                 timeout: float) -> None:
        self.url, self.requests = url, list(requests)
        self.seed, self.vocab, self.timeout = seed, vocab, timeout
        self.records: list[Record] = []
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self._pacer: threading.Thread | None = None

    def start(self, t_open: float) -> None:
        """``t_open`` is the monotonic time at which due_s == 0."""
        self._pacer = threading.Thread(
            target=self._pace, args=(t_open,), daemon=True)
        self._pacer.start()

    def _pace(self, t_open: float) -> None:
        for r in self.requests:
            prompt = sched.prompt_tokens(r, self.seed, self.vocab)
            wait = t_open + r.due_s - time.monotonic()
            if wait > 0 and self._stop.wait(wait):
                return
            if self._stop.is_set():
                return
            rec = Record(r.index, r.due_s, max_tokens=r.max_tokens)
            with self._lock:
                self.records.append(rec)
            threading.Thread(
                target=post, daemon=True,
                args=(self.url, rec, prompt, t_open, self.timeout)).start()

    def outstanding(self, before_s: float) -> int:
        """Requests due before ``before_s`` that have no reply yet,
        those not yet sent included."""
        with self._lock:
            done = {r.index for r in self.records if r.done_s}
        return sum(1 for r in self.requests
                   if r.due_s < before_s and r.index not in done)

    def stop(self) -> None:
        """Send nothing further. Replies still on their way are left to
        their daemon threads; their records simply stay unanswered."""
        self._stop.set()
        if self._pacer is not None:
            self._pacer.join(timeout=5.0)

    def snapshot(self) -> list[Record]:
        with self._lock:
            return list(self.records)


class ClosedLoop:
    """``clients`` callers, each sending its next request when the reply
    to the last has come."""

    def __init__(self, url: str, traffic: dict, clients: int, seed: int,
                 vocab: int, timeout: float) -> None:
        self.url, self.traffic, self.clients = url, traffic, clients
        self.seed, self.vocab, self.timeout = seed, vocab, timeout
        self.records: list[Record] = []
        self._stop = threading.Event()
        self._lock = threading.Lock()

    def start(self, t_open: float) -> None:
        for c in range(self.clients):
            threading.Thread(target=self._client, args=(c, t_open),
                             daemon=True).start()

    def _client(self, c: int, t_open: float) -> None:
        turn = 0
        while not self._stop.is_set():
            r = sched.closed_request(self.traffic, self.clients, c, turn)
            prompt = sched.prompt_tokens(r, self.seed, self.vocab)
            now = time.monotonic() - t_open
            rec = Record(r.index, now, max_tokens=r.max_tokens)
            with self._lock:
                self.records.append(rec)
            post(self.url, rec, prompt, t_open, self.timeout)
            if not rec.ok:
                # a refused connection would otherwise spin
                self._stop.wait(0.2)
            turn += 1

    def stop(self) -> None:
        self._stop.set()

    def snapshot(self) -> list[Record]:
        with self._lock:
            return list(self.records)
