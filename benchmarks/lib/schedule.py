"""One general traffic generator, driven by a traffic file.

The idea (seeded arrivals and length families, a checksum over the
canonical schedule) is ``observability/loadgen.make_schedule``'s; the
families there are fixed in code, so this one reads them from data.

What the two seeds do. Arrival times and lengths come from the traffic
file's own ``schedule_seed``: they are the mix, the same for every run,
like a recorded trace. ``--seed`` makes every token id. A window of this
benchmark holds tens of requests, not thousands, and a median over 35
requests moves by more than any admissible bound when the arrivals are
drawn anew; so the run's seed changes the inputs and never the amount
or the order of work (PERF.md, section 2).

Every draw is a pure function of (schedule_seed, stream, index), so a
schedule for a longer window has the shorter one as its prefix.
"""

from __future__ import annotations

import hashlib
import math
import random
from dataclasses import dataclass

import numpy as np


def _rng(seed: int, *stream) -> random.Random:
    return random.Random("/".join(str(s) for s in (seed, *stream)))


def draw(dist: dict, rng: random.Random) -> float:
    """One draw from a distribution given as data."""
    kind = dist["dist"]
    if kind == "const":
        x = dist["value"]
    elif kind == "uniform_int":
        x = rng.randint(dist["min"], dist["max"])
    elif kind == "choice":
        x = rng.choice(dist["values"])
    elif kind == "exponential":
        x = rng.expovariate(1.0 / dist["mean"])
    elif kind == "lognormal":  # clipped, whole
        x = math.exp(rng.gauss(math.log(dist["median"]), dist["sigma"]))
        x = int(round(min(max(x, dist["min"]), dist["max"])))
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return x


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float  # from the window's opening; negative inside the ramp
    private_len: int  # tokens only this request has
    max_tokens: int
    doc: int = -1  # shared document, -1 for none
    doc_len: int = 0

    @property
    def prompt_len(self) -> int:
        return self.doc_len + self.private_len

    def canonical(self) -> str:
        return (f"{self.index},{self.due_s:.9f},{self.private_len},"
                f"{self.max_tokens},{self.doc},{self.doc_len}")


def _lengths(traffic: dict, index: int) -> tuple[int, int]:
    rng = _rng(traffic["schedule_seed"], "len", index)
    return (int(draw(traffic["prompt_len"], rng)),
            int(draw(traffic["output_len"], rng)))


def open_schedule(traffic: dict, rate_req_s: float, seconds: float):
    """Requests due from ``-ramp_s`` to ``seconds + drain_s``, sorted by
    due time, and the documents to admit during set-up: those asked
    before the ramp began and again after."""
    seed = traffic["schedule_seed"]
    start, end = -float(traffic["ramp_s"]), seconds + traffic["drain_s"]
    sharing = traffic.get("sharing")
    asks: list[tuple[float, int, int]] = []  # due, doc, doc_len
    preload: list[tuple[int, int]] = []
    if not sharing:
        arr = _rng(seed, "arrivals")
        t = start
        while True:
            t += arr.expovariate(1.0) / rate_req_s
            if t > end:
                break
            asks.append((t, -1, 0))
    else:
        n_asks = sharing["asks_per_doc"]
        arr = _rng(seed, "arrivals")
        t = start - float(sharing["lead_s"])
        doc = 0
        while True:
            t += arr.expovariate(1.0) * n_asks / rate_req_s
            if t > end:
                break
            drng = _rng(seed, "doc", doc)
            doc_len = int(draw(sharing["doc_len"], drng))
            due, times = t, []
            for _ in range(n_asks):
                times.append(due)
                due += draw(sharing["ask_gap_s"], drng)
            inside = [x for x in times if start <= x <= end]
            if inside and min(times) < start:
                preload.append((doc, doc_len))
            asks.extend((x, doc, doc_len) for x in inside)
            doc += 1
        asks.sort()
    out = []
    for i, (due, doc, doc_len) in enumerate(asks):
        p, n = _lengths(traffic, i)
        out.append(Request(i, due, p, n, doc, doc_len))
    return out, preload


def closed_request(traffic: dict, clients: int, client: int,
                   turn: int) -> Request:
    """The ``turn``-th request of one closed-loop client."""
    index = turn * clients + client
    p, n = _lengths(traffic, index)
    return Request(index, 0.0, p, n)


def tokens(seed: int, stream: int, index: int, n: int,
           vocab: int) -> list[int]:
    """``n`` token ids, a pure function of the run's seed, the stream
    (1 documents, 2 requests, 3 warm-up, 4 preload questions) and the
    index within it. The seed may exceed 32 bits."""
    return np.random.default_rng([seed, stream, index]).integers(
        0, vocab, n).tolist()


def doc_tokens(seed: int, doc: int, doc_len: int, vocab: int) -> list[int]:
    return tokens(seed, 1, doc, doc_len, vocab)


def prompt_tokens(req: Request, seed: int, vocab: int) -> list[int]:
    """The request's token ids: its document's, then its own."""
    own = tokens(seed, 2, req.index, req.private_len, vocab)
    if req.doc < 0:
        return own
    return doc_tokens(seed, req.doc, req.doc_len, vocab) + own


def checksum(requests, seed: int, vocab: int) -> str:
    """sha256 over the canonical schedule and every token id."""
    h = hashlib.sha256()
    for r in requests:
        h.update(r.canonical().encode())
        h.update(np.asarray(prompt_tokens(r, seed, vocab),
                            np.int64).tobytes())
    return h.hexdigest()
