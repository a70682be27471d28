"""One cell, once: ``python benchmarks/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``.

This is the parent. It never imports jax: the child (serve.py) owns the
chips. It reads the cell's files, starts the child, waits for /health,
warms up every shape the traffic uses, offers the traffic over HTTP,
scrapes /metrics once a second, stops the child and prints one JSON
object as the last line of its output. It holds no cell's and no
model's name: a cell is the files its name leads to.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time

T_START = time.monotonic()

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from lib import cell as cells  # noqa: E402
from lib import client, prom, schedule, trace  # noqa: E402
from lib.context import CheckRun, Context  # noqa: E402
from lib.peaks import DEVICE_PEAKS  # noqa: E402
from lib.stats import percentile  # noqa: E402

CHECKOUT = cells.CHECKOUT
HEALTH_LIMIT_S = 1100.0  # a cold child compiles nothing before /health,
# but makes and quantises 7.6 B weights
REQUEST_LIMIT_S = 900.0  # a cold warm-up request compiles its programs
STOP_LIMIT_S = 60.0
TRACE_LIMIT_S = 240.0
TRACE_SECONDS = 6.0


class RunFailure(Exception):
    """The run cannot give a result (no chip, a child that died)."""


def say(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """serve.py as a child process, its output in a log of its own."""

    def __init__(self, cell, out: str) -> None:
        self.out = out
        self.port = free_port()
        self.url = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(out, "server.log")
        self.log = open(self.log_path, "w")
        cmd = [sys.executable, os.path.join(HERE, "serve.py"),
               "--config", os.path.join(HERE, "configs",
                                        cell.spec["config"] + ".json"),
               "--port", str(self.port), "--out", out]
        if not cell.listed:
            cmd.append("--allow-cpu")
        # every compile leaves a line in the log, so that one inside the
        # window is seen whatever the program's own counter says
        env = dict(os.environ, JAX_LOG_COMPILES="1")
        self.proc = subprocess.Popen(
            cmd, cwd=CHECKOUT, env=env, stdout=self.log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise RunFailure(f"the server exited {rc}; the end of its "
                             f"log:\n{self.tail()}")

    def tail(self, n: int = 4000) -> str:
        self.log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()[-n:]

    def wait_healthy(self) -> None:
        deadline = time.monotonic() + HEALTH_LIMIT_S
        while time.monotonic() < deadline:
            self.alive()
            try:
                if client.http(self.url + "/health", timeout=5)[0] == 200:
                    return
            except OSError:
                pass  # not listening yet: the weights are being made
            time.sleep(0.5)
        raise RunFailure("the server was not healthy in time")

    def metrics(self) -> str:
        return client.http(self.url + "/metrics", timeout=30)[1]

    def log_size(self) -> int:
        return os.path.getsize(self.log_path)

    def compiled_between(self, start: int, end: int) -> list[str]:
        """What JAX logged as compiled in that stretch of the log."""
        with open(self.log_path, "rb") as f:
            f.seek(start)
            text = f.read(end - start).decode(errors="replace")
        return [ln.strip()[:160] for ln in text.splitlines()
                if "Compiling " in ln]

    def signal(self, sig) -> None:
        if self.proc.poll() is None:
            self.proc.send_signal(sig)

    def stop(self) -> int | None:
        """SIGTERM, then wait. None when it had to be killed."""
        self.signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=STOP_LIMIT_S)
        except subprocess.TimeoutExpired:
            rc = None
        self.kill()
        return rc

    def kill(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        self.proc.wait()
        self.log.close()


def warm_up(child: Child, warmup: list[dict], seed: int, vocab: int,
            preload) -> list[str]:
    """Every shape the window will use, before it opens: the cell's
    ``warmup`` requests, then the documents to have in the cache.
    Returns what was found wrong."""
    wrong: list[str] = []
    t_open = time.monotonic()

    def send(index, tokens, max_tokens):
        child.alive()
        rec = client.Record(index, 0.0, max_tokens=max_tokens)
        client.post(child.url, rec, tokens, t_open, REQUEST_LIMIT_S)
        bad = client.check_reply(rec, vocab)
        if bad:
            wrong.append(f"warm-up {bad}")
        return rec

    for i, w in enumerate(warmup):
        tokens = schedule.tokens(seed, 3, i, w["prompt_len"], vocab)
        first = send(-1 - i, tokens, w["max_tokens"])
        if not w.get("twice"):
            continue
        hits0 = prom.value(child.metrics(),
                           "kubeinfer_prefix_cache_hits_total") or 0
        again = send(-1 - i, tokens, w["max_tokens"])
        if first.tokens != again.tokens:
            wrong.append(f"warm-up {i}: the same greedy prompt gave other "
                         "tokens the second time")
        hits = prom.value(child.metrics(),
                          "kubeinfer_prefix_cache_hits_total") or 0
        if w.get("expect_hit") and hits <= hits0:
            wrong.append(f"warm-up {i}: the repeat did not come from the "
                         "radix cache")
    # documents first asked before the ramp: in the cache when it opens
    for doc, doc_len in preload:
        tokens = schedule.doc_tokens(seed, doc, doc_len, vocab) \
            + schedule.tokens(seed, 4, doc, 33, vocab)
        send(-1000 - doc, tokens, 1)
    return wrong


def device_has_peaks(device: dict) -> bool:
    """The look for a chip: a number from anything else is no result."""
    return device["platform"] == "tpu" and device["kind"] in DEVICE_PEAKS


def run_checks(cell, moment: str, check_run: CheckRun) -> list[str]:
    """Each of the cell's checks/<kind>.py at one of its two moments
    (``before_window``, ``after_exit``); a file that has nothing to do
    at a moment leaves the function out."""
    wrong: list[str] = []
    for mod in cell.checks:
        fn = getattr(mod, moment, None)
        if fn is not None:
            wrong += fn(check_run)
    return wrong


class Scraper(threading.Thread):
    """/metrics at the window's opening and every second after, the
    last at its close."""

    def __init__(self, child: Child, t_open: float, seconds: float):
        super().__init__(daemon=True)
        self.child, self.t_open, self.seconds = child, t_open, seconds
        self.scrapes: list[tuple[float, str]] = []
        self.error = ""

    def run(self) -> None:
        k = 0
        while k <= self.seconds:
            wait = self.t_open + k - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            try:
                self.scrapes.append(
                    (time.monotonic() - self.t_open, self.child.metrics()))
            except OSError as e:
                self.error = f"scrape at {k} s: {e}"
            k += 1


def reduce_trace(out: str) -> dict | None:
    """lib/trace.py in a process of its own, held to the CPU: the chip's
    owner has exited, and this one must not ask for it."""
    summary = os.path.join(out, "trace_summary.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "lib", "trace.py"),
         os.path.join(out, "trace"), summary],
        cwd=CHECKOUT, env=env, timeout=TRACE_LIMIT_S,
        stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True)
    if r.returncode != 0:
        print(f"run.py: the trace reduction failed:\n{r.stderr[-2000:]}",
              file=sys.stderr)
        return None
    with open(summary, encoding="utf-8") as f:
        return json.load(f)


def read_metrics(defs: list[dict], ctx: Context) -> dict:
    """Each metric through the reader its file names; a reader that
    found nothing to read leaves its metric out."""
    out = {}
    for m in defs:
        args = dict(m["reader"])
        value = cells.reader(args.pop("kind"))(args, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run(args) -> int:
    cell = cells.load_cell(args.workload)
    if not os.path.isdir(os.path.join(CHECKOUT, "kubeinfer_tpu")):
        raise RunFailure("the program is not next to the benchmark")
    conf, traffic, seconds = cell.config, cell.traffic, float(args.seconds)
    vocab = conf["vocab_size"]
    out = os.path.join(CHECKOUT, ".bench_out", cell.name)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    open_loop = traffic["loop"] == "open"
    requests, preload = ([], [])
    if open_loop:
        requests, preload = schedule.open_schedule(
            traffic, cell.spec["rate_req_s"], seconds)

    child = Child(cell, out)
    try:
        child.wait_healthy()
        with open(os.path.join(out, "device.json")) as f:
            device = json.load(f)
        phases = {"healthy": time.monotonic() - T_START}
        wrong = warm_up(child, cell.spec["warmup"], args.seed, vocab,
                        preload)
        phases["warmed_up"] = time.monotonic() - T_START
        check_run = CheckRun(cell=cell, config=conf, seed=args.seed,
                             out=out, url=child.url, requests=requests)
        wrong += run_checks(cell, "before_window", check_run)

        # the ramp runs into the window: no pause between them
        t_open = time.monotonic() + traffic["ramp_s"]
        if open_loop:
            loop = client.OpenLoop(child.url, requests, args.seed, vocab,
                                   REQUEST_LIMIT_S)
        else:
            loop = client.ClosedLoop(child.url, traffic,
                                     cell.spec["clients"], args.seed,
                                     vocab, REQUEST_LIMIT_S)
        loop.start(t_open)
        scraper = Scraper(child, t_open, seconds)
        scraper.start()
        setup_s = t_open - T_START

        time.sleep(max(0.0, t_open - time.monotonic()))
        log_open = child.log_size()
        trace_s = min(TRACE_SECONDS, seconds / 2)
        if args.trace:
            time.sleep(max(0.0, t_open + (seconds - trace_s) / 2
                           - time.monotonic()))
            child.signal(signal.SIGUSR1)
            time.sleep(trace_s)
            child.signal(signal.SIGUSR2)
        time.sleep(max(0.0, t_open + seconds - time.monotonic()))
        log_close = child.log_size()
        scraper.join(timeout=60)
        if open_loop:
            limit = time.monotonic() + traffic["drain_s"]
            while loop.outstanding(seconds) and time.monotonic() < limit:
                child.alive()
                time.sleep(0.1)
        loop.stop()
        records = loop.snapshot()
        last = child.metrics()
        if args.trace:
            limit = time.monotonic() + TRACE_LIMIT_S
            done = os.path.join(out, "trace", "done")
            while not os.path.exists(done) and time.monotonic() < limit:
                child.alive()
                time.sleep(0.5)
        compiled = child.compiled_between(log_open, log_close)
        rc = child.stop()
    finally:
        child.kill()
    if compiled:
        wrong.append(f"{len(compiled)} program(s) compiled inside the "
                     f"window, the first: {compiled[0]}")
    if rc != 0:
        wrong.append(f"the server exited {rc} on SIGTERM")

    # --- the requests the cell judges: due in the window and answered
    # by the end of the drain (open loop), replied in it (closed loop)
    window = client.judged(records, seconds, requests if open_loop else None)
    if open_loop:
        attempted = sum(1 for q in requests if 0 <= q.due_s < seconds)
        failed = attempted - sum(1 for r in window if r.ok)
    else:
        attempted = len(window)
        failed = sum(1 for r in window if not r.ok)
    for r in window:
        bad = client.check_reply(r, vocab)
        if bad:
            wrong.append(bad)
    if failed:
        wrong.append(f"{failed} of {attempted} requests failed or were "
                     "not answered inside the drain limit")
    if scraper.error:
        wrong.append(scraper.error)

    scrapes = scraper.scrapes
    if len(scrapes) >= 2:
        c0 = prom.value(scrapes[0][1], "kubeinfer_engine_compiles_total")
        c1 = prom.value(scrapes[-1][1], "kubeinfer_engine_compiles_total")
        if c1 != c0:
            wrong.append(f"kubeinfer_engine_compiles_total rose from {c0} "
                         f"to {c1} inside the window")
    else:
        wrong.append("fewer than two scrapes of /metrics")

    if not device_has_peaks(device):
        wrong.append(f"the device is {device['platform']} "
                     f"{device['kind']!r}, not a TPU of the peaks table")

    # the peak was read before the child went; the chips are free now
    peak = prom.by_label(last, "kubeinfer_device_peak_bytes_in_use",
                         "device")
    device["memory_peak_bytes"] = int(max(peak.values())) if peak else 0
    check_run.url, check_run.window = None, window
    wrong += run_checks(cell, "after_exit", check_run)

    ctx = Context(seconds=seconds, setup_s=setup_s, config=conf, out=out,
                  window=window, scrapes=scrapes,
                  peaks=DEVICE_PEAKS.get(device["kind"]))
    if args.trace:
        ctx.trace = reduce_trace(out)
    metrics = read_metrics(
        cell.per_layer if args.trace else cell.end_to_end, ctx)

    late = [r.lateness_ms for r in window] if open_loop else []
    say({"workload": cell.name, "seed": args.seed, "loop": traffic["loop"],
         "requests_sent": len(records),
         "requests_replied": sum(1 for r in records if r.done_s),
         "queue_depth_open_close": [
             prom.value(s[1], "kubeinfer_engine_queue_depth")
             for s in (scrapes[:1] + scrapes[-1:])],
         "generator_lateness_ms": {
             "p50": percentile(late, 50) if late else None,
             "max": max(late) if late else None},
         "setup_phases_s": phases,
         "notes": ctx.notes + check_run.notes, "wrong": wrong[:20]})
    result = {"correct": not wrong, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace:
        devs = (ctx.trace or {}).get("devices") or {}
        for name, d in sorted(devs.items()):
            say({"trace_device": name, "busy_s": d["busy_s"],
                 "window_s": d["window_s"],
                 "idle_share": 1 - d["busy_s"] / d["window_s"],
                 "programs": d["modules"][:8]})
        if devs:
            n = len(devs)
            device["busy_s"] = sum(d["busy_s"] for d in devs.values()) / n
            device["window_s"] = sum(
                d["window_s"] for d in devs.values()) / n
            top = sorted(devs.items())[0][1]
            result["breakdown"] = {
                "device_ops": [list(r) for r in trace.by_label(top["ops"])],
                "idle_gaps": top["gaps"][:10]}
        # what the same run read end to end, for the cost of tracing
        result["end_to_end_while_traced"] = {
            k: v["value"]
            for k, v in read_metrics(cell.end_to_end, ctx).items()}
    # every number compared, beside its limit: in the line, last, and
    # as the last lines on standard error
    compared = {"failed_requests": [failed, 0],
                "compiled_in_window": [len(compiled), 0],
                "server_exit_code": [rc, 0], **check_run.compared}
    result["compared"] = compared
    say(result)
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except (RunFailure, cells.CellError) as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
