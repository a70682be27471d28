"""Find a cell's knee, once: ``python benchmarks/sweep.py --workload
<name> --rates 0.5,0.65,0.85 --seconds 30``.

One server, one warm-up, then the cell's own traffic at each rate in
turn (the same arrival pattern, scaled). For each rate: requests
completed per second over offered, queue depth at the window's opening
and close, and the latencies. The knee is the highest rate at which
completed is at least 0.95 of offered and the queue is no deeper at
the close than at the opening. Not part of a check: PERF.md keeps the
sweep, the cell's file the chosen rate.
"""

from __future__ import annotations

import argparse
import os
import shutil
import sys
import time

import run as bench
from lib import cell as cells
from lib import client, prom, schedule
from lib.stats import percentile


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=int, default=30)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    conf, traffic = cell.config, cell.traffic
    vocab, seconds = conf["vocab_size"], float(args.seconds)
    out = os.path.join(cells.CHECKOUT, ".bench_out", cell.name + ".sweep")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    child = bench.Child(cell, out)
    try:
        child.wait_healthy()
        plans = [(float(r), *schedule.open_schedule(traffic, float(r),
                                                    seconds))
                 for r in args.rates.split(",")]
        wrong = bench.warm_up(child, cell.spec["warmup"], args.seed, vocab,
                              [])
        bench.say({"warm_up_wrong": wrong,
                   "setup_s": time.monotonic() - bench.T_START})
        rows: list[dict] = []
        for phase, (rate, requests, preload) in enumerate(plans):
            # another seed for each rate: the same token ids again would
            # be served from the prefix cache
            seed = args.seed + 1000 * (phase + 1)
            bench.warm_up(child, [], seed, vocab, preload)
            t_open = time.monotonic() + traffic["ramp_s"]
            loop = client.OpenLoop(child.url, requests, seed, vocab,
                                   bench.REQUEST_LIMIT_S)
            loop.start(t_open)
            time.sleep(max(0.0, t_open - time.monotonic()))
            q0 = prom.value(child.metrics(), "kubeinfer_engine_queue_depth")
            time.sleep(max(0.0, t_open + seconds - time.monotonic()))
            q1 = prom.value(child.metrics(), "kubeinfer_engine_queue_depth")
            limit = time.monotonic() + 60.0
            while loop.outstanding(seconds) and time.monotonic() < limit:
                child.alive()
                time.sleep(0.1)
            loop.stop()
            recs = loop.snapshot()
            done = [r for r in recs if r.ok and 0 <= r.done_s <= seconds]
            due = [r for r in recs if r.ok and 0 <= r.due_s < seconds]
            offered = sum(1 for q in requests if 0 <= q.due_s < seconds)
            ttft = [r.ttft_ms for r in due]
            tpot = [r.tpot_ms for r in due if len(r.tokens) > 1]
            rows.append({
                "rate_req_s": rate, "offered": offered,
                "offered_per_s": offered / seconds,
                "completed_per_s": len(done) / seconds,
                "answered_of_due": len(due),
                "queue_depth": [q0, q1],
                "out_tok_s": sum(len(r.tokens) for r in done) / seconds,
                "ttft_p50_ms": percentile(ttft, 50) if ttft else None,
                "ttft_p90_ms": percentile(ttft, 90) if ttft else None,
                "tpot_p50_ms": percentile(tpot, 50) if tpot else None,
            })
            bench.say(rows[-1])
            # let the queue empty before the next rate
            limit = time.monotonic() + 60.0
            while time.monotonic() < limit and any(
                    not r.done_s for r in loop.snapshot()):
                time.sleep(0.2)
        rc = child.stop()
        held = [r["rate_req_s"] for r in rows
                if r["completed_per_s"] >= 0.95 * r["offered_per_s"]
                and r["answered_of_due"] == r["offered"]
                and (r["queue_depth"][1] or 0) <= (r["queue_depth"][0] or 0)]
        bench.say({"server_exit": rc, "knee_req_s": max(held, default=None)})
    finally:
        child.kill()
    return 0


if __name__ == "__main__":
    sys.exit(main())
