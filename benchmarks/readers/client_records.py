"""A statistic over the client's own records of the requests the cell
judges: for an open loop those due inside the window and answered by
the end of the drain, whenever the reply came; for a closed loop those
replied inside the window, its only population.

args: ``field`` (a number on lib.client.Record: req_ms, ttft_ms, tpot_ms,
overhead_ms, lateness_ms, wall_ms, n_tokens), ``stat`` (p<q>, mean, max,
sum_per_s). ``n_tokens`` as ``sum_per_s`` over an open loop is the
offered token rate less what failed or was never answered: it does not
move with the server's speed, which is what the latencies are for:
counted over the replies that land inside the window instead, a slow
server is credited with the ramp's backlog draining into it and a fast
one, which has answered that before the window opens, reads as a loss
(PERF.md, section 2).
"""

from lib.stats import percentile, rate


def read(args, ctx):
    recs = ctx.window
    name = args["field"]
    values = [len(r.tokens) if name == "n_tokens" else getattr(r, name)
              for r in recs if r.ok
              and (name != "tpot_ms" or len(r.tokens) > 1)]
    stat = args["stat"]
    if stat == "sum_per_s":
        return rate(sum(values), ctx.seconds)
    if not values:
        return None
    if stat == "mean":
        return sum(values) / len(values)
    if stat == "max":
        return max(values)
    if stat.startswith("p"):
        return percentile(values, float(stat[1:]))
    raise ValueError(f"unknown stat {stat!r}")
