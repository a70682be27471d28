"""A statistic over the client's own records.

args: ``field`` (a number on lib.client.Record: req_ms, ttft_ms, tpot_ms,
overhead_ms, lateness_ms, wall_ms, n_tokens), ``stat`` (p<q>, mean, max,
sum_per_s), ``population`` ("window": the requests the cell judges, due
in the window for an open loop and replied in it for a closed one;
"completed": every request replied inside the window).
"""

from lib.stats import percentile, rate


def read(args, ctx):
    recs = ctx.completed if args.get("population") == "completed" \
        else ctx.window
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
