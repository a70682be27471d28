"""A gauge, sampled once a second through the window.

args: ``series``, ``labels``, ``agg`` (mean or max over the samples; a
labelled series is first reduced over its label values by ``over``:
sum, the default, or max), ``scale``. Gauges such as occupancy are the
server's own sliding-window snapshots: they are sampled, never
differenced.
"""

from lib import prom


def read(args, ctx):
    over = max if args.get("over") == "max" else sum
    vals = []
    for _, text in ctx.scrapes:
        want = args.get("labels") or {}
        got = [v for have, v in prom.samples(text, args["series"])
               if all(f'{k}="{w}"' in have for k, w in want.items())]
        if got:
            vals.append(over(got))
    if not vals:
        return None
    agg = max(vals) if args["agg"] == "max" else sum(vals) / len(vals)
    return agg * args.get("scale", 1.0)
