"""Shares of device time from the trace, the mean over the devices.

args: ``stat``: "idle_share" (1 - busy/window) or "share_of_busy" (self
time of the operations whose HLO line matches ``match``, over busy
time).
"""

from lib import trace


def read(args, ctx):
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    devs = ctx.trace["devices"]
    if args["stat"] == "idle_share":
        shares = [1.0 - d["busy_s"] / d["window_s"] for d in devs.values()
                  if d["window_s"] > 0]
    elif args["stat"] == "share_of_busy":
        shares = [sum(r[1] for r in rows) / dev["busy_s"]
                  for _, dev, rows in trace.matching_ops(
                      ctx.trace, args["match"]) if dev["busy_s"] > 0]
    else:
        raise ValueError(f"unknown stat {args['stat']!r}")
    return sum(shares) / len(shares) if shares else None
