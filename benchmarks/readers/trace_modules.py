"""Share of device time by program, from the trace's "XLA Modules"
line, the mean over the devices.

args: ``match`` (pattern searched in the module's name, which is
``jit_<function>`` plus the profiler's serial number), ``stat``:
"share_of_busy", the matching programs' time over device busy time. A
program's time is its module events' durations, idle stretches inside
a program included, so the shares of all programs can add up to a
little over 1.
"""

import re


def read(args, ctx):
    if not ctx.trace or not ctx.trace["devices"]:
        return None
    if args["stat"] != "share_of_busy":
        raise ValueError(f"unknown stat {args['stat']!r}")
    rx = re.compile(args["match"])
    shares = []
    for dev in ctx.trace["devices"].values():
        if not dev.get("modules") or dev["busy_s"] <= 0:
            continue
        took = sum(row[1] for row in dev["modules"] if rx.search(row[0]))
        shares.append(took / dev["busy_s"])
    return sum(shares) / len(shares) if shares else None
