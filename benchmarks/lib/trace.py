"""From a profiler trace to numbers: device busy and idle time, time by
operation, the longest idle gaps.

Two halves. ``extract`` reads the profiler's ``.xplane.pb`` with
``jax.profiler.ProfileData`` (so it runs in a process of its own, held
to the CPU) and groups the device's events by HLO instruction. The
rest is plain arithmetic on ``(name, start, duration)`` tuples, which
the tests check on a small recorded trace.

On a TPU plane the "XLA Ops" line holds one event per executed HLO
instruction; a ``while`` or a ``call`` spans its body's events, so a
sum of durations counts the body twice. ``self_times`` charges every
nanosecond to the innermost event that covers it; busy time is the
union of all events.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def union_ns(intervals) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def self_times(events):
    """``events``: (key, start, dur). Yields (key, self_ns): the event's
    duration less what the events nested inside it cover."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    stack: list[list] = []  # [key, end, child_ns, dur]
    out = []

    def close(until):
        while stack and stack[-1][1] <= until:
            key, _, child, dur = stack.pop()
            out.append((key, max(0, dur - child)))
            if stack:
                stack[-1][2] += dur

    for key, start, dur in evs:
        close(start)
        stack.append([key, start + dur, 0, dur])
    close(float("inf"))
    return out


def gaps(events, top: int = 10):
    """The longest stretches in which nothing ran, each labelled by the
    operation that ended it. What the host was doing meanwhile needs
    host spans on the device's clock, which the program does not write
    yet (PERF.md, list for the tracing issue)."""
    evs = sorted(events, key=lambda e: e[1])
    found, end = [], None
    for key, start, dur in evs:
        if end is not None and start > end:
            found.append((start - end, key))
        end = max(end or 0, start + dur)
    found.sort(reverse=True)
    return [(f"before:{label(key)}", ns / 1e9) for ns, key in found[:top]]


def reduce_plane(ops, modules=()):
    """One device's numbers from its op events (name, start, dur)."""
    ops = list(ops)
    if not ops:
        return None
    t0 = min(s for _, s, _ in ops)
    t1 = max(s + d for _, s, d in ops)
    by_name: dict = {}
    for key, ns in self_times(ops):
        row = by_name.setdefault(key, [0, 0])
        row[0] += ns
        row[1] += 1
    by_mod: dict = {}
    for key, _, dur in modules:
        row = by_mod.setdefault(key, [0, 0])
        row[0] += dur
        row[1] += 1
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": union_ns((s, s + d) for _, s, d in ops) / 1e9,
        "ops": sorted(([k, v[0] / 1e9, v[1]] for k, v in by_name.items()),
                      key=lambda r: -r[1]),
        "modules": sorted(([k, v[0] / 1e9, v[1]]
                           for k, v in by_mod.items()), key=lambda r: -r[1]),
        "gaps": [list(g) for g in gaps(ops)],
    }


_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPERAND = re.compile(r"\s*%[\w.\-]+")
_SERIAL = re.compile(r"\.\d+$")


def label(hlo: str, limit: int = 160) -> str:
    """A short name for one HLO instruction as the TPU trace prints it
    (the whole HLO line): ``kind(operand types)->result type``, without
    layouts, operand names and serial numbers, so the 28 layers' copies
    of one operation share a label."""
    head, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo[:limit]
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):  # a tuple of results
        cut = rest.find(") ") + 1
        result, call = rest[:cut], rest[cut + 1:]
    else:
        result, _, call = rest.partition(" ")
    kind, _, operands = call.partition("(")
    operands = _OPERAND.sub("", operands.split("), ")[0].rstrip(")"))
    name = _SERIAL.sub("", head.lstrip("%"))
    if kind == "fusion":
        kind = name  # the fusion's own name says what it fuses
    return f"{kind}({operands})->{result}"[:limit]


def by_label(ops, top: int = 10):
    """(label, seconds) of the labels that took most self time."""
    total: dict = {}
    for key, self_s, _ in ops:
        lab = label(key)
        total[lab] = total.get(lab, 0.0) + self_s
    return sorted(total.items(), key=lambda kv: -kv[1])[:top]


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def extract(trace_dir: str, plane_pattern: str = DEVICE_PLANE) -> dict:
    """Every device plane of the trace, reduced. A TPU plane names an
    operation by its whole HLO line, operand types included, which is
    what the kernel readers match and parse; the profiler's stats on
    an event add nothing to it (PR 25 looked)."""
    from jax.profiler import ProfileData  # the only use of jax here

    path = find_xplane(trace_dir)
    if path is None:
        return {"devices": {}, "planes": []}
    data = ProfileData.from_file(path)
    devices, planes = {}, []
    for plane in data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        planes.append([plane.name, sorted(lines)])
        if not re.match(plane_pattern, plane.name):
            continue
        ops, mods = [], []
        if OPS_LINE in lines:
            for ev in lines[OPS_LINE].events:
                ops.append((ev.name, int(ev.start_ns), int(ev.duration_ns)))
        if MODULES_LINE in lines:
            for ev in lines[MODULES_LINE].events:
                mods.append((ev.name, int(ev.start_ns),
                             int(ev.duration_ns)))
        reduced = reduce_plane(ops, mods)
        if reduced is not None:
            devices[plane.name] = reduced
    return {"devices": devices, "planes": planes}


def matching_ops(summary: dict, pattern: str):
    """For each device, the (HLO line, self seconds, count) rows that
    match ``pattern``."""
    rx = re.compile(pattern)
    for name, dev in summary["devices"].items():
        yield name, dev, [r for r in dev["ops"] if rx.search(r[0])]


def main(argv) -> int:
    """``python lib/trace.py <trace dir> <summary.json>``: the
    reduction, run after the server has gone, held to the CPU."""
    import json

    summary = extract(argv[1])
    with open(argv[2], "w", encoding="utf-8") as f:
        json.dump(summary, f)
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
