"""What the host was doing while the device stood idle: the program's
own spans and the device's events from one profiler trace, on one
clock.

The serving loop writes ``engine.*`` spans on its scheduler thread with
``jax.profiler.TraceAnnotation`` (kubeinfer_tpu/observability/
stepprof.py lists the names); the profiler puts them on a line of the
host plane of the same ``.xplane.pb`` that holds the device's planes.
``extract`` reads both with ``jax.profiler.ProfileData`` (so it runs in
a process held to the CPU, after the server has gone). The rest is
plain arithmetic on dicts and tuples, which the tests check on a small
sample recorded on the chip.

A sample (what ``extract`` returns, JSON as it stands):
``spans``: [name, start ns, end ns, args] of the scheduler's line;
``devices``: per device plane ``busy`` ([start, end] stretches in which
some operation ran, merged across gaps under ``min_gap_ns``),
``modules`` ([name, start, end] of the "XLA Modules" line) and
``idle_ns_exact`` (window less the exact union of operations).
"""

from __future__ import annotations

import re

from lib import trace
from lib.stats import percentile

SCHEDULER_SPAN = "engine.pass"  # the line that holds it is the scheduler's
PREFIX = "engine."
UNATTRIBUTED = "unattributed"
MIN_GAP_NS = 1000  # shorter stretches between operations are not gaps
WINDOW_MODULE = r"jit_(decode|verify)_window"
DISPATCH = re.compile(r"^engine\.(decode|verify)\.dispatch$")


def merge(intervals, min_gap_ns: int = MIN_GAP_NS):
    """``(start, end)`` stretches merged where they touch, overlap or
    lie closer than ``min_gap_ns``."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s - out[-1][1] < min_gap_ns:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy):
    """The idle stretches between merged busy stretches."""
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def innermost(spans):
    """``spans``: (name, start, end, ...) properly nested, as one
    thread's TraceMe events are. Returns (start, end, name, pass index)
    pieces, in time order and without overlap: each instant is charged
    to the innermost span that covers it. The pass index counts the
    ``engine.pass`` spans; -1 outside any."""
    evs = sorted(spans, key=lambda s: (s[1], -s[2]))
    out: list[tuple[int, int, str, int]] = []
    stack: list[list] = []  # [name, end, cursor, pass index]
    n_pass = -1

    def close(until):
        while stack and stack[-1][1] <= until:
            name, end, cursor, idx = stack.pop()
            if end > cursor:
                out.append((cursor, end, name, idx))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, end, *_ in evs:
        close(start)
        if stack:
            top = stack[-1]
            end = min(end, top[1])  # a child never outlives its parent
            if start > top[2]:
                out.append((top[2], start, top[0], top[3]))
            top[2] = start
        if name == SCHEDULER_SPAN:
            n_pass += 1
            idx = n_pass
        else:  # a span whose pass the trace's edge cut belongs to none
            idx = stack[-1][3] if stack else -1
        stack.append([name, end, start, idx])
    close(float("inf"))
    return sorted(out)


def attribute(gap_list, pieces):
    """Idle nanoseconds by the innermost span that covers them; what
    no span covers goes to ``unattributed``. Both inputs in time
    order."""
    by_name: dict[str, int] = {}
    i = 0
    for g0, g1 in gap_list:
        covered = 0
        while i < len(pieces) and pieces[i][1] <= g0:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < g1:
            s, e, name, _ = pieces[j]
            part = min(e, g1) - max(s, g0)
            if part > 0:
                by_name[name] = by_name.get(name, 0) + part
                covered += part
            j += 1
        if g1 - g0 > covered:
            by_name[UNATTRIBUTED] = by_name.get(UNATTRIBUTED, 0) \
                + (g1 - g0) - covered
    return by_name


def owner(gap, pieces):
    """The span that covers most of one gap."""
    by_name = attribute([gap], pieces)
    return max(by_name.items(), key=lambda kv: kv[1])[0]


def idle_by_span(busy, spans, longer_than_ns: int = 0):
    """Rows (name, idle seconds, share of all idle time), largest
    first, over the gaps longer than ``longer_than_ns``."""
    chosen = [g for g in gaps(busy) if g[1] - g[0] > longer_than_ns]
    by_name = attribute(chosen, innermost(spans))
    total = sum(by_name.values())
    return [(name, ns / 1e9, ns / total if total else 0.0)
            for name, ns in sorted(by_name.items(), key=lambda kv: -kv[1])]


def longest_gaps(busy, spans, top: int = 10):
    """(seconds, owner, offset into the trace in seconds) of the
    longest gaps."""
    pieces = innermost(spans)
    t0 = busy[0][0] if busy else 0
    found = sorted(gaps(busy), key=lambda g: g[0] - g[1])[:top]
    return [((g1 - g0) / 1e9, owner((g0, g1), pieces), (g0 - t0) / 1e9)
            for g0, g1 in found]


def self_time_per_pass(spans):
    """name -> (passes it ran in, median ms, p90 ms) of its self time
    summed inside one ``engine.pass``."""
    per: dict[str, dict[int, int]] = {}
    for s, e, name, idx in innermost(spans):
        if idx < 0:
            continue
        row = per.setdefault(name, {})
        row[idx] = row.get(idx, 0) + (e - s)
    out = {}
    for name, row in per.items():
        ms = [ns / 1e6 for ns in row.values()]
        out[name] = (len(ms), percentile(ms, 50), percentile(ms, 90))
    return out


def clock_check(spans, modules, tolerance_ns: int = 200_000):
    """The host's spans and the device's events are on one clock if
    every window's dispatch span starts no later than its module event
    and the module ends no later than the readback that waited for it.
    A dispatch is paired with the next readback on the line, and with
    the window module whose end lies nearest to that readback's.
    Returns windows checked, violations beyond the tolerance, and the
    worst offsets seen (ns, negative = in order)."""
    line = sorted(spans, key=lambda s: s[1])
    windows = sorted((m for m in modules if re.search(WINDOW_MODULE, m[0])),
                     key=lambda m: m[2])
    checked = violations = 0
    worst_start = worst_end = None
    for n, (name, d0, _d1, *_rest) in enumerate(line):
        if not DISPATCH.match(name):
            continue
        want = name.replace(".dispatch", ".readback")
        back = next((s for s in line[n + 1:] if s[0] == want), None)
        if back is None or not windows:
            continue  # the trace ended inside this window
        mod = min(windows, key=lambda m: abs(m[2] - back[2]))
        early = d0 - mod[1]  # > 0: the module began before its dispatch
        late = mod[2] - back[2]  # > 0: it ended after its readback
        checked += 1
        if early > tolerance_ns or late > tolerance_ns:
            violations += 1
        worst_start = early if worst_start is None \
            else max(worst_start, early)
        worst_end = late if worst_end is None else max(worst_end, late)
    return {"windows": checked, "violations": violations,
            "tolerance_us": tolerance_ns / 1e3,
            "worst_module_start_before_dispatch_us":
                None if worst_start is None else worst_start / 1e3,
            "worst_module_end_after_readback_us":
                None if worst_end is None else worst_end / 1e3}


def report(sample: dict) -> dict:
    """Everything hostgaps.py prints, from one sample."""
    spans = [tuple(s) for s in sample["spans"]]
    out: dict = {"spans": len(spans), "devices": {}}
    out["self_time_ms_per_pass"] = {
        name: {"passes": n, "p50": p50, "p90": p90}
        for name, (n, p50, p90) in sorted(
            self_time_per_pass(spans).items(), key=lambda kv: -kv[1][1])}
    for name, dev in sorted(sample["devices"].items()):
        busy = [tuple(b) for b in dev["busy"]]
        mods = [tuple(m) for m in dev["modules"]]
        rows_all = idle_by_span(busy, spans)
        rows_long = idle_by_span(busy, spans, longer_than_ns=1_000_000)
        named = sum(r[2] for r in rows_long if r[0] != UNATTRIBUTED)
        out["devices"][name] = {
            "clock_check": clock_check(spans, mods),
            "idle_s": sum(r[1] for r in rows_all),
            "idle_s_exact": dev.get("idle_ns_exact", 0) / 1e9,
            "idle_by_span": [list(r) for r in rows_all],
            "idle_by_span_gaps_over_1ms": [list(r) for r in rows_long],
            "named_share_of_idle_in_gaps_over_1ms": named,
            "longest_gaps": [list(g) for g in longest_gaps(busy, spans)],
        }
    return out


def scheduler_line(lines):
    """Among (line name, events) pairs, the events of the one line that
    holds ``engine.pass`` spans. The profiler names a line after the OS
    thread, which Python does not rename before 3.14, so the scheduler
    thread (``continuous-batcher`` to Python) is found by what it
    wrote."""
    found = [evs for _, evs in lines
             if any(e[0] == SCHEDULER_SPAN for e in evs)]
    if len(found) > 1:
        raise ValueError(f"{len(found)} host lines hold {SCHEDULER_SPAN}")
    return found[0] if found else []


def extract(trace_dir: str, plane_pattern: str = trace.DEVICE_PLANE,
            min_gap_ns: int = MIN_GAP_NS) -> dict:
    """The sample of one trace directory (see the module's docstring)."""
    from jax.profiler import ProfileData  # the only use of jax here

    path = trace.find_xplane(trace_dir)
    if path is None:
        return {"spans": [], "devices": {}}
    host_lines, devices = [], {}
    for plane in ProfileData.from_file(path).planes:
        if re.match(plane_pattern, plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
                   for e in lines[trace.OPS_LINE].events] \
                if trace.OPS_LINE in lines else []
            if not ops:
                continue
            mods = [[e.name, int(e.start_ns),
                     int(e.start_ns + e.duration_ns)]
                    for e in lines[trace.MODULES_LINE].events] \
                if trace.MODULES_LINE in lines else []
            window = max(e for _, e in ops) - min(s for s, _ in ops)
            devices[plane.name] = {
                "busy": merge(ops, min_gap_ns), "modules": mods,
                "idle_ns_exact": window - trace.union_ns(ops)}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = [[e.name, int(e.start_ns),
                        int(e.start_ns + e.duration_ns), dict(e.stats)]
                       for e in ln.events if e.name.startswith(PREFIX)]
                if evs:
                    host_lines.append((ln.name, evs))
    return {"spans": scheduler_line(host_lines), "devices": devices}
