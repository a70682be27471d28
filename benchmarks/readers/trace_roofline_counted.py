"""A kernel's share of its roofline where the shapes alone do not say
how much work a call had: ``trace_roofline`` with the missing extents
taken from the server's counters.

args: ``match`` and ``opcount`` as ``trace_roofline``; ``counted``: for
each further argument of the opcount file's ``count`` a ratio of
counter changes over the window ({"num": [...], "den": [...]}, terms as
``prometheus_delta``), the mean per call. The same mean goes to every
call in the trace: ``count`` is linear in these arguments, so the sum
over calls is right where the traced stretch is like the window.
Nothing to read (a server without the counters, a trace without the
kernel) gives nothing.
"""

from lib import trace
from lib.cell import opcount
from lib.stats import ratio
from readers.prometheus_delta import delta


def read(args, ctx):
    if not ctx.trace or not ctx.trace["devices"] or not ctx.peaks \
            or len(ctx.scrapes) < 2:
        return None
    first, last = ctx.scrapes[0][1], ctx.scrapes[-1][1]
    counted = {}
    for name, spec in args["counted"].items():
        num = delta(spec["num"], first, last)
        den = delta(spec["den"], first, last)
        if num is None or den is None or ratio(num, den) is None:
            return None
        counted[name] = ratio(num, den)
    oc = opcount(args["opcount"])
    flops, bw = ctx.peaks[oc.PEAK], ctx.peaks["hbm_bytes_per_s"]
    shares = []
    for _, dev, rows in trace.matching_ops(ctx.trace, args["match"]):
        least = spent = 0.0
        for key, self_s, n_calls in rows:
            shape = oc.shapes_from_hlo(key)
            if shape is None:
                ctx.notes.append(
                    f"{args['opcount']}: no shapes in {key[:120]}")
                return None
            ops, moved = oc.count(*shape, **counted)
            least += n_calls * max(ops / flops, moved / bw)
            spent += self_s
        if spent > 0:
            shares.append(100.0 * least / spent)
            ctx.notes.append(
                f"{args['opcount']}: least {least:.4f} s, measured "
                f"{spent:.4f} s, per call {counted}")
    return sum(shares) / len(shares) if shares else None
