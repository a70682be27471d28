"""A kernel's share of its roofline, in percent, from the device trace.

args: ``match`` (pattern over the operation's HLO line),
``opcount`` (the file under opcount/ that counts one call from its
shapes). For every matching HLO instruction the least time the chip
could take is the larger of operations over the compute peak and bytes
over the memory peak; the value is 100 * (sum of least times over all
calls) / (the kernel's measured self time), the mean over the devices.
Which bound it was goes to the run's notes. An instruction whose shapes
cannot be read makes the reader return nothing: a share over part of
the calls would be a wrong number.
"""

from lib import trace
from lib.cell import opcount


def read(args, ctx):
    if not ctx.trace or not ctx.trace["devices"] or not ctx.peaks:
        return None
    oc = opcount(args["opcount"])
    flops, bw = ctx.peaks[oc.PEAK], ctx.peaks["hbm_bytes_per_s"]
    shares = []
    for _, dev, rows in trace.matching_ops(ctx.trace, args["match"]):
        least = t_ops = t_bytes = spent = 0.0
        for key, self_s, n_calls in rows:
            shape = oc.shapes_from_hlo(key)
            if shape is None:
                ctx.notes.append(
                    f"{args['opcount']}: no shapes in {key[:120]}")
                return None
            ops, moved = oc.count(*shape)
            least += n_calls * max(ops / flops, moved / bw)
            t_ops += n_calls * ops / flops
            t_bytes += n_calls * moved / bw
            spent += self_s
        if spent > 0:
            shares.append(100.0 * least / spent)
            ctx.notes.append(
                f"{args['opcount']}: bound by "
                f"{'compute' if t_ops > t_bytes else 'memory'} "
                f"(compute {t_ops:.4f} s, memory {t_bytes:.4f} s, "
                f"measured {spent:.4f} s)")
    return sum(shares) / len(shares) if shares else None
