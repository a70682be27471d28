"""Model operations of the tokens the server computed in the window, as
a share of what the chips could have done in the time its step programs
ran, in percent.

args: ``parts``: for each part that sizes/<model_type>.py's
``flops_per_token(conf)`` prices (``layers``: one token through the
decoder stack; ``head``: one position's logits), the counters whose
change over the window counts that part's tokens, each {series, labels,
times} with ``times`` 1 or -1; ``time``: the counters of seconds whose
change is the time the step programs ran; ``peak``: the row of
lib/peaks.py that applies. The value is 100 * sum over parts
(operations per token * tokens) / (time * peak * chips). Operations the
program runs for nothing (bucket padding, retired rows of a decode
batch) are not among the counted tokens, so this is the useful share of
the whole step: it rises when any part of a step gets faster, and it
stays a bound on a claim when a kernel's own roofline matches nothing
any more. Over the window's own length it would be a constant of the
schedule below the knee, which is why the time is the steps' own.
"""

from lib.cell import sizes
from readers.prometheus_delta import delta


def read(args, ctx):
    if len(ctx.scrapes) < 2 or not ctx.peaks:
        return None
    first, last = ctx.scrapes[0][1], ctx.scrapes[-1][1]
    conf = ctx.config
    price = sizes(conf["model_type"]).flops_per_token(conf)
    tokens = {part: delta(terms, first, last)
              for part, terms in args["parts"].items()}
    seconds = delta(args["time"], first, last)
    if None in tokens.values() or not seconds or seconds < 0:
        return None
    ops = sum(price[part] * n for part, n in tokens.items())
    if ops <= 0:
        return None
    return 100.0 * ops / (seconds * ctx.peaks[args["peak"]] * conf["chips"])
