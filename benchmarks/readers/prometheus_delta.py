"""Change of counters over the window, as a ratio.

args: ``num`` and ``den``, each a list of {series, labels, times}
(``times`` 1, the default, or -1); ``scale``.
The value is scale * (sum of num deltas) / (sum of den deltas) between
the scrape at the window's opening and the one at its close. Histogram
``_sum``/``_count`` series are counters like any other; bucket quantiles
are never used (the buckets jump 0.1 -> 0.25 s around today's step).
"""

from lib import prom
from lib.stats import ratio


def delta(terms, first, last):
    """Sum of the terms' changes between two pages; None where a series
    is absent from the last."""
    total = 0.0
    for t in terms:
        a = prom.value(first, t["series"], t.get("labels"))
        b = prom.value(last, t["series"], t.get("labels"))
        if b is None:
            return None
        total += t.get("times", 1) * (b - (a or 0.0))
    return total


def read(args, ctx):
    if len(ctx.scrapes) < 2:
        return None
    first, last = ctx.scrapes[0][1], ctx.scrapes[-1][1]
    num = delta(args["num"], first, last)
    den = delta(args["den"], first, last)
    if num is None or den is None:
        return None
    r = ratio(num, den)
    return None if r is None else r * args.get("scale", 1.0)
