"""``moe.grouped_matmul``: rows x[M, K] sorted by expert times the
experts' weights w[E, K, N], out[M, N], all 16-bit.

A lower bound of the work, not what a call happens to move: the weights
of the experts that at least one row reached, read once, and the rows
that were held, read and written once. The shapes say neither (M is
every routed pair, held or not; E every held expert, reached or not), so
the caller gives both from the server's counters as means per MoE call:
``reached`` experts and ``rows`` held pairs. An expert no row reached
is never counted, and never read. Neither is held to the shape: a
counter that runs past it has to show, as a share over 100.
"""

from __future__ import annotations

import re

PEAK = "bf16_flops"
_W = re.compile(r"\bbf16\[(\d+),(\d+),(\d+)\]")
_X = re.compile(r"\bbf16\[(\d+),(\d+)\]")


def count(m: int, k: int, n: int, e: int, reached: float,
          rows: float) -> tuple[float, float]:
    """(operations, bytes) of one call."""
    ops = 2 * rows * k * n
    moved = 2 * (reached * k * n + rows * k + rows * n)
    return ops, moved


def shapes_from_hlo(text: str) -> tuple[int, int, int, int] | None:
    """(M, K, N, E) from the instruction's HLO text: the weights are the
    one 3-d operand [E, K, N], the rows the 2-d operand whose second
    extent is K."""
    w = _W.search(text)
    if not w:
        return None
    e, k, n = (int(x) for x in w.groups())
    for a, b in _X.findall(text):
        if int(b) == k:
            return int(a), k, n, e
    return None
