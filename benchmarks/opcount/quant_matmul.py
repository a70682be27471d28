"""``weight_quant.quant_matmul``: x[M, K] (bf16) times int8 qw[K, N] with
one f32 scale per output column, out[M, N] in x's type.

What the algorithm needs, not what the kernel happens to move: every
operand is read once and the result written once. The kernel pads all
three operands to whole 128-tiles; padding is the kernel's cost, so the
counts use the padded shapes only where the caller passes them. The
multiply runs in x's type (the int8 tile is cast up), so the compute
peak that applies is the bf16 one.
"""

from __future__ import annotations

import re

PEAK = "bf16_flops"
_SHAPE = re.compile(r"\b(bf16|f32|s8)\[(\d+),(\d+)\]")


def count(m: int, k: int, n: int, x_bytes: int = 2) -> tuple[int, int]:
    """(operations, bytes) of one call."""
    ops = 2 * m * k * n
    moved = m * k * x_bytes + k * n + 4 * n + m * n * x_bytes
    return ops, moved


def shapes_from_hlo(text: str) -> tuple[int, int, int] | None:
    """(M, K, N) from the instruction's HLO text: the int8 operand is
    [K, N], the activation the 16- or 32-bit operand whose second
    extent is K."""
    found = _SHAPE.findall(text)
    qw = [(int(a), int(b)) for t, a, b in found if t == "s8"]
    if not qw:
        return None
    k, n = qw[0]
    for t, a, b in found:
        if t != "s8" and int(b) == k and int(a) != 1:
            return int(a), k, n
    return None
