"""``gdn.gdn_decode_step``: one token of the gated delta rule for every
slot. Operands x f32[B, Hv, 8, Dk] (q, k, beta v, the two gates, padded
to a tile) and the state f32[B, Hv, Dk, Dv], which is updated in place.

A lower bound of the work, not what a call happens to move: for the
rows that are decoding, the state read once and written once and the
step's vectors (q, k, v and two scalars a head in, o out). A slot that
holds no decoding request needs nothing, so the shapes do not say how
much work a call had (B is every slot): the caller gives ``rows``, the
mean decoding rows a step, from the server's counters. A kernel that
walks idle slots reads low here, and one that skips them can reach 100.
Per state element a decay, a multiply-add into S^T k, a multiply-add of
the write and a multiply-add into S^T q: 7 operations. The arithmetic
is float32 on the vector unit, far under any peak of the table; the
bound is the memory's, and the compute peak named here only has to
exist.
"""

from __future__ import annotations

import re

PEAK = "bf16_flops"
_STATE = re.compile(r"\bf32\[(\d+),(\d+),(\d+),(\d+)\]")


def count(b: int, h: int, dk: int, dv: int,
          rows: float) -> tuple[float, float]:
    """(operations, bytes) of one call in which ``rows`` of the ``b``
    slots were decoding."""
    ops = 7 * rows * h * dk * dv
    moved = 4 * rows * h * (2 * dk * dv + 2 * dk + dv + 2 + dv)
    return ops, moved


def shapes_from_hlo(text: str) -> tuple[int, int, int, int] | None:
    """(B, Hv, Dk, Dv): the state is the 4-d float32 operand whose third
    extent is not the vectors' tile of 8."""
    for dims in _STATE.findall(text):
        b, h, dk, dv = (int(x) for x in dims)
        if dk != 8:
            return b, h, dk, dv
    return None
