"""Operations and bytes that a sparse GLM solve needs, from its shapes.

One value-and-gradient evaluation has to read every real entry once: its
value and its index, 8 bytes, and two multiply-adds (the margin ``v w[col]``
and the transpose ``v r[row]``), 4 operations; the labels, offsets and weights
beside them, 12 bytes and 8 operations a row; and two coefficient-length
vectors (``w`` read, the gradient written). Chunk padding and the second copy
of the entries that a dual layout keeps are the program's choice, not
required work, so they lower a share that is computed from this count. An
L-BFGS solve of ``k`` iterations needs ``k + 1`` evaluations; trial points
that a line search rejects are not required work either.
"""

from __future__ import annotations


def pass_work(entries: int, rows: int, dim: int) -> tuple[float, float]:
    """``(flops, bytes)`` of one value-and-gradient pass."""
    flops = 4.0 * entries + 8.0 * rows
    bytes_ = 8.0 * entries + 12.0 * rows + 8.0 * dim
    return flops, bytes_


def solve_passes(iterations: int) -> int:
    return int(iterations) + 1
