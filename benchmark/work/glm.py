"""Operations and bytes that a dense GLM solve needs, from its shapes.

One value-and-gradient evaluation reads the design once (a fused pass; the
margins ``X w`` and the gradient ``X' r`` from the same read), with the
labels, offsets and weights beside it, and does two multiply-adds per entry.
An L-BFGS solve of ``k`` iterations needs ``k + 1`` evaluations: one at the
start and one per accepted step. Trial points that a line search rejects are
not required work, so they lower a share that is computed from this count.
"""

from __future__ import annotations


def pass_work(rows: int, dim: int, itemsize: int = 4) -> tuple[float, float]:
    """``(flops, bytes)`` of one value-and-gradient pass over ``rows x dim``."""
    flops = 4.0 * rows * dim + 8.0 * rows
    bytes_ = float(rows) * dim * itemsize + 12.0 * rows + 8.0 * dim
    return flops, bytes_


def solve_passes(iterations: int) -> int:
    return int(iterations) + 1
