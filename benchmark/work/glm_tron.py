"""Operations and bytes that a dense GLM solve by trust-region Newton needs,
from its shapes and its counts.

A solve of ``k`` outer iterations needs ``k + 1`` value-and-gradient passes
(``work/glm.py``'s pass: one at the start, one per trial point, and a trust
region tries exactly one point an iteration) and one Hessian-vector pass for
every product its conjugate gradients made. A product ``X'(d2 * (X v))`` reads
the design once (both contractions from the same read), the per-row weights
``d2`` beside it, ``v`` in and the product out, and does two multiply-adds per
entry. The pass that computes ``d2`` at a new iterate is not required work:
its margins were in the evaluation that accepted the iterate, so it lowers a
share that is computed from this count, as a rejected trial point does.
"""

from __future__ import annotations

from benchmark.work.glm import pass_work, solve_passes


def hvp_work(rows: int, dim: int, itemsize: int = 4) -> tuple[float, float]:
    """``(flops, bytes)`` of one Hessian-vector pass over ``rows x dim``."""
    flops = 4.0 * rows * dim
    bytes_ = float(rows) * dim * itemsize + 4.0 * rows + 8.0 * dim
    return flops, bytes_


def solves_work(rows: int, dim: int, itemsize: int,
                solves: list[tuple[int, int]]) -> dict:
    """The required work of ``solves`` (``(iterations, hvps)`` each), whole
    and the Hessian-vector passes alone.

    ``passes`` is the whole in units of one value-and-gradient pass's bytes
    (the bound that holds): the accepted reader of the evaluation kernel's
    share (``readers/kernel_roofline.py``) scales the whole by evaluations
    over ``passes``, and so gets the evaluations' own bytes."""
    pass_flops, pass_bytes = pass_work(rows, dim, itemsize)
    hvp_flops, hvp_bytes = hvp_work(rows, dim, itemsize)
    evaluations = sum(solve_passes(i) for i, _ in solves)
    products = sum(h for _, h in solves)
    bytes_ = pass_bytes * evaluations + hvp_bytes * products
    return {"flops_per_chip": pass_flops * evaluations + hvp_flops * products,
            "bytes_per_chip": bytes_, "passes": bytes_ / pass_bytes,
            "evaluation_passes": evaluations, "hvp_passes": products,
            "hvp_kernel_flops": hvp_flops * products,
            "hvp_kernel_bytes": hvp_bytes * products}
