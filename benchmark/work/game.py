"""Operations and bytes that one coordinate-descent sweep of a GLMix model
needs, from its shapes and the window's counts.

Every solve is L-BFGS over a logistic objective, and one value-and-gradient
evaluation reads its rows' design once (margins and gradient from the same
read) with the labels, offsets and weights beside it: ``work/glm.py``'s pass.
The fixed effect needs ``iterations + 1`` passes over all rows; a random
effect's lane (one entity) needs ``its iterations + 1`` passes over its own
REAL rows. Nothing else is required work: not a padded row or lane, not a
trial point a line search rejected, not a pass the batch ran for a lane that
had already ended. The sum over lanes is taken where the lanes are, on the
device: a ``game.re.solve`` span carries ``row_iterations`` (the sum over its
lanes of rows x iterations) and ``row_evaluations`` (rows x evaluations).
"""

from __future__ import annotations

from benchmark.work.glm import pass_work


def lanes_work(row_passes: float, lane_passes: float, dim: int,
               itemsize: int = 4) -> tuple[float, float]:
    """``(flops, bytes)`` of per-entity passes: ``row_passes`` rows read in
    all (each with its three per-row vectors), ``lane_passes`` coefficient
    vectors read and gradients written."""
    flops = 4.0 * row_passes * dim + 8.0 * row_passes
    bytes_ = row_passes * (dim * itemsize + 12.0) + 8.0 * dim * lane_passes
    return flops, bytes_


def fixed_work(solves: list[dict], itemsize: int = 4) -> tuple[float, float]:
    """Required work of the window's fixed-effect solves (``glm.solve``
    records: ``rows``, ``dim``, ``iterations``)."""
    flops = bytes_ = 0.0
    for s in solves:
        f, b = pass_work(int(s["rows"]), int(s["dim"]), itemsize)
        flops += f * (int(s["iterations"]) + 1)
        bytes_ += b * (int(s["iterations"]) + 1)
    return flops, bytes_


def random_work(solves: list[dict], *, count: str,
                itemsize: int = 4) -> tuple[float, float]:
    """Work of the window's bucket solves (``game.re.solve`` records).
    ``count="iterations"``: the required passes, ``iterations + 1`` a lane.
    ``count="evaluations"``: every evaluation a lane made, a rejected trial
    point counted as work done (what a kernel's share of its roofline is
    held against)."""
    flops = bytes_ = 0.0
    for s in solves:
        if count == "iterations":
            rows = float(s["row_iterations"]) + float(s["rows"])
            lanes = float(s["iterations"]) + float(s["lanes"])
        else:
            rows, lanes = float(s["row_evaluations"]), float(s["evaluations"])
        f, b = lanes_work(rows, lanes, int(s["dim"]), itemsize)
        flops, bytes_ = flops + f, bytes_ + b
    return flops, bytes_
