"""Dense logistic design drawn on the device, block by block.

The shape of ``bench.py::_make_problem``: about ``nnz_per_row`` of ``dim``
entries a row are non-zero (here a Bernoulli mask of that density), a value is
normal / sqrt(nnz) times its column's scale, the column scales are log-uniform
over ``column_scale_decades``, and labels come from a planted model whose
coefficient is normal / scale. What ``--seed`` draws of it, and what the
workload's ``problem_seed`` fixes, is ``gen/common.py``'s. No host array of
the design's size exists: the rows are drawn inside one jitted call.
"""

from __future__ import annotations

import functools

import jax
from jax import lax

from benchmark.gen import common


def generate(seed: int, workload: dict, config: dict) -> dict:
    """``{"x": (rows, dim), "y": (rows,)}`` on the device."""
    rows = int(workload["rows_per_chip"])
    dim = int(config["dim"])
    chunk = int(workload["row_chunk"])
    if rows % chunk:
        raise ValueError(f"row_chunk {chunk} does not divide {rows} rows")

    @jax.jit
    def draw(k_problem, k_signs):
        scale, w_true, k_rows = common.problem(
            k_problem, k_signs, dim, workload["column_scale_decades"])
        block = functools.partial(
            common.block, scale=scale, w_true=w_true, chunk=chunk,
            nnz=int(workload["nnz_per_row"]))
        xs, ys = lax.map(block, jax.random.split(k_rows, rows // chunk))
        return xs.reshape(rows, dim), ys.reshape(rows)

    x, y = draw(*common.keys(seed, workload))
    return {"x": x, "y": y}
