"""The dense logistic design of ``gen/glm_dense.py`` in the stacked layout that
the mesh path trains on: ``(chips, rows_per_chip, dim)``, block ``i`` on chip
``i``.

Drawn under ``shard_map``, every chip its own rows: the column scales, the
planted model and the seed's column signs are the same on every chip, the
rows of chip ``i`` from the problem's row key folded with ``i``. No chip draws
another's rows and no host array of the design's size exists.
"""

from __future__ import annotations

import functools

import jax
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from benchmark.gen import common


def generate(seed: int, workload: dict, config: dict, mesh) -> dict:
    """``{"x": (chips, rows, dim), "y": (chips, rows)}``, the leading axis
    laid over the mesh's one axis."""
    (axis,) = mesh.axis_names
    rows = int(workload["rows_per_chip"])
    dim = int(config["dim"])
    chunk = int(workload["row_chunk"])
    if rows % chunk:
        raise ValueError(f"row_chunk {chunk} does not divide {rows} rows")

    def chip_rows(k_problem, k_signs):
        scale, w_true, k_rows = common.problem(
            k_problem, k_signs, dim, workload["column_scale_decades"])
        block = functools.partial(
            common.block, scale=scale, w_true=w_true, chunk=chunk,
            nnz=int(workload["nnz_per_row"]))
        k_chip = jax.random.fold_in(k_rows, lax.axis_index(axis))
        xs, ys = lax.map(block, jax.random.split(k_chip, rows // chunk))
        return xs.reshape(1, rows, dim), ys.reshape(1, rows)

    draw = jax.jit(shard_map(chip_rows, mesh=mesh, in_specs=P(),
                             out_specs=(P(axis), P(axis))))
    x, y = draw(*common.keys(seed, workload))
    return {"x": x, "y": y}
