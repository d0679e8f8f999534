"""Dense logistic design drawn on the device from the seed.

The shape of ``bench.py::_make_problem``: about ``nnz_per_row`` of ``dim``
entries a row are non-zero (here a Bernoulli mask of that density), a value is
normal / sqrt(nnz) times its column's scale, the column scales are log-uniform
over ``column_scale_decades``, and labels come from a planted model whose
coefficient is normal / scale. The seed draws all of it: the column scales,
the planted model and every row. No host array of the design's size exists:
the rows are drawn block by block inside one jitted call.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.gen.common import key_from_seed


def generate(seed: int, workload: dict, config: dict) -> dict:
    """``{"x": (rows, dim), "y": (rows,)}`` on the device."""
    rows = int(workload["rows_per_chip"])
    dim = int(config["dim"])
    chunk = int(workload["row_chunk"])
    nnz = int(workload["nnz_per_row"])
    lo, hi = workload["column_scale_decades"]
    if rows % chunk:
        raise ValueError(f"row_chunk {chunk} does not divide {rows} rows")

    @jax.jit
    def draw(key):
        k_scale, k_model, k_rows = jax.random.split(key, 3)
        scale = jnp.power(10.0, jax.random.uniform(
            k_scale, (dim,), jnp.float32, lo, hi))
        w_true = jax.random.normal(k_model, (dim,), jnp.float32) / scale

        def block(k):
            kn, km, ky = jax.random.split(k, 3)
            v = jax.random.normal(kn, (chunk, dim), jnp.float32) * (
                scale / math.sqrt(nnz))
            x = jnp.where(jax.random.uniform(km, (chunk, dim), jnp.float32)
                          < nnz / dim, v, 0.0)
            m = jnp.sum(x * w_true, axis=-1)
            y = jax.random.uniform(ky, (chunk,), jnp.float32) \
                < jax.nn.sigmoid(m)
            return x, y.astype(jnp.float32)

        xs, ys = lax.map(block, jax.random.split(k_rows, rows // chunk))
        return xs.reshape(rows, dim), ys.reshape(rows)

    x, y = draw(key_from_seed(seed))
    return {"x": x, "y": y}
