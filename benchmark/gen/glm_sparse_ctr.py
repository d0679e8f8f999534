"""Hashed click-through rows drawn on the device, block by block.

The shape of the LIBSVM ``criteo`` set: every row has one active bin for each
of its fields (the configuration's ``integer_fields`` of
``integer_buckets`` values each, then one field per entry of
``categorical_cardinalities``). Within a field of ``V`` values the id is
``floor(V ** u) - 1`` for a uniform ``u``: a Zipf law of exponent 1, so a
field's first id holds ``log 2 / log V`` of the rows. ``(field, id)`` is mixed
by a fixed 32-bit integer hash into ``dim`` bins; two fields of one row that
land in one bin stay two entries. A value is 1.0 (one-hot) times its bin's
sign. Labels come from a planted model (a normal coefficient a bin, times
``planted_scale``) plus an intercept found by bisection so that
``positive_rate`` of the rows are positive.

The workload's ``problem_seed`` draws the rows, the planted model and the
labels; ``--seed`` draws each bin's sign, the planted coefficient mirrored
with it, so that every product ``x_ij w_j`` keeps its value (``gen/common.py``).
Nothing of the design's size exists on the host.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmark.gen import common


def field_sizes(config: dict) -> list[int]:
    return ([int(config["integer_buckets"])] * int(config["integer_fields"])
            + [int(v) for v in config["categorical_cardinalities"]])


def _mix(field, ident):
    """A fixed 32-bit mix of ``(field, id)`` (the finalizer of MurmurHash3
    over ``id`` offset by the field's own odd constant)."""
    h = ident.astype(jnp.uint32) + (field.astype(jnp.uint32) + 1) * \
        jnp.uint32(0x9E3779B1)
    h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def generate(seed: int, workload: dict, config: dict) -> dict:
    """``{"cols": (rows, fields) int32, "vals": (rows, fields) float32,
    "y": (rows,) float32}`` on the device, rows in order."""
    rows = int(workload["rows"])
    block = int(workload["row_block"])
    dim = int(config["dim"])
    sizes = field_sizes(config)
    if len(sizes) != int(config["nnz_per_row"]):
        raise ValueError("one entry a field: nnz_per_row counts the fields")
    if rows % block:
        raise ValueError(f"row_block {block} does not divide {rows} rows")
    log_v = jnp.asarray(np.log(np.asarray(sizes, np.float64)), jnp.float32)
    top = jnp.asarray(np.asarray(sizes) - 1, jnp.int32)
    fields = jnp.arange(len(sizes), dtype=jnp.int32)
    rate = float(workload["positive_rate"])

    @jax.jit
    def draw(k_problem, k_signs):
        k_model, k_rows, k_labels = jax.random.split(k_problem, 3)
        w_true = float(workload["planted_scale"]) * jax.random.normal(
            k_model, (dim,), jnp.float32)
        sign = jnp.ones((dim,), jnp.float32)
        if k_signs is not None:
            sign = jnp.where(jax.random.bernoulli(k_signs, 0.5, (dim,)),
                             1.0, -1.0)
        w_signed = w_true * sign

        def one(k):
            u = jax.random.uniform(k, (block, len(sizes)), jnp.float32)
            ident = jnp.minimum(
                jnp.floor(jnp.exp(u * log_v)).astype(jnp.int32) - 1, top)
            cols = (_mix(fields, ident) % jnp.uint32(dim)).astype(jnp.int32)
            vals = sign[cols]
            return cols, vals, jnp.sum(vals * w_signed[cols], axis=-1)

        cols, vals, m = lax.map(one, jax.random.split(k_rows, rows // block))
        m = m.reshape(rows)

        def halve(_, lo_hi):
            lo, hi = lo_hi
            mid = 0.5 * (lo + hi)
            over = jnp.mean(jax.nn.sigmoid(m + mid)) > rate
            return jnp.where(over, lo, mid), jnp.where(over, mid, hi)

        lo, hi = lax.fori_loop(0, 40, halve,
                               (jnp.float32(-30.0), jnp.float32(30.0)))
        y = jax.random.uniform(k_labels, (rows,), jnp.float32) \
            < jax.nn.sigmoid(m + 0.5 * (lo + hi))
        width = len(sizes)
        return (cols.reshape(rows, width), vals.reshape(rows, width),
                y.astype(jnp.float32))

    cols, vals, y = draw(*common.keys(seed, workload))
    return {"cols": cols, "vals": vals, "y": y}

