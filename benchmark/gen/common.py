"""Seed handling and the dense logistic problem shared by the generators.

A workload file may fix the problem: with a ``problem_seed`` the column
scales, the planted model and every row come from that number, the same for
every ``--seed``, and ``--seed`` draws each column's sign (the planted
coefficient is mirrored with it). That is a symmetry of the objective which
keeps the value of every product ``x_ij w_j``: every seed's solve takes bit
for bit the same steps, mirrored, and so the same amount of work. A draw that
is the same problem only in exact arithmetic (the order of the rows, a row's
sign with its label flipped) moves the solve's count of rejected trial points
as much as another problem does (PERF.md, section 6, PR 27), so the seed draws
none. Without a ``problem_seed`` the seed draws the whole problem.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def key_from_seed(seed: int):
    """A PRNG key for any whole-number seed: the driver's seeds pass 2**31,
    more than a 32-bit signed key seed holds, so the high bits are folded in."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def keys(seed: int, workload: dict):
    """``(the key that draws the problem, the key that draws the column
    signs or None)``."""
    if workload.get("problem_seed") is None:
        return key_from_seed(seed), None
    return key_from_seed(workload["problem_seed"]), key_from_seed(seed)


def problem(k_problem, k_signs, dim: int, decades):
    """``(column scales, planted model, the key of the rows)``, the scales
    signed as the seed draws them."""
    k_scale, k_model, k_rows = jax.random.split(k_problem, 3)
    lo, hi = decades
    scale = jnp.power(10.0, jax.random.uniform(
        k_scale, (dim,), jnp.float32, lo, hi))
    w_true = jax.random.normal(k_model, (dim,), jnp.float32) / scale
    if k_signs is not None:
        sign = jnp.where(jax.random.bernoulli(k_signs, 0.5, (dim,)), 1.0, -1.0)
        scale, w_true = scale * sign, w_true * sign
    return scale, w_true, k_rows


def block(k, scale, w_true, *, chunk: int, nnz: int):
    """One block: ``(chunk, dim)`` rows and their labels."""
    dim = scale.shape[0]
    kn, km, ky = jax.random.split(k, 3)
    v = jax.random.normal(kn, (chunk, dim), jnp.float32) * (
        scale / math.sqrt(nnz))
    x = jnp.where(jax.random.uniform(km, (chunk, dim), jnp.float32)
                  < nnz / dim, v, 0.0)
    m = jnp.sum(x * w_true, axis=-1)
    y = jax.random.uniform(ky, (chunk,), jnp.float32) < jax.nn.sigmoid(m)
    return x, y.astype(jnp.float32)
