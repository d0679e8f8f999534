"""Plain reference for the sparse logistic GLM: float32 ``jax.numpy`` over the
generator's own entries, ``cols`` and ``vals`` of shape ``(rows, width)``, one
row of them a sample. It imports nothing of the program and takes nothing that
the program built.

The objective is ``sum_i softplus(m_i) - y_i m_i + 0.5 * l2 * |w|^2`` with
``m_i = sum_f vals[i, f] * w[cols[i, f]]``; the gradient is the exact float32
transpose of the same entries, ``g[c] = sum over the entries in bin c of
vals * (sigmoid(m_i) - y_i)``, a scatter-add of one block of rows after the
other (each bin's sum grows by its own entries alone: no running sum over the
whole design). The optimizer is ``reference/lbfgs.py``.

Besides, for the readings that the limits are set from: ``round_to`` rounds
the gathered coefficients and the per-row residuals before they are
multiplied (the lower-precision control: the values, 1 or -1, are exact in
bfloat16, so rounding them would show nothing), and ``skip_bin`` leaves one
bin's entries out of the transpose (a planted fault).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.lbfgs import lbfgs  # noqa: F401  (the family's)


def _blocks(a, block: int):
    return a.reshape((a.shape[0] // block, block) + a.shape[1:])


@functools.partial(jax.jit, static_argnames=("block", "round_to"))
def value_and_grad(cols, vals, y, w, l2, skip_bin=-1, *, block: int,
                   round_to=None):
    """Objective and gradient at ``w``."""
    n = y.shape[0]
    if n % block:
        raise ValueError(f"block {block} does not divide {n} rows")
    rounded = (lambda a: a) if round_to is None else (
        lambda a: a.astype(round_to).astype(jnp.float32))

    def body(acc, cvy):
        c, v, yb = cvy
        m = jnp.sum(v * rounded(w[c]), axis=-1)
        loss = jnp.sum(jnp.logaddexp(0.0, m) - yb * m)
        r = rounded(jax.nn.sigmoid(m) - yb)
        part = jnp.where(c == skip_bin, 0.0, v * r[:, None])
        g = acc[1].at[c.reshape(-1)].add(part.reshape(-1))
        return (acc[0] + loss, g), None

    (f, g), _ = lax.scan(
        body, (jnp.float32(0.0), jnp.zeros_like(w)),
        (_blocks(cols, block), _blocks(vals, block), _blocks(y, block)))
    return f + 0.5 * l2 * jnp.vdot(w, w), g + l2 * w


def objective(cols, vals, y, l2, *, block: int, round_to=None, skip_bin=-1):
    """``w -> (value, grad)`` on the data, for :func:`lbfgs`."""
    return lambda w: value_and_grad(
        cols, vals, y, w, jnp.float32(l2), jnp.int32(skip_bin), block=block,
        round_to=round_to)


@functools.partial(jax.jit, static_argnames=("block",))
def first_of_duplicates(cols, vals, *, block: int):
    """``vals`` with every entry zeroed whose bin an earlier entry of the same
    row already holds: a row's colliding entries counted once (a planted
    fault)."""
    def one(cv):
        c, v = cv
        same = c[:, :, None] == c[:, None, :]  # [i, f, f'] : f and f' collide
        earlier = jnp.tril(jnp.ones(same.shape[1:], bool), k=-1)
        return jnp.where(jnp.any(same & earlier, axis=-1), 0.0, v)

    out = lax.map(one, (_blocks(cols, block), _blocks(vals, block)))
    return out.reshape(vals.shape)


@functools.partial(jax.jit, static_argnames=("dim",))
def busiest_bin(cols, *, dim: int):
    """The bin that holds the most entries."""
    counts = jnp.zeros((dim,), jnp.int32).at[cols.reshape(-1)].add(1)
    return jnp.argmax(counts).astype(jnp.int32)
