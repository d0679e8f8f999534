"""Plain reference for the dense logistic GLM: float32 ``jax.numpy``, every
product and sum in float32 (elementwise products and reductions, so no matrix
unit and no lower-precision pass), the design read in blocks of rows. It
imports nothing of the program and takes nothing that the program made.

The objective is ``sum_i softplus(m_i) - y_i m_i + 0.5 * l2 * |w|^2`` with
``m = X w``; the optimizer is ``reference/lbfgs.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.reference.lbfgs import lbfgs  # noqa: F401  (the family's)


@functools.partial(jax.jit, static_argnames=("chunk", "round_to"))
def value_and_grad(x, y, w, l2, *, chunk: int, round_to=None):
    """Objective and gradient at ``w``. ``round_to`` (a dtype name) rounds each
    block of the design to that type first: the lower-precision control."""
    n, d = x.shape
    if n % chunk:
        raise ValueError(f"block {chunk} does not divide {n} rows")

    def body(acc, xy):
        xb, yb = xy
        if round_to is not None:
            xb = xb.astype(round_to).astype(jnp.float32)
        m = jnp.sum(xb * w, axis=-1)
        loss = jnp.sum(jnp.logaddexp(0.0, m) - yb * m)
        r = jax.nn.sigmoid(m) - yb
        g = jnp.sum(r[:, None] * xb, axis=0)
        return (acc[0] + loss, acc[1] + g), None

    (f, g), _ = lax.scan(
        body, (jnp.float32(0.0), jnp.zeros_like(w)),
        (x.reshape(n // chunk, chunk, d), y.reshape(n // chunk, chunk)))
    return f + 0.5 * l2 * jnp.vdot(w, w), g + l2 * w


def objective(x, y, l2, *, chunk: int, round_to=None):
    """``w -> (value, grad)`` on the data, for :func:`lbfgs`."""
    return lambda w: value_and_grad(x, y, w, jnp.float32(l2), chunk=chunk,
                                    round_to=round_to)
