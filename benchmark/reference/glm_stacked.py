"""The plain reference of ``reference/glm.py`` over the stacked layout
``(chips, rows, dim)``: every chip evaluates its own rows with that file's
float32 pass (no regularization term), and the host sums the chips' losses and
gradients in float64 and adds the L2 term once. No collective, no mesh, and
nothing of the program: what the program's ``psum`` has to equal.

An evaluation dispatches every chip's pass before it waits for any, so it
takes one chip's time, not the sum of the chips'.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import glm


@functools.partial(jax.jit, static_argnames=("chunk", "round_to"))
def _blocks_value_and_grad(x, y, w, *, chunk: int, round_to=None):
    """Loss and gradient over the blocks one chip holds: ``(k, rows, dim)``
    read as ``k * rows`` rows."""
    return glm.value_and_grad(
        x.reshape(-1, x.shape[-1]), y.reshape(-1), w, jnp.float32(0.0),
        chunk=chunk, round_to=round_to)


def chip_blocks(x, y):
    """Per chip, the blocks of the design and of the labels that it holds."""
    labels = {s.device: s.data for s in y.addressable_shards}
    return [(s.data, labels[s.device]) for s in x.addressable_shards]


def value_and_grad(x, y, w, l2, *, chunk: int, round_to=None):
    """Objective (a float) and gradient (float64, on the host) at ``w``."""
    w = np.asarray(w, np.float32)
    parts = [_blocks_value_and_grad(
        xb, yb, jax.device_put(w, xb.device), chunk=chunk, round_to=round_to)
        for xb, yb in chip_blocks(x, y)]
    f = sum(float(p[0]) for p in parts)
    g = sum(np.asarray(p[1], np.float64) for p in parts)
    w64, l2 = w.astype(np.float64), float(l2)
    return f + 0.5 * l2 * float(w64 @ w64), g + l2 * w64


def objective(x, y, l2, *, chunk: int, round_to=None):
    """``w -> (value, grad)`` on the data, for :func:`lbfgs`."""
    return lambda w: value_and_grad(x, y, w, l2, chunk=chunk,
                                    round_to=round_to)
