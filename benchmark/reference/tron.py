"""Plain reference for a logistic GLM fitted by trust-region Newton: the
method of LIBLINEAR's ``tron.cpp`` (Lin, Weng, Keerthi, "Trust Region Newton
Method for Large-Scale Logistic Regression", JMLR 9, 2008), which upstream
Photon-ML ports as ``optimization/TRON.scala``. It imports nothing of the
program and takes nothing that the program made.

The objective is ``sum_i softplus(m_i) - y_i m_i + 0.5 * l2 * |w|^2`` with
``m = X w``; its gradient ``X'(sigmoid(m) - y) + l2 w``; its Hessian times a
vector ``X'(d2 * (X v)) + l2 v`` with ``d2 = sigmoid(m) (1 - sigmoid(m))``:
two plain contractions, ``jax.numpy`` under
``jax.default_matmul_precision("highest")`` in the design's own type (float32
in the benchmark), the design read a block of rows at a time. The vectors of
the method (``w``, ``g``, ``s``, ``r``, ``p``) are held on the host in the type
of ``w0`` and every scalar is a Python float.

The method. Steihaug conjugate gradients from ``s = 0`` on ``H s = -g``: stop
when ``|r| <= 0.1 |g|``, after ``cg_max_iterations`` products, or at the
trust region's boundary, which the step takes along ``p`` when ``s + alpha p``
would cross it or the curvature ``p.Hp`` is not positive. The radius starts at
``|g0|``, is shrunk to ``min(radius, |s|)`` after the first step, and is then
updated by LIBLINEAR's rule with eta 1e-4 / 0.25 / 0.75 and sigma 0.25 / 0.5 /
4 from the ratio of the actual to the predicted reduction and the
interpolated step length ``alpha``. A step is accepted when the actual
reduction exceeds 1e-4 of the predicted; the solve has converged when an
accepted iterate's ``|g| <= tolerance * max(|g0|, 1)``.

Departures from ``tron.cpp`` that the program (``optimize/tron.py``) makes,
made here likewise so that the two walk one path:

- the predicted reduction is tracked inside the conjugate gradients (an
  interior step adds ``0.5 alpha r.r``, the boundary step ``tau r.r - 0.5
  tau^2 p.Hp``), where ``tron.cpp`` forms ``-0.5 (g.s - s.r)`` afterwards;
  after a boundary step the residual is left as it was (nothing reads it);
- the actual reduction of a trial point whose value is not finite is minus
  infinity (the radius shrinks and the point is rejected), and the step
  length is then 0.25, 4 where the value is finite and ``f_new - f - g.s``
  is not positive;
- the solve ends as ``stuck`` when the radius falls under 1e-12; ``tron.cpp``'s
  other ways out (``f < -1e32``, both reductions under ``1e-12 |f|``) and any
  cap on steps that fail to improve are absent;
- the conjugate gradients are not preconditioned (the paper's form; newer
  LIBLINEAR releases precondition) and their cap is the configuration's;
- the gradient test is relative to ``max(|g0|, 1)`` as the repo's other
  minimizers have it, where ``tron.cpp`` takes ``eps`` times a norm that
  depends on the class balance.

Besides, for the readings that the limits are set from: ``round_to`` rounds
each block of the design first (the lower-precision control), and
:func:`tron` takes the curvature and the caps as arguments, so a fault can be
planted by the caller.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

ETA = (1e-4, 0.25, 0.75)
SIGMA = (0.25, 0.5, 4.0)
CG_STOP = 0.1
STUCK = 1e-12


def options(optimizer: dict) -> dict:
    """:func:`tron`'s keywords from a configuration's ``optimizer`` group."""
    return dict(max_iterations=int(optimizer["max_iterations"]),
                tolerance=float(optimizer["tolerance"]),
                cg_max_iterations=int(optimizer["cg_max_iterations"]),
                cg_stop=float(optimizer["cg_stop"]),
                eta=tuple(float(v) for v in optimizer["eta"]),
                sigma=tuple(float(v) for v in optimizer["sigma"]))


def _blocks(x, chunk: int, *vectors):
    n, d = x.shape
    if n % chunk:
        raise ValueError(f"block {chunk} does not divide {n} rows")
    return (x.reshape(n // chunk, chunk, d),) + tuple(
        v.reshape(n // chunk, chunk) for v in vectors)


def _block(xb, round_to):
    return xb if round_to is None else xb.astype(round_to).astype(xb.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "round_to"))
def value_and_grad(x, y, w, l2, *, chunk: int, round_to=None):
    """Objective and gradient at ``w``."""
    def body(acc, xy):
        xb, yb = _block(xy[0], round_to), xy[1]
        m = xb @ w
        loss = jnp.sum(jnp.logaddexp(0.0, m) - yb * m)
        return (acc[0] + loss, acc[1] + (jax.nn.sigmoid(m) - yb) @ xb), None

    with jax.default_matmul_precision("highest"):
        (f, g), _ = lax.scan(body, (jnp.zeros((), w.dtype), jnp.zeros_like(w)),
                             _blocks(x, chunk, y))
    return f + 0.5 * l2 * jnp.vdot(w, w), g + l2 * w


@functools.partial(jax.jit, static_argnames=("chunk", "round_to"))
def curvature(x, w, *, chunk: int, round_to=None):
    """``d2`` at ``w``, one entry a row: ``sigmoid(m) (1 - sigmoid(m))``."""
    def body(_, xb):
        s = jax.nn.sigmoid(_block(xb, round_to) @ w)
        return None, s * (1.0 - s)

    with jax.default_matmul_precision("highest"):
        _, d2 = lax.scan(body, None, _blocks(x, chunk)[0])
    return d2.reshape(-1)


@functools.partial(jax.jit, static_argnames=("chunk", "round_to"))
def hessian_vector(x, d2, v, l2, *, chunk: int, round_to=None):
    """``X'(d2 * (X v)) + l2 v``."""
    def body(acc, xd):
        xb = _block(xd[0], round_to)
        return acc + (xd[1] * (xb @ v)) @ xb, None

    with jax.default_matmul_precision("highest"):
        hv, _ = lax.scan(body, jnp.zeros_like(v), _blocks(x, chunk, d2))
    return hv + l2 * v


class Problem:
    """The objective on the data at one L2 weight, for :func:`tron`: host
    vectors in, host vectors out. ``d2_at`` (``w -> d2`` on the device)
    replaces the curvature: a planted fault's."""

    def __init__(self, x, y, l2: float, *, chunk: int, round_to=None,
                 d2_at=None):
        self.x, self.y = x, y
        self.l2 = jnp.asarray(l2, x.dtype)
        self.args = dict(chunk=chunk, round_to=round_to)
        self.d2_at = d2_at or (lambda w: curvature(self.x, w, **self.args))

    def _device(self, v):
        return jnp.asarray(v, self.x.dtype)

    def fun(self, w):
        f, g = value_and_grad(self.x, self.y, self._device(w), self.l2,
                              **self.args)
        return float(f), np.asarray(g)

    def hessian_at(self, w):
        d2 = self.d2_at(self._device(w))
        return lambda v: np.asarray(hessian_vector(
            self.x, d2, self._device(v), self.l2, **self.args))


def conjugate_gradients(hv, g, radius: float, *, cap: int,
                        cg_stop: float = CG_STOP):
    """``(s, predicted reduction, products)``: Steihaug's truncated
    conjugate gradients on ``H s = -g`` inside ``|s| <= radius``."""
    dtype = g.dtype
    stop = cg_stop * float(np.linalg.norm(g))
    s = np.zeros_like(g)
    r = -g
    p = r.copy()
    rr = float(r @ r)
    q = 0.0
    products = 0
    done = math.sqrt(rr) <= stop
    while not done and products < cap:
        hp = hv(p).astype(dtype)
        products += 1
        php = float(p @ hp)
        alpha = rr / php if php > 0 else rr
        s_next = s + dtype.type(alpha) * p
        if float(np.linalg.norm(s_next)) > radius or php <= 0:
            ps, pp, ss = float(p @ s), float(p @ p), float(s @ s)
            disc = ps * ps + pp * (radius * radius - ss)
            tau = (-ps + math.sqrt(max(disc, 0.0))) / (pp if pp > 0 else 1.0)
            s = s + dtype.type(tau) * p
            q += -tau * rr + 0.5 * tau * tau * php
            break
        s = s_next
        q -= 0.5 * alpha * rr
        r = r - dtype.type(alpha) * hp
        rr_new = float(r @ r)
        done = math.sqrt(rr_new) <= stop
        p = r + dtype.type(rr_new / rr if rr > 0 else rr_new) * p
        rr = rr_new
    return s, -q, products


def tron(fun, hessian_at, w0, *, max_iterations: int, tolerance: float,
         cg_max_iterations: int, cg_stop: float = CG_STOP, eta=ETA,
         sigma=SIGMA) -> dict:
    """Minimise ``fun(w) -> (value, grad)`` from ``w0``;
    ``hessian_at(w) -> (v -> H v)``. Returns the iterate, the value and
    gradient norm after every iteration (index 0: the start; a rejected
    step repeats the entry before it), the Hessian-vector products made,
    and whether the gradient test was met."""
    eta0, eta1, eta2 = eta
    sigma1, sigma2, sigma3 = sigma
    w = np.asarray(w0)
    f, g = fun(w)
    g = g.astype(w.dtype)
    gnorm0 = float(np.linalg.norm(g))
    tol = tolerance * max(gnorm0, 1.0)
    radius = gnorm0
    values, gnorms, hvps = [f], [gnorm0], 0
    converged, stuck = gnorm0 <= tol, False
    while not converged and not stuck and len(values) <= max_iterations:
        s, predicted, products = conjugate_gradients(
            hessian_at(w), g, radius, cap=cg_max_iterations, cg_stop=cg_stop)
        hvps += products
        snorm = float(np.linalg.norm(s))
        w_new = w + s
        f_new, g_new = fun(w_new)
        g_new = g_new.astype(w.dtype)
        gs = float(g @ s)
        finite = math.isfinite(f_new)
        actual = f - f_new if finite else -math.inf
        denom = f_new - f - gs
        if math.isfinite(denom) and denom > 0:
            alpha = max(sigma1, -0.5 * (gs / denom))
        else:
            alpha = sigma3 if finite else sigma1
        if len(values) == 1:
            radius = min(radius, snorm)
        if actual < eta0 * predicted:
            radius = min(max(alpha, sigma1) * snorm, sigma2 * radius)
        elif actual < eta1 * predicted:
            radius = max(sigma1 * radius, min(alpha * snorm, sigma2 * radius))
        elif actual < eta2 * predicted:
            radius = max(sigma1 * radius, min(alpha * snorm, sigma3 * radius))
        else:
            radius = max(radius, min(alpha * snorm, sigma3 * radius))
        accept = finite and actual > eta0 * predicted
        if accept:
            w, f, g = w_new, f_new, g_new
        values.append(f)
        gnorms.append(float(np.linalg.norm(g)))
        converged = accept and gnorms[-1] <= tol
        stuck = radius < STUCK
    return {"w": w, "values": values, "grad_norms": gnorms, "hvps": hvps,
            "iterations": len(values) - 1, "converged": converged}
