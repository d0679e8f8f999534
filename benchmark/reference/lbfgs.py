"""Plain L-BFGS as the configurations state it: history 10, Armijo
backtracking by halving with c1 1e-4, the first step of length 1/max(|d|, 1)
and later steps of 1, a pair kept when ``s.y > 1e-10 |s| |y|``, steepest
descent when the two-loop direction is not a descent direction, convergence
when ``|g| <= tol * max(|g0|, 1)``. Vector algebra on the host in float64;
every evaluation of the objective is the caller's (on the device)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def options(optimizer: dict) -> dict:
    """:func:`lbfgs`'s keywords from a configuration's ``optimizer`` group."""
    return dict(max_iterations=int(optimizer["max_iterations"]),
                tolerance=float(optimizer["tolerance"]),
                history=int(optimizer["history"]),
                max_line_search=int(optimizer["max_line_search"]))


def _two_loop(g, pairs):
    q = g.copy()
    alphas = []
    for s, yv, rho in reversed(pairs):
        a = rho * (s @ q)
        q -= a * yv
        alphas.append(a)
    if pairs:
        s, yv, _ = pairs[-1]
        yy = yv @ yv
        q *= (s @ yv) / yy if yy > 1e-10 else 1.0
    for (s, yv, rho), a in zip(pairs, reversed(alphas)):
        q += (a - rho * (yv @ q)) * s
    return -q


def lbfgs(fun, w0, *, max_iterations: int, tolerance: float = 1e-6,
          history: int = 10, max_line_search: int = 25) -> dict:
    """Minimise ``fun(w) -> (value, grad)`` from ``w0``. Returns the iterate,
    and the value and gradient norm after every iteration (index 0: the
    start)."""
    w = np.asarray(w0, np.float64)

    def ev(wv):
        f, g = fun(jnp.asarray(wv, jnp.float32))
        return float(f), np.asarray(g, np.float64)

    f, g = ev(w)
    g0 = np.linalg.norm(g)
    tol = tolerance * max(g0, 1.0)
    values, gnorms, pairs = [f], [g0], []
    for _ in range(max_iterations):
        if gnorms[-1] <= tol:
            break
        d = _two_loop(g, pairs)
        if g @ d >= 0:
            d = -g
        alpha = 1.0 if pairs else 1.0 / max(np.linalg.norm(d), 1.0)
        gd = g @ d
        ok = False
        for _ls in range(max_line_search + 1):
            w_t = w + alpha * d
            f_t, g_t = ev(w_t)
            if f_t <= f + 1e-4 * alpha * gd:
                ok = np.isfinite(f_t)
                break
            alpha *= 0.5
        if not ok:
            break
        s, yv = w_t - w, g_t - g
        sy = s @ yv
        if sy > 1e-10 * np.linalg.norm(s) * np.linalg.norm(yv):
            pairs = (pairs + [(s, yv, 1.0 / max(sy, 1e-10))])[-history:]
        w, f, g = w_t, f_t, g_t
        values.append(f)
        gnorms.append(np.linalg.norm(g))
    return {"w": w, "values": values, "grad_norms": gnorms}
