"""``reference/lbfgs.py``'s L-BFGS for many independent problems at once: the
same rules lane by lane (history, Armijo halving with c1 1e-4, first step
1/max(|d|, 1) and later steps 1, a pair kept when ``s.y > 1e-10 |s| |y|``,
steepest descent when the two-loop direction is no descent direction,
convergence when ``|g| <= tol * max(|g0|, 1)``), so that hundreds of thousands
of per-entity solves cost one evaluation of the caller's objective per trial
point and not one each. Vector algebra on the host in float64, every lane its
own step length, history and stopping; a lane that has ended is evaluated
where it stands and ignored. ``selfcheck/test_game_family.py`` holds it to
``lbfgs.py`` one lane at a time."""

from __future__ import annotations

import numpy as np


def _dot(a, b):
    return np.einsum("ed,ed->e", a, b)


def _two_loop(g, s_hist, y_hist, rho, valid):
    """``-H g`` per lane; slot ``m - 1`` of the histories is the newest pair,
    ``valid`` says which slots a lane has filled."""
    q = g.copy()
    m = s_hist.shape[0]
    alphas = np.zeros((m, g.shape[0]))
    for k in range(m - 1, -1, -1):
        a = np.where(valid[k], rho[k] * _dot(s_hist[k], q), 0.0)
        q -= a[:, None] * y_hist[k]
        alphas[k] = a
    yy = _dot(y_hist[-1], y_hist[-1])
    sy = _dot(s_hist[-1], y_hist[-1])
    scale = np.where(valid[-1] & (yy > 1e-10), sy / np.where(yy > 0, yy, 1.0),
                     1.0)
    q *= scale[:, None]
    for k in range(m):
        b = np.where(valid[k], rho[k] * _dot(y_hist[k], q), 0.0)
        q += (alphas[k] - b)[:, None] * s_hist[k]
    return -q


def lbfgs_lanes(fun, w0, *, max_iterations: int, tolerance: float = 1e-6,
                history: int = 10, max_line_search: int = 25) -> dict:
    """Minimise every lane of ``fun(W (E, d) float32) -> (values (E,), grads
    (E, d))`` from ``w0``. Returns per lane the iterate, its value and
    gradient norm, the first gradient's norm, the iterations taken and whether
    the gradient test was met."""
    w = np.array(w0, np.float64)
    lanes, d = w.shape

    def ev(wv):
        f, g = fun(np.asarray(wv, np.float32))
        return np.asarray(f, np.float64), np.asarray(g, np.float64)

    f, g = ev(w)
    g0 = np.linalg.norm(g, axis=1)
    tol = tolerance * np.maximum(g0, 1.0)
    gnorm = g0.copy()
    s_hist = np.zeros((history, lanes, d))
    y_hist = np.zeros((history, lanes, d))
    rho = np.zeros((history, lanes))
    valid = np.zeros((history, lanes), bool)
    iterations = np.zeros(lanes, np.int64)
    active = gnorm > tol
    for _ in range(max_iterations):
        if not active.any():
            break
        direction = _two_loop(g, s_hist, y_hist, rho, valid)
        uphill = _dot(g, direction) >= 0
        direction[uphill] = -g[uphill]
        gd = _dot(g, direction)
        alpha = np.where(valid[-1], 1.0, 1.0 / np.maximum(
            np.linalg.norm(direction, axis=1), 1.0))
        searching = active.copy()
        accepted = np.zeros(lanes, bool)
        w_new, f_new, g_new = w.copy(), f.copy(), g.copy()
        for _ls in range(max_line_search + 1):
            w_t = np.where(searching[:, None],
                           w + alpha[:, None] * direction, w)
            f_t, g_t = ev(w_t)
            enough = searching & (f_t <= f + 1e-4 * alpha * gd)
            accepted |= enough & np.isfinite(f_t)
            w_new[enough], f_new[enough], g_new[enough] = (
                w_t[enough], f_t[enough], g_t[enough])
            searching &= ~enough
            if not searching.any():
                break
            alpha[searching] *= 0.5
        # a lane whose search found no finite decrease ends where it stood
        active &= accepted
        step, dg = w_new - w, g_new - g
        sy = _dot(step, dg)
        keep = active & (sy > 1e-10 * np.linalg.norm(step, axis=1)
                         * np.linalg.norm(dg, axis=1))
        for hist, new in ((s_hist, step), (y_hist, dg)):
            hist[:-1, keep] = hist[1:, keep]
            hist[-1, keep] = new[keep]
        rho[:-1, keep] = rho[1:, keep]
        rho[-1, keep] = 1.0 / np.maximum(sy[keep], 1e-10)
        valid[:-1, keep] = valid[1:, keep]
        valid[-1, keep] = True
        w[active], f[active], g[active] = (
            w_new[active], f_new[active], g_new[active])
        iterations[active] += 1
        gnorm = np.linalg.norm(g, axis=1)
        active &= gnorm > tol
    return {"w": w, "value": f, "grad_norm": gnorm, "grad0_norm": g0,
            "iterations": iterations, "converged": gnorm <= tol}
