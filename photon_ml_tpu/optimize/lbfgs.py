"""L-BFGS as a single on-device ``lax.while_loop``.

TPU-first replacement for the reference's
``photon-lib/.../optimization/LBFGS.scala`` (a wrapper over
``breeze.optimize.LBFGS`` with history 10 and strong-Wolfe line search).

Design: instead of a JVM driver loop calling out to executors per gradient,
the *entire* optimization — two-loop recursion, backtracking line search,
curvature-pair ring buffer, convergence test — compiles into one XLA program.
``value_and_grad_fn`` is a pure closure; on a sharded mesh it contains a
``psum`` (see :mod:`photon_ml_tpu.parallel.distributed`) and the same loop
drives a whole pod with one launch, replacing a broadcast + ``treeAggregate``
round-trip per iteration.

Ring-buffer history with validity masking keeps every shape static; the solver
is ``vmap``-able, which is how millions of per-entity random-effect solves
batch onto the MXU (SURVEY.md §7 "vmap-batched block solves").

Line search: backtracking Armijo with adaptive growth. For the convex GLM
objectives this framework trains, the minimizer is unique, so solutions agree
with the reference's strong-Wolfe breeze implementation to tolerance even
though the iteration paths differ; parity is asserted on solutions, not paths
(tests vs scipy L-BFGS-B).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimize.common import (
    OptimizerConfig,
    OptimizerResult,
    ValueAndGrad,
    armijo_backtracking,
    init_trace,
    record_trace,
    update_history,
)

Array = jax.Array

_EPS = 1e-10
_ARMIJO_C1 = 1e-4


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _State:
    w: Array
    f: Array
    g: Array
    s_hist: Array  # (m, d) ring buffer of steps
    y_hist: Array  # (m, d) ring buffer of gradient diffs
    rho: Array  # (m,) 1 / (s.y)
    n_pairs: Array  # int32: total pairs ever stored (ring position = n % m)
    it: Array
    evals: Array  # int32: calls of ``fun`` so far, the one at w0 included
    converged: Array
    failed: Array  # line search found no decrease
    stalls: Array  # int32: consecutive accepted steps with zero fp progress
    values: Array
    grad_norms: Array


def two_loop_direction(g: Array, s_hist: Array, y_hist: Array, rho: Array,
                       n_pairs: Array, history: int) -> Array:
    """Masked L-BFGS two-loop recursion; returns the descent direction -H g.

    Statically unrolled over the (small) history length with dynamic ring
    indices — XLA-friendly, no data-dependent shapes.
    """
    m = history
    valid = jnp.minimum(n_pairs, m)

    def idx_newest(k):  # k = 0 is the newest pair
        return jnp.mod(n_pairs - 1 - k, m)

    q = g
    alphas = []
    for k in range(m):
        i = idx_newest(k)
        use = k < valid
        a = jnp.where(use, rho[i] * jnp.vdot(s_hist[i], q), 0.0)
        q = q - a * y_hist[i]
        alphas.append((i, use, a))

    # Initial Hessian scaling gamma = s.y / y.y of the newest pair.
    i0 = idx_newest(0)
    yy = jnp.vdot(y_hist[i0], y_hist[i0])
    sy = jnp.vdot(s_hist[i0], y_hist[i0])
    gamma = jnp.where((valid > 0) & (yy > _EPS), sy / jnp.maximum(yy, _EPS), 1.0)
    r = gamma * q

    for i, use, a in reversed(alphas):
        b = jnp.where(use, rho[i] * jnp.vdot(y_hist[i], r), 0.0)
        r = r + (a - b) * s_hist[i]

    return -r


def backtracking_line_search(fun: ValueAndGrad, w: Array, f: Array, g: Array,
                             d: Array, alpha0: Array, max_steps: int,
                             active: Array):
    """Armijo backtracking: shrink alpha until sufficient decrease, for a
    solve that is still ``active`` (see :func:`armijo_backtracking`).

    Returns ``(alpha, f_new, g_new, w_new, ok, trials)``, ``trials`` the
    number of calls of ``fun``. On total failure returns the
    last trial point with ``ok=False`` (the reference's breeze throws a
    ``LineSearchFailed``; here the outer loop terminates via the flag). The
    acceptance predicate is NaN-safe: an overflowing trial (f=NaN/inf) shrinks
    alpha rather than exiting.
    """
    gd = jnp.vdot(g, d)

    def trial(alpha):
        f_t, g_t = fun(w + alpha * d)
        return w + alpha * d, f_t, g_t

    def sufficient(alpha, w_t, f_t):
        return f_t <= f + _ARMIJO_C1 * alpha * gd

    alpha, w_new, f_new, g_new, ok, trials = armijo_backtracking(
        trial, sufficient, alpha0, max_steps, active)
    return alpha, f_new, g_new, w_new, ok, trials


def minimize_lbfgs(fun: ValueAndGrad, w0: Array,
                   config: OptimizerConfig = OptimizerConfig()) -> OptimizerResult:
    """Minimize ``fun`` starting at ``w0``; fully jittable and vmappable."""
    m, d = config.history, w0.shape[-1]
    f0, g0 = fun(w0)
    gnorm0 = jnp.linalg.norm(g0)
    values, gnorms = init_trace(config, f0, gnorm0)
    tol = config.tolerance * jnp.maximum(gnorm0, 1.0)

    init = _State(
        w=w0, f=f0, g=g0,
        s_hist=jnp.zeros((m, d), w0.dtype),
        y_hist=jnp.zeros((m, d), w0.dtype),
        rho=jnp.zeros((m,), w0.dtype),
        n_pairs=jnp.int32(0),
        it=jnp.int32(0),
        evals=jnp.int32(1),
        converged=gnorm0 <= tol,
        failed=jnp.asarray(False),
        stalls=jnp.int32(0),
        values=values, grad_norms=gnorms,
    )

    def cond(s: _State):
        return (~s.converged) & (~s.failed) & (s.it < config.max_iterations)

    def body(s: _State):
        # Unbatched this is always true. Under vmap the batched while_loop
        # runs body for every lane while any lane's cond holds and keeps the
        # carry of a lane whose cond is false as it was, so a finished lane's
        # state, iterations and evaluations stand; what the lanes SHARE is
        # the line search's trip count, and a finished lane adds nothing to it.
        active = cond(s)
        d_dir = two_loop_direction(s.g, s.s_hist, s.y_hist, s.rho, s.n_pairs, m)
        # Safeguard: fall back to steepest descent on a non-descent direction.
        descent = jnp.vdot(s.g, d_dir) < 0
        d_dir = jnp.where(descent, d_dir, -s.g)
        # First step scales by 1/||g||, later steps start at 1 (standard L-BFGS).
        alpha0 = jnp.where(s.n_pairs > 0, 1.0,
                           1.0 / jnp.maximum(jnp.linalg.norm(d_dir), 1.0))
        alpha, f_new, g_new, w_new, ok, trials = backtracking_line_search(
            fun, s.w, s.f, s.g, d_dir, alpha0, config.max_line_search, active)

        s_hist, y_hist, rho, n_pairs = update_history(
            s.s_hist, s.y_hist, s.rho, s.n_pairs, w_new - s.w, g_new - s.g, ok,
            _EPS)

        it = s.it + 1
        gnorm = jnp.linalg.norm(g_new)
        # Record only accepted iterates: a rejected final step must not leave
        # a NaN/increased value inside the valid trace prefix.
        values, gnorms = record_trace(
            s.values, s.grad_norms, it,
            jnp.where(ok, f_new, s.f), jnp.where(ok, gnorm, jnp.linalg.norm(s.g)))
        # Stall: an "accepted" step with no representable decrease (the
        # Armijo bound rounds to f at working precision). A single flat step
        # can still precede useful movement near the optimum, so require TWO
        # consecutive stalls before terminating; convergence is still judged
        # by the gradient test alone.
        stalls = jnp.where(ok & (f_new >= s.f), s.stalls + 1, jnp.int32(0))
        return _State(
            w=jnp.where(ok, w_new, s.w),
            f=jnp.where(ok, f_new, s.f),
            g=jnp.where(ok, g_new, s.g),
            s_hist=s_hist, y_hist=y_hist, rho=rho, n_pairs=n_pairs,
            it=it,
            evals=s.evals + trials,
            converged=ok & (gnorm <= tol),
            failed=(~ok) | (stalls >= 2),
            stalls=stalls,
            values=values, grad_norms=gnorms,
        )

    final = lax.while_loop(cond, body, init)
    return OptimizerResult(
        w=final.w, value=final.f, grad_norm=jnp.linalg.norm(final.g),
        iterations=final.it, evaluations=final.evals,
        converged=final.converged,
        values=final.values, grad_norms=final.grad_norms,
    )
