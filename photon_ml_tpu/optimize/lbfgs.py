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
is ``vmap``-able.

Two loops, one set of rules. :func:`minimize_lbfgs` is the nested form: an
outer ``while_loop`` (iterations) around the line search's own (halvings). It
serves every single solve (the fixed effect, a GLM's lambda path): a direction
is worked out once an iteration, under no select. Under ``vmap`` each of the
two loops runs while ANY lane's condition holds, so a batch pays, every
iteration, the longest search among its lanes: the sum of the iterations'
slowest searches, where its slowest lane needs far less (OWL-QN and TRON
buckets still run so). :func:`minimize_lbfgs_lanes` is the flat form for a
batch that knows it is one (an L-BFGS bucket of a random effect, through
``glm/problem.py::OptimizationProblem.run_lanes``): ONE loop over the batched
state whose trip is one evaluation a lane, each lane at its own place in its
own solve, so the batch runs what its slowest lane evaluates, and counts its
passes itself; the price is a direction worked out, and selected, at every
trip. The needs conflict, so the loops are two. The rules, the constants and
the order of a lane's operations are one; the flat form writes the two-loop
recursion and the history update out once more on arrays that carry the lanes
last, because the per-lane pieces under ``vmap`` cost a TPU eight times the
bucket's kernel at every trip (the comment above ``_dot`` says how).

Line search: backtracking Armijo with adaptive growth. For the convex GLM
objectives this framework trains, the minimizer is unique, so solutions agree
with the reference's strong-Wolfe breeze implementation to tolerance even
though the iteration paths differ; parity is asserted on solutions, not paths
(tests vs scipy L-BFGS-B).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

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
        hvps=jnp.zeros_like(final.it),
    )


# --- the flat loop of a batch ----------------------------------------------
#
# Every array below carries the lane axis LAST: ``w`` is ``(d, E)``, a history
# ``(m, d, E)``, a lane's scalar ``(E,)``. Under ``vmap`` the per-lane pieces
# above (``vdot``, ``hist[i]``, ``.at[pos].set``) put the lane axis first, and
# the TPU compiler then lays an ``(E, m, d)`` history out with ``d`` (8 for a
# random effect) in its 128-lane dimension: 25 times the bytes, read some
# forty times by one two-loop recursion, 120 ms an iteration for a bucket of
# 168,000 lanes whose kernel takes 15 ms a pass (PERF.md, PR 29). A flat loop
# pays that at every trip, so its arithmetic is written out here on whole
# arrays with the lanes last, where the same arrays are dense. What a lane
# computes, and in which order, is :func:`two_loop_direction`'s and
# ``update_history``'s; what differs is where a pair is kept (the histories
# here are in order of age, newest first, shifted when a pair is stored: no
# ring position, no per-lane index) and how a dot product over ``d`` is
# summed (in index order), which moves last bits and nothing else.


def _dot(a: Array, b: Array) -> Array:
    return jnp.sum(a * b, axis=0)


def _norm(a: Array) -> Array:
    return jnp.sqrt(jnp.sum(a * a, axis=0))


def _two_loop_direction_lanes(g: Array, s_hist: Array, y_hist: Array,
                              rho: Array, n_pairs: Array) -> Array:
    """:func:`two_loop_direction` for every lane (slot 0 the newest pair)."""
    m = s_hist.shape[0]
    valid = jnp.minimum(n_pairs, m)
    q = g
    alphas = []
    for k in range(m):
        a = jnp.where(k < valid, rho[k] * _dot(s_hist[k], q), 0.0)
        q = q - a * y_hist[k]
        alphas.append(a)

    yy = _dot(y_hist[0], y_hist[0])
    sy = _dot(s_hist[0], y_hist[0])
    gamma = jnp.where((valid > 0) & (yy > _EPS), sy / jnp.maximum(yy, _EPS),
                      1.0)
    r = gamma * q
    for k in reversed(range(m)):
        b = jnp.where(k < valid, rho[k] * _dot(y_hist[k], r), 0.0)
        r = r + (alphas[k] - b) * s_hist[k]
    return -r


def _update_history_lanes(s_hist: Array, y_hist: Array, rho: Array,
                          n_pairs: Array, step: Array, y: Array,
                          accept: Array):
    """``update_history`` for every lane: where a pair is stored it becomes
    slot 0 and the oldest of ``m`` is dropped."""
    sy = _dot(step, y)
    store = accept & (sy > _EPS * _norm(step) * _norm(y))
    push = lambda hist, new: jnp.where(
        store, jnp.concatenate([new[None], hist[:-1]]), hist)
    return (push(s_hist, step), push(y_hist, y),
            push(rho, 1.0 / jnp.maximum(sy, _EPS)),
            jnp.where(store, n_pairs + 1, n_pairs))


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _Lanes:
    """The lanes of :func:`minimize_lbfgs_lanes`: their solves (``_State``'s
    fields, lanes last; ``evals`` counts every trial point as it is made)
    and the search each is in."""

    solve: _State
    d: Array  # (d, E) the search's direction
    gd: Array  # g.d
    alpha: Array  # the step the next trip tries
    halvings: Array  # int32: halvings made in this search


def _running(s: _State, config: OptimizerConfig) -> Array:
    return (~s.converged) & (~s.failed) & (s.it < config.max_iterations)


def _begin_search(s: _State):
    """Direction, ``g.d`` and first step of the search from ``s``: what
    ``minimize_lbfgs``'s body works out before its line search."""
    d_dir = _two_loop_direction_lanes(s.g, s.s_hist, s.y_hist, s.rho,
                                      s.n_pairs)
    descent = _dot(s.g, d_dir) < 0
    d_dir = jnp.where(descent, d_dir, -s.g)
    alpha0 = jnp.where(s.n_pairs > 0, 1.0,
                       1.0 / jnp.maximum(_norm(d_dir), 1.0))
    return d_dir, _dot(s.g, d_dir), alpha0


def _trip(fun, lanes: _Lanes, tol: Array, config: OptimizerConfig) -> _Lanes:
    """One evaluation a lane: the trial point its search stands at. Where
    the search ends there (sufficient decrease, or ``max_line_search``
    halvings made) the lane does what ``minimize_lbfgs``'s body does after
    its line search returned and begins its next search; else its step is
    halved. A lane that has ended keeps its state."""
    s = lanes.solve
    running = _running(s, config)
    w_t = s.w + lanes.alpha * lanes.d
    f_t, g_t = fun(w_t)
    sufficient = f_t <= s.f + _ARMIJO_C1 * lanes.alpha * lanes.gd
    ends = running & (sufficient | (lanes.halvings >= config.max_line_search))
    ok = sufficient & jnp.isfinite(f_t)
    moves = ends & ok

    s_hist, y_hist, rho, n_pairs = _update_history_lanes(
        s.s_hist, s.y_hist, s.rho, s.n_pairs, w_t - s.w, g_t - s.g, moves)
    it = jnp.where(ends, s.it + 1, s.it)
    gnorm = _norm(g_t)
    values, gnorms = s.values, s.grad_norms
    if values.shape[0]:  # track_states
        at = ends & (jnp.arange(values.shape[0])[:, None] == it)
        values = jnp.where(
            at, jnp.where(ok, f_t, s.f).astype(jnp.float32), values)
        gnorms = jnp.where(
            at, jnp.where(ok, gnorm, _norm(s.g)).astype(jnp.float32), gnorms)
    stalls = jnp.where(
        ends, jnp.where(ok & (f_t >= s.f), s.stalls + 1, jnp.int32(0)),
        s.stalls)
    solve = _State(
        w=jnp.where(moves, w_t, s.w),
        f=jnp.where(moves, f_t, s.f),
        g=jnp.where(moves, g_t, s.g),
        s_hist=s_hist, y_hist=y_hist, rho=rho, n_pairs=n_pairs,
        it=it,
        evals=jnp.where(running, s.evals + 1, s.evals),
        converged=jnp.where(ends, ok & (gnorm <= tol), s.converged),
        failed=jnp.where(ends, (~ok) | (stalls >= 2), s.failed),
        stalls=stalls,
        values=values, grad_norms=gnorms,
    )
    d_dir, gd, alpha0 = _begin_search(solve)
    halves = running & ~ends
    return _Lanes(
        solve=solve,
        d=jnp.where(ends, d_dir, lanes.d),
        gd=jnp.where(ends, gd, lanes.gd),
        alpha=jnp.where(ends, alpha0,
                        jnp.where(halves, lanes.alpha * 0.5, lanes.alpha)),
        halvings=jnp.where(ends, jnp.int32(0),
                           jnp.where(halves, lanes.halvings + 1,
                                     lanes.halvings)),
    )


def lanes_last(a: Array) -> Array:
    """``(E, d) -> (d, E)``, column by column, not ``.T``: the TPU compiler
    folds a transpose into the layouts on either side, and an ``(E, d)``
    layout (d in the 128-lane dimension) then spreads to every array of the
    loop."""
    return jnp.stack([a[:, k] for k in range(a.shape[1])])


def lanes_first(a: Array) -> Array:
    """``(d, E) -> (E, d)``: :func:`lanes_last` back."""
    return jnp.stack([a[k] for k in range(a.shape[0])], axis=1)


def vmapped_evaluation(fun: Callable[[Any, Array], tuple[Array, Array]],
                       lanes: Any) -> Callable[[Array], tuple[Array, Array]]:
    """The evaluation :func:`minimize_lbfgs_lanes` takes, from a per-lane
    ``fun(lane, w (d,)) -> (value, grad (d,))`` and ``lanes``, a pytree whose
    leaves lead with the lane axis: ``vmap(fun)`` between two column stacks,
    paid at every trip. (A batch whose objective evaluates lanes-last
    arrays itself hands that over and pays neither.)"""
    def evaluate(w):  # (d, E) -> (E,), (d, E)
        f, g = jax.vmap(fun)(lanes, lanes_first(w))
        return f, lanes_last(g)
    return evaluate


def minimize_lbfgs_lanes(evaluate: Callable[[Array], tuple[Array, Array]],
                         w0: Array,
                         config: OptimizerConfig = OptimizerConfig()
                         ) -> tuple[OptimizerResult, Array]:
    """Minimize every lane of a batch from ``w0[e]`` (``w0``: ``(E, d)``),
    given the batch's evaluation ``w (d, E) -> (values (E,), grads (d, E))``
    on arrays that carry the lanes last, in which a lane's value and
    gradient depend on its own column alone: the flat form (module
    docstring).

    Returns the lanes' results (every field leads with the lane axis), each
    what the loop gives that lane alone, bit for bit, and what
    :func:`minimize_lbfgs` gives it up to the order in which a dot product
    over ``d`` is summed; and ``passes`` (int32 scalar): the batched
    evaluations made, the one at ``w0`` and one a trip of the loop, counted
    by the loop itself. ``passes`` equals the largest ``evaluations`` of any
    lane.
    """
    m, (n_lanes, d) = config.history, w0.shape
    w = lanes_last(w0)
    f0, g0 = evaluate(w)
    gnorm0 = _norm(g0)
    values, gnorms = jax.vmap(
        lambda f, gn: init_trace(config, f, gn), out_axes=-1)(f0, gnorm0)
    tol = config.tolerance * jnp.maximum(gnorm0, 1.0)
    solve = _State(
        w=w, f=f0, g=g0,
        s_hist=jnp.zeros((m, d, n_lanes), w0.dtype),
        y_hist=jnp.zeros((m, d, n_lanes), w0.dtype),
        rho=jnp.zeros((m, n_lanes), w0.dtype),
        n_pairs=jnp.zeros((n_lanes,), jnp.int32),
        it=jnp.zeros((n_lanes,), jnp.int32),
        evals=jnp.ones((n_lanes,), jnp.int32),
        converged=gnorm0 <= tol,
        failed=jnp.zeros((n_lanes,), bool),
        stalls=jnp.zeros((n_lanes,), jnp.int32),
        values=values, grad_norms=gnorms,
    )
    d_dir, gd, alpha0 = _begin_search(solve)
    init = _Lanes(solve=solve, d=d_dir, gd=gd, alpha=alpha0,
                  halvings=jnp.zeros((n_lanes,), jnp.int32))

    def cond(carry):
        return jnp.any(_running(carry[0].solve, config))

    def body(carry):
        state, trips = carry
        return _trip(evaluate, state, tol, config), trips + 1

    state, trips = lax.while_loop(cond, body, (init, jnp.int32(0)))
    final = state.solve
    return OptimizerResult(
        w=lanes_first(final.w), value=final.f, grad_norm=_norm(final.g),
        iterations=final.it, evaluations=final.evals,
        converged=final.converged,
        values=final.values.T, grad_norms=final.grad_norms.T,
        hvps=jnp.zeros_like(final.it),
    ), trips + 1
