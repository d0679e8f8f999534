"""Shared optimizer configuration, result, and per-iteration state tracking.

Counterparts of the reference's optimizer plumbing
(``photon-lib/.../optimization/{Optimizer, OptimizerConfig, OptimizerState,
OptimizationStatesTracker}.scala``) re-imagined for XLA: the whole optimizer
runs on-device inside one ``lax.while_loop``, so the state "tracker" is a pair
of fixed-length device arrays (value, gradient-norm per iteration) written with
dynamic indexing — readable after the fact exactly like the reference's
iteration table in the Photon log.

Convergence semantics follow the reference/breeze:
- gradient-norm tolerance **relative to the initial gradient norm**
  (``normOfGradient <= tolerance * initialNormOfGradient``), and
- maximum iteration cap.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array

#: ``fun(w) -> (value, grad)`` — the only thing optimizers know about models.
ValueAndGrad = Callable[[Array], Tuple[Array, Array]]
#: ``hvp(w, v) -> H @ v`` for TRON.
Hvp = Callable[[Array, Array], Array]


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Static optimizer configuration (shapes compile into the XLA program).

    Defaults mirror the reference's ``OptimizerConfig`` /
    ``GLMOptimizationConfiguration`` defaults: tolerance 1e-6 relative
    gradient norm (breeze's practical floor for an Armijo-type search in
    double precision), L-BFGS history 10.
    """

    max_iterations: int = 80
    tolerance: float = 1e-6
    history: int = 10  # L-BFGS/OWLQN memory
    max_line_search: int = 25
    cg_max_iterations: int = 30  # TRON inner CG cap
    track_states: bool = True

    def __post_init__(self) -> None:
        if self.max_iterations < 1:
            raise ValueError("max_iterations must be >= 1")
        if self.history < 1:
            raise ValueError("history must be >= 1")
        if not self.tolerance > 0:
            raise ValueError("tolerance must be > 0")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class OptimizerResult:
    """What every minimizer returns (a pytree, so it can flow out of jit/vmap).

    ``values``/``grad_norms`` are fixed-length ``(max_iterations + 1,)`` traces
    padded with +inf beyond ``iterations`` — the reference's
    ``OptimizationStatesTracker`` as arrays.

    ``evaluations`` counts the calls of the value-and-gradient function the
    solve made, the one at ``w0`` included: ``iterations + 1`` when no line
    search (or trust region) rejected a trial point, more by one for each
    rejected one. TRON's Hessian-vector products are not evaluations:
    they are ``hvps``, the products the solve's conjugate-gradient loops
    made (each one pass over the design, as an evaluation is), zero from
    every minimizer that makes none.
    """

    w: Array
    value: Array
    grad_norm: Array
    iterations: Array  # int32 scalar
    evaluations: Array  # int32 scalar
    converged: Array  # bool scalar
    values: Array
    grad_norms: Array
    hvps: Array  # int32 scalar


def init_trace(config: OptimizerConfig, f0: Array, gnorm0: Array) -> tuple[Array, Array]:
    """Allocate the per-iteration (value, grad-norm) trace, or empty arrays
    when ``track_states`` is off (e.g. vmapped per-entity solves where the
    trace would be carried through every lane)."""
    if not config.track_states:
        empty = jnp.zeros((0,), dtype=jnp.float32)
        return empty, empty
    n = config.max_iterations + 1
    # +inf (not NaN) padding beyond the recorded iterations: consumers
    # filter with isfinite either way, and NaN padding would trip
    # jax_debug_nans (the --debug-nans driver flag) on allocation
    values = jnp.full((n,), jnp.inf, dtype=jnp.float32).at[0].set(
        f0.astype(jnp.float32))
    gnorms = jnp.full((n,), jnp.inf, dtype=jnp.float32).at[0].set(
        gnorm0.astype(jnp.float32))
    return values, gnorms


def record_trace(values: Array, gnorms: Array, it: Array, f: Array, gnorm: Array):
    if values.shape[0] == 0:  # tracking disabled
        return values, gnorms
    return values.at[it].set(f.astype(jnp.float32)), gnorms.at[it].set(
        gnorm.astype(jnp.float32))


def armijo_backtracking(trial, sufficient, alpha0: Array, max_steps: int,
                        active: Array):
    """Generic halving backtracking search shared by L-BFGS and OWL-QN.

    ``trial(alpha) -> (w_t, f_t, g_t)`` evaluates a candidate step (OWL-QN's
    trial includes the orthant projection); ``sufficient(alpha, w_t, f_t) ->
    bool`` is the acceptance predicate and MUST be written so NaN trial values
    return False (e.g. ``f_t <= bound``), which makes overflowing trial steps
    shrink instead of exiting the loop.

    ``active`` is the caller's own loop condition for this solve. Under
    ``vmap`` the loop below runs while ANY lane's condition holds, and a lane
    whose solve has ended (gradient ~ 0, so ``f_t <= f + c1*alpha*g.d`` fails
    by rounding) would hold every other lane of the batch for ``max_steps``
    halvings in every outer iteration: an inactive lane makes the one trial
    at ``alpha0`` that the loop's shape requires and asks for no more.

    Returns ``(alpha, w_t, f_t, g_t, ok, trials)``; ``trials`` (int32) is the
    number of calls of ``trial`` made, the first at ``alpha0`` included.
    """
    def cond(st):
        alpha, w_t, f_t, _, ls = st
        return active & (~sufficient(alpha, w_t, f_t)) & (ls < max_steps)

    def body(st):
        alpha = st[0] * 0.5
        w_t, f_t, g_t = trial(alpha)
        return alpha, w_t, f_t, g_t, st[4] + 1

    w1, f1, g1 = trial(alpha0)
    alpha, w_t, f_t, g_t, ls = jax.lax.while_loop(
        cond, body, (alpha0, w1, f1, g1, jnp.int32(0)))
    ok = sufficient(alpha, w_t, f_t) & jnp.isfinite(f_t)
    return alpha, w_t, f_t, g_t, ok, ls + 1


def update_history(s_hist: Array, y_hist: Array, rho: Array, n_pairs: Array,
                   step: Array, y: Array, accept: Array, eps: float = 1e-10):
    """Conditionally push an (s, y) curvature pair into the ring buffers.

    Shared by L-BFGS and OWL-QN; pairs are stored only when the step was
    accepted and the curvature ``s.y`` is meaningfully positive.
    """
    m = s_hist.shape[0]
    sy = jnp.vdot(step, y)
    store = accept & (sy > eps * jnp.linalg.norm(step) * jnp.linalg.norm(y))
    pos = jnp.mod(n_pairs, m)
    s_hist = jnp.where(store, s_hist.at[pos].set(step), s_hist)
    y_hist = jnp.where(store, y_hist.at[pos].set(y), y_hist)
    rho = jnp.where(store, rho.at[pos].set(1.0 / jnp.maximum(sy, eps)), rho)
    n_pairs = jnp.where(store, n_pairs + 1, n_pairs)
    return s_hist, y_hist, rho, n_pairs
