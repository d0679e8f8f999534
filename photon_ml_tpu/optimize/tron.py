"""TRON: trust-region Newton with conjugate-gradient inner solves.

Replacement for ``photon-lib/.../optimization/TRON.scala`` (the reference's
port of LIBLINEAR's TRON). Same structure — an outer trust-region loop whose
radius adapts via the LIBLINEAR constants (eta0/1/2, sigma1/2/3), and an inner
Steihaug conjugate-gradient solve that touches the Hessian **only through
Hessian-vector products** — but both loops are nested ``lax.while_loop``s
compiled into one XLA program (SURVEY.md §7 hard part #4). The product is the
caller's: for a GLM the closed form ``Xᵀ(d2 ∘ (Xv)) + λv`` of
:meth:`GLMObjective.hvp_operator` (the counterpart of the reference's
``HessianVectorAggregator``; nothing is differentiated), whose weights ``d2``
are computed once an outer iteration and whose two contractions are one read
of the design in the Pallas kernel ``ops/pallas_glm.py::fused_hvp`` where
that serves the design. Every product is counted: ``OptimizerResult.hvps``.

On a sharded mesh each Hvp carries one ``psum``, so the inner CG is k
collectives back-to-back on ICI — the pattern that replaces the reference's
k × ``treeAggregate`` per Newton step.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optimize.common import (
    Hvp,
    OptimizerConfig,
    OptimizerResult,
    ValueAndGrad,
    init_trace,
    record_trace,
)

Array = jax.Array

# LIBLINEAR tron.cpp trust-region update constants (mirrored by the
# reference's TRON.scala).
_ETA0, _ETA1, _ETA2 = 1e-4, 0.25, 0.75
_SIGMA1, _SIGMA2, _SIGMA3 = 0.25, 0.5, 4.0
_CG_TOL = 0.1  # inner CG stops at ||r|| <= 0.1 * ||g||


def _trcg(hvp, g: Array, delta: Array, max_cg: int, active: Array):
    """Steihaug truncated CG: approximately solve H s = -g within ||s||<=delta.

    Returns ``(s, at_boundary, prered, products)`` where ``products`` (int32)
    is the loop's trip count, one Hessian-vector product a trip, and
    ``prered = -(g.s + 0.5 s.Hs)``
    is the quadratic-model reduction, tracked incrementally from CG internals
    (interior step: q -= 0.5*alpha*r.r; boundary step: q += -tau*r.r +
    0.5*tau^2*p.Hp, using the invariant r.p = r.r) so the outer loop never
    pays an extra Hessian-vector product — on a sharded mesh that is one
    avoided collective per Newton iteration. Fixed iteration cap with
    tolerance masking keeps the loop shape static for XLA. ``active`` is the
    outer loop's condition for this solve: under ``vmap`` the CG loop runs
    while any lane's is unfinished, and a lane whose solve has ended starts
    it done (its step is discarded with the rest of its iteration, and it
    counts no product: a batched ``while_loop`` holds the state of a lane
    whose own condition is false, ``i`` included).
    """
    cg_tol = _CG_TOL * jnp.linalg.norm(g)

    def cond(st):
        s, r, p, rr, q, i, done = st
        return (~done) & (i < max_cg)

    def body(st):
        s, r, p, rr, q, i, _ = st
        hp = hvp(p)
        php = jnp.vdot(p, hp)
        alpha = rr / jnp.where(php > 0, php, 1.0)
        s_next = s + alpha * p
        crossed = (jnp.linalg.norm(s_next) > delta) | (php <= 0)

        # Backtrack to the trust-region boundary along p.
        ps = jnp.vdot(p, s)
        pp = jnp.vdot(p, p)
        ss = jnp.vdot(s, s)
        disc = ps * ps + pp * (delta * delta - ss)
        tau = (-ps + jnp.sqrt(jnp.maximum(disc, 0.0))) / jnp.where(pp > 0, pp, 1.0)
        s_bound = s + tau * p

        q_interior = q - 0.5 * alpha * rr
        q_bound = q - tau * rr + 0.5 * tau * tau * php

        s_new = jnp.where(crossed, s_bound, s_next)
        q_new = jnp.where(crossed, q_bound, q_interior)
        r_new = r - alpha * hp
        rr_new = jnp.vdot(r_new, r_new)
        converged = jnp.sqrt(rr_new) <= cg_tol
        beta = rr_new / jnp.where(rr > 0, rr, 1.0)
        p_new = r_new + beta * p
        done = crossed | converged
        return (s_new, jnp.where(crossed, r, r_new), p_new,
                jnp.where(crossed, rr, rr_new), q_new, i + 1, done)

    s0 = jnp.zeros_like(g)
    r0 = -g
    init = (s0, r0, r0, jnp.vdot(r0, r0), jnp.zeros_like(jnp.vdot(r0, r0)),
            jnp.int32(0), (~active) | (jnp.linalg.norm(r0) <= cg_tol))
    s, r, p, rr, q, i, done = lax.while_loop(cond, body, init)
    at_boundary = jnp.linalg.norm(s) >= delta * (1.0 - 1e-6)
    return s, at_boundary, -q, i


def minimize_tron(fun: ValueAndGrad, hvp: Hvp, w0: Array,
                  config: OptimizerConfig = OptimizerConfig(),
                  *, hvp_at=None) -> OptimizerResult:
    """Trust-region Newton minimization of a twice-differentiable ``fun``.

    ``hvp(w, v)`` must return the exact Hessian-vector product at ``w``.
    ``hvp_at(w) -> (v -> Hv)``, when given, takes precedence: the operator
    is built once per outer iteration, so work that depends only on ``w``
    (a GLM's margin/d2 pass over the design) is hoisted out of the inner
    CG loop explicitly instead of trusting XLA's loop-invariant code
    motion, and the product itself can be a fused one-pass kernel.
    Jittable and vmappable.
    """
    f0, g0 = fun(w0)
    gnorm0 = jnp.linalg.norm(g0)
    values, gnorms = init_trace(config, f0, gnorm0)
    tol = config.tolerance * jnp.maximum(gnorm0, 1.0)

    init = _State(
        w=w0, f=f0, g=g0, delta=gnorm0,
        it=jnp.int32(0), evals=jnp.int32(1), hvps=jnp.int32(0),
        converged=gnorm0 <= tol, failed=jnp.asarray(False),
        values=values, grad_norms=gnorms,
    )

    def cond(s):
        return (~s.converged) & (~s.failed) & (s.it < config.max_iterations)

    def body(s):
        op = hvp_at(s.w) if hvp_at is not None else (lambda v: hvp(s.w, v))
        step, at_boundary, prered, products = _trcg(
            op, s.g, s.delta, config.cg_max_iterations, cond(s))
        snorm = jnp.linalg.norm(step)
        w_new = s.w + step
        f_new, g_new = fun(w_new)

        gs = jnp.vdot(s.g, step)
        # NaN-safe actual reduction: a non-finite trial value (overflowing
        # loss) must behave like "no reduction" so the radius SHRINKS and the
        # solver recovers — NaN propagating into delta would otherwise disable
        # the trust region permanently (every comparison False).
        actred = jnp.where(jnp.isfinite(f_new), s.f - f_new, -jnp.inf)

        # LIBLINEAR step-size interpolation for the radius update.
        denom = f_new - s.f - gs
        alpha = jnp.where(jnp.isfinite(denom) & (denom > 0),
                          jnp.maximum(_SIGMA1, -0.5 * (gs / jnp.where(
                              denom == 0, 1.0, denom))),
                          jnp.where(jnp.isfinite(f_new), _SIGMA3, _SIGMA1))
        delta = s.delta
        # On the very first iteration LIBLINEAR shrinks delta to min(delta, snorm).
        delta = jnp.where(s.it == 0, jnp.minimum(delta, snorm), delta)
        delta = jnp.where(
            actred < _ETA0 * prered,
            jnp.minimum(jnp.maximum(alpha, _SIGMA1) * snorm, _SIGMA2 * delta),
            jnp.where(
                actred < _ETA1 * prered,
                jnp.maximum(_SIGMA1 * delta,
                            jnp.minimum(alpha * snorm, _SIGMA2 * delta)),
                jnp.where(
                    actred < _ETA2 * prered,
                    jnp.maximum(_SIGMA1 * delta,
                                jnp.minimum(alpha * snorm, _SIGMA3 * delta)),
                    jnp.maximum(delta,
                                jnp.minimum(alpha * snorm, _SIGMA3 * delta)))))

        accept = (actred > _ETA0 * prered) & jnp.isfinite(f_new)
        # A vanishing radius means no further progress is possible.
        stuck = delta < 1e-12

        it = s.it + 1
        gnorm_acc = jnp.linalg.norm(jnp.where(accept, g_new, s.g))
        values, gnorms = record_trace(
            s.values, s.grad_norms, it,
            jnp.where(accept, f_new, s.f), gnorm_acc)
        return _State(
            w=jnp.where(accept, w_new, s.w),
            f=jnp.where(accept, f_new, s.f),
            g=jnp.where(accept, g_new, s.g),
            delta=delta, it=it,
            evals=s.evals + 1,  # the one call of ``fun`` above
            hvps=s.hvps + products,
            converged=accept & (jnp.linalg.norm(g_new) <= tol),
            failed=stuck,
            values=values, grad_norms=gnorms,
        )

    final = lax.while_loop(cond, body, init)
    return OptimizerResult(
        w=final.w, value=final.f, grad_norm=jnp.linalg.norm(final.g),
        iterations=final.it, evaluations=final.evals,
        converged=final.converged,
        values=final.values, grad_norms=final.grad_norms,
        hvps=final.hvps,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class _State:
    w: Array
    f: Array
    g: Array
    delta: Array
    it: Array
    evals: Array
    hvps: Array
    converged: Array
    failed: Array
    values: Array
    grad_norms: Array
