"""GLM optimization problems: objective + optimizer + regularization in one box.

Re-design of the reference's optimization-problem layer
(``photon-api/.../optimization/game/GeneralizedLinearOptimizationProblem.scala``,
``DistributedOptimizationProblem.scala``, ``SingleNodeOptimizationProblem.scala``
and ``optimization/GLMOptimizationConfiguration.scala``).

The reference splits distributed vs single-node problems because the former
aggregates over an RDD and the latter over a local Iterable. Here both are the
*same* pure functions — the distinction collapses to whether the value/grad
closure contains a ``psum`` (see :mod:`photon_ml_tpu.parallel.distributed`).
One ``OptimizationProblem`` serves the fixed effect on a pod and, vmapped, a
million random-effect entities.

Optimizer dispatch follows the reference exactly: an L1/elastic-net
regularization context selects OWLQN (the L1 part handled by orthant
projection, never differentiated); TRON may be requested explicitly and uses
the objective's closed-form Hessian-vector products; otherwise L-BFGS. The
regularization weight ``lam`` is a *dynamic* scalar so a single XLA
compilation serves the whole warm-start lambda sweep.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.ops.objective import GLMData, GLMObjective
from photon_ml_tpu.ops.regularization import NoRegularization, RegularizationContext
from photon_ml_tpu.optimize import (
    OptimizerConfig,
    OptimizerResult,
    minimize_lbfgs,
    minimize_owlqn,
    minimize_tron,
)
from photon_ml_tpu.optimize.lbfgs import (
    LanePasses,
    minimize_lbfgs_lanes,
    stage_widths,
    vmapped_evaluation,
)
from photon_ml_tpu.types import OptimizerType, VarianceComputationType

Array = jax.Array

#: Optional wrapper installed around raw (value, grad)/(hvp) closures —
#: the distributed layer injects psum here (photon_ml_tpu.parallel).
ObjectiveWrapper = Callable[[Callable], Callable]


@dataclasses.dataclass(frozen=True)
class GLMOptimizationConfiguration:
    """Per-problem optimization settings (reference
    ``GLMOptimizationConfiguration.scala``)."""

    optimizer: OptimizerType = OptimizerType.LBFGS
    regularization: RegularizationContext = NoRegularization
    optimizer_config: OptimizerConfig = OptimizerConfig()
    variance_type: VarianceComputationType = VarianceComputationType.NONE

    def __post_init__(self) -> None:
        if self.optimizer == OptimizerType.TRON and self.regularization.has_l1:
            raise ValueError(
                "TRON needs a twice-differentiable objective; L1/elastic-net "
                "requires OWLQN (as in the reference)")

    @property
    def solver(self) -> OptimizerType:
        """The minimizer a solve under this configuration runs: TRON when
        asked for, OWL-QN whenever the regularization has an L1 part, else
        L-BFGS (:meth:`OptimizationProblem.run`'s dispatch)."""
        if self.optimizer == OptimizerType.TRON:
            return OptimizerType.TRON
        if self.regularization.has_l1:
            return OptimizerType.OWLQN
        return OptimizerType.LBFGS


@dataclasses.dataclass(frozen=True)
class OptimizationProblem:
    """A ready-to-run GLM solve: minimizes
    ``sum_i w_i l(margin_i, y_i) + 0.5*l2*||w||^2 (+ l1*||w||_1)``.

    All methods are pure and jit/vmap-safe; ``lam`` (the total regularization
    weight, split into l1/l2 by the regularization context) is a traced
    scalar.
    """

    objective: GLMObjective
    config: GLMOptimizationConfiguration = GLMOptimizationConfiguration()

    def _split(self, lam) -> tuple[Array, Array]:
        reg = self.config.regularization
        lam = jnp.asarray(lam, jnp.result_type(float))
        return reg.l1_weight(lam), reg.l2_weight(lam)

    def run(self, data: GLMData, w0: Array, lam=0.0) -> OptimizerResult:
        """Solve from ``w0`` (the warm-start hook) at regularization ``lam``."""
        l1, l2 = self._split(lam)
        fun = lambda w: self.objective.value_and_grad(w, data, l2)
        cfg = self.config.optimizer_config
        solver = self.config.solver
        if solver == OptimizerType.TRON:
            hvp = lambda w, v: self.objective.hvp(w, v, data, l2)
            # operator form only when it pays: the fused one-pass Hvp
            # kernel per CG product, d2 pass hoisted per outer iteration
            # (measured 1.5x on the TRON bench shape; forcing it onto the
            # plain closed form measured slower — see hvp_prefers_operator)
            prefers = getattr(self.objective, "hvp_prefers_operator", None)
            hvp_at = ((lambda w: self.objective.hvp_operator(w, data, l2))
                      if prefers is not None and prefers(data) else None)
            return minimize_tron(fun, hvp, w0, cfg, hvp_at=hvp_at)
        if solver == OptimizerType.OWLQN:
            return minimize_owlqn(fun, w0, l1, cfg)
        return minimize_lbfgs(fun, w0, cfg)

    def run_lanes(self, data: GLMData, w0: Array, lam=0.0
                  ) -> tuple[OptimizerResult, Optional[LanePasses]]:
        """:meth:`run` for every lane of a batch: ``data``'s leaves and
        ``w0`` lead with the lane axis, and so do the result's fields; a
        lane's result is what :meth:`run` gives it (an L-BFGS lane's up to
        the order in which a dot product over ``d`` is summed).

        A caller that knows its solves are a batch (a random-effect bucket)
        calls this, not ``vmap(run)``: an L-BFGS batch then runs the flat
        loop (:func:`minimize_lbfgs_lanes`: one evaluation a lane a trip, in
        stages that drop the lanes that have ended) and the second value is
        its :class:`LanePasses`: ``passes``, the batched evaluations it made
        (its slowest lane's evaluations), and ``lane_passes``, the lanes
        they were over. The loop is given the batch's evaluation, lanes last
        as it holds them, for each stage's lanes: the entity kernel's where
        the objective's gate says it serves such lanes (by their ``(S, D)``
        and dtype), its operands laid out once a stage and every stage as
        wide as the kernel's block plan takes; else the objective under
        ``vmap``, every stage a whole number of 128-lane rows. OWL-QN and
        TRON batches are ``vmap(run)``, whose nested loops count no passes:
        ``None``.
        """
        if self.config.solver != OptimizerType.LBFGS:
            return jax.vmap(self.run, in_axes=(0, 0, None))(
                data, w0, lam), None
        _, l2 = self._split(lam)
        objective = self.objective
        fit = objective.entity_kernel_fit(data)
        if fit is None:
            evaluation_of = lambda lanes: vmapped_evaluation(
                lambda lane, w: objective.value_and_grad(w, lane, l2), lanes)
        else:
            evaluation_of = lambda lanes: objective.entity_kernel_evaluation(
                lanes, l2)
        return minimize_lbfgs_lanes(
            evaluation_of, data, w0, self.config.optimizer_config,
            stage_widths(w0.shape[0], fit))

    # --- variance (reference VarianceComputationType SIMPLE / FULL) -------
    def compute_variances(self, w: Array, data: GLMData, lam=0.0) -> Optional[Array]:
        """Per-coefficient variance approximations of the reference:

        - SIMPLE: elementwise inverse of the Hessian diagonal
          (``HessianDiagonalAggregator`` path),
        - FULL: diagonal of the full Hessian inverse
          (``HessianMatrixAggregator`` path; small dims only).
        """
        vt = self.config.variance_type
        if vt == VarianceComputationType.NONE:
            return None
        _, l2 = self._split(lam)
        if vt == VarianceComputationType.SIMPLE:
            diag = self.objective.hessian_diagonal(w, data, l2)
            return 1.0 / jnp.maximum(diag, jnp.finfo(diag.dtype).tiny)
        h = self.objective.hessian_matrix(w, data, l2)
        # pinv, not inv: padded/unobserved feature dims (all-zero design
        # columns, e.g. random-effect bucket padding) make H singular; the
        # pseudo-inverse assigns them variance 0 instead of NaN-ing the
        # whole inverse.
        return jnp.diag(jnp.linalg.pinv(h, hermitian=True))

    def run_with_variances(self, data: GLMData, w0: Array, lam=0.0
                           ) -> tuple[Coefficients, OptimizerResult]:
        result = self.run(data, w0, lam)
        variances = self.compute_variances(result.w, data, lam)
        return Coefficients(means=result.w, variances=variances), result
