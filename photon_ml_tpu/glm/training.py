"""Single-model GLM training: the warm-start regularization sweep.

Re-design of the reference's legacy training stage
(``photon-client/src/main/scala/com/linkedin/photon/ml/ModelTraining.scala``):
train one model per regularization weight, descending, each solve warm-started
from the previous lambda's solution, then pick the best by a validation
evaluator (``Evaluation.scala`` + ``ModelSelection``).

TPU shape: the solve for every lambda reuses ONE compiled XLA program (lambda
is a traced scalar), and so does every later sweep of the process on the same
signature (the program is held across calls; normalization factors and the
regularization mask are its arguments): the first sweep costs one compile + k
solves, the next ones k solves. Normalization
is a coefficient-space reparameterization inside the objective; trained
coefficients are mapped back to original feature space before models are
returned, mirroring the reference's back-transformation at output time.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.evaluation import EvaluationResults, Evaluator, evaluate_all
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration, OptimizationProblem
from photon_ml_tpu.models import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.ops.design import design_kind
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext, NoNormalization
from photon_ml_tpu.ops.objective import GLMData, GLMObjective
from photon_ml_tpu.optimize import OptimizerResult
from photon_ml_tpu.telemetry import profiling
from photon_ml_tpu.types import TaskType

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrainedModel:
    """One (lambda, model, optimization trace) entry of the sweep."""

    regularization_weight: float
    model: GeneralizedLinearModel
    result: OptimizerResult
    evaluation: Optional[EvaluationResults] = None


def build_problem(
    task: TaskType,
    config: GLMOptimizationConfiguration,
    normalization: NormalizationContext = NoNormalization,
    reg_mask: Optional[Array] = None,
    mesh=None,
) -> OptimizationProblem:
    """The one place the sweep's optimization problem is assembled — shared
    with the diagnostics stage so bootstrap/fitting solves diagnose exactly
    the objective that trained the model.

    ``fused=True``: on TPU with a dense design and identity normalization,
    value+grad runs the one-pass Pallas kernel (1.35x in-solve — see
    ops/pallas_glm.py); every other combination transparently takes the
    closed-form/autodiff path, so the flag is safe to set unconditionally.

    With a ``mesh`` (carrying a ``data`` axis) the objective becomes the
    shard_map/psum :class:`~photon_ml_tpu.parallel.distributed.
    DistributedGLMObjective` over it — the sweep then expects the stacked
    per-device data layout (``shard_glm_data`` /
    ``global_glm_data_multihost``) and runs one psum per iteration; on a
    multi-controller job every process executes the same sweep in lockstep
    (the reference's per-iteration broadcast + treeAggregate,
    ``ModelTraining.scala``).
    """
    objective = GLMObjective(
        loss=loss_for_task(task), normalization=normalization,
        reg_mask=reg_mask, fused=True)
    if mesh is not None:
        from photon_ml_tpu.parallel.distributed import DistributedGLMObjective

        return OptimizationProblem(
            DistributedGLMObjective(objective=objective, mesh=mesh), config)
    return OptimizationProblem(objective, config)


@lru_cache(maxsize=None)
def _sweep_solve_fn(task: TaskType, config: GLMOptimizationConfiguration,
                    mesh, batched: bool):
    """One compiled sweep solve per (task, config, mesh) for the life of the
    process, as ``game/coordinate.py::_fixed_train_fn`` is for the GAME fixed
    effect: a second call of :func:`train_glm_sweep` on a signature it has
    seen traces, lowers and compiles nothing.

    ``normalization`` (a pytree) and ``reg_mask`` are ARGUMENTS of the
    program, never closed-over constants, and the problem is assembled inside
    the trace: calls with other factors of one shape share the executable and
    each gets its own numbers. ``ProfiledFunction`` keys its executables by
    tree structure and leaf shape/dtype/sharding, so identity normalization
    (no leaves: the fused Pallas path), scaling, scaling with shifts, a mask
    or none, and every data shape each get their own under the one wrapper.
    ``batched`` vmaps the solve over the lambda axis
    (:func:`train_glm_sweep_batched`). No defaults, and callers pass all four
    positionally: ``lru_cache`` keys by the form of the call, and two forms
    of one key would be two wrappers.

    The function must stay named ``run``: the benchmark reads the solve's
    device time from the programs named ``jit_run``.
    """
    def run(data, w0, lam, normalization, reg_mask):
        return build_problem(task, config, normalization, reg_mask,
                             mesh=mesh).run(data, w0, lam)

    if batched:
        # data/w0 as explicit unbatched args (in_axes=None), NOT a closure:
        # a closed-over device array becomes an HLO constant — a GB-scale
        # design baked into the program
        return profiling.profile_jit(
            jax.vmap(run, in_axes=(None, None, 0, None, None)),
            "glm.sweep_solve_batched")
    return profiling.profile_jit(run, "glm.sweep_solve")


def train_glm_sweep(
    task: TaskType,
    data: GLMData,
    regularization_weights: Sequence[float],
    config: GLMOptimizationConfiguration = GLMOptimizationConfiguration(),
    normalization: NormalizationContext = NoNormalization,
    reg_mask: Optional[Array] = None,
    initial: Optional[Array] = None,
    warm_start: bool = True,
    mesh=None,
    dim: Optional[int] = None,
) -> list[TrainedModel]:
    """Train one GLM per regularization weight with warm starts.

    Weights are processed in descending order (strongest regularization first,
    the stable warm-start direction the reference uses); the returned list
    follows that order. ``reg_mask`` excludes coefficients (e.g. the
    intercept) from regularization. With ``mesh``, ``data`` must be the
    stacked per-device layout (see :func:`build_problem`) and ``dim`` names
    the coefficient length (the stacked layout's ``dim`` property reflects
    block shapes, not the model).

    Spans (``telemetry/tracing.py``): one ``glm.sweep{solves, warm_start,
    design, optimizer}`` around the whole call, one
    ``glm.solve{regularization_weight, iterations, evaluations, hvps,
    converged}`` around each solve's dispatch, the last four
    held as the result's device scalars and read only when the record is
    (``hvps``: TRON's Hessian-vector products, zero from the others). A
    ``glm.solve`` span's ``seconds`` is the HOST's dispatch time, never the
    device's (the solves are dispatched back to back, nothing here waits for
    one): the device time of a solve is the ``jit_run`` event of a profiler
    trace. The span places the host on that timeline and carries the
    device's counts. The first solve's span holds a ``jit.compile`` only
    on the process's first call with a signature (task, config, mesh, the
    structure of ``normalization`` and ``reg_mask``, every shape): the
    compiled solve is kept across calls (:func:`_sweep_solve_fn`).
    """
    for lam in regularization_weights:
        config.regularization.check_weight(lam)

    from photon_ml_tpu.resilience import fault_point, fault_value, heartbeat
    from photon_ml_tpu.telemetry import tracing
    # fleet-metrics fold point (no-op unless --metrics-port installed a
    # hook). The lambda loop is the GLM driver's sweep boundary and is
    # collective-symmetric under --multihost: every process runs the
    # identical sorted sweep over the psum'd objective.
    from photon_ml_tpu.telemetry.aggregate import sweep_boundary

    out: list[TrainedModel] = []
    with tracing.span("glm.sweep", solves=len(regularization_weights),
                      warm_start=bool(warm_start),
                      design=design_kind(data.design),
                      optimizer=config.solver.name):
        # the eager problem serves compute_variances below; building it is
        # also where a concrete reg_mask is held to 0/1 (the traced one
        # inside the compiled solve cannot be)
        problem = build_problem(task, config, normalization, reg_mask,
                                mesh=mesh)
        # one compile serves every lambda (a traced scalar) of every call
        # with this signature; profile_jit makes that visible —
        # photon_compiles_total{fn="glm.sweep_solve"} moves once per
        # process and signature, not per call and not per lambda
        run = _sweep_solve_fn(task, config, mesh, False)
        d = data.dim if dim is None else dim
        w = jnp.zeros((d,)) if initial is None else jnp.asarray(initial)
        if mesh is not None:
            # the cold start (one device) and every warm start (a solve's
            # output, replicated over the mesh) reach the program under
            # ONE placement, or the second lambda compiles it again
            from photon_ml_tpu.parallel.mesh import replicated

            w = jax.device_put(w, replicated(mesh))

        for lam in sorted(regularization_weights, reverse=True):
            # per-lambda liveness + injection: the lambda loop is the GLM
            # driver's sweep boundary (what the GAME drivers' per-sweep
            # worker.stall / optimizer.step sites are to coordinate descent)
            heartbeat("glm.sweep")
            fault_point("worker.stall", regularization_weight=float(lam))
            with tracing.span("glm.solve",
                              regularization_weight=float(lam)) as solve:
                result = run(data, w, jnp.asarray(lam, w.dtype),
                             normalization, reg_mask)
                solve.set(iterations=result.iterations,
                          evaluations=result.evaluations,
                          hvps=result.hvps,
                          converged=result.converged)
            w_solved = fault_value("optimizer.step", result.w,
                                   regularization_weight=float(lam))
            variances = problem.compute_variances(w_solved, data, lam)
            coeffs = Coefficients(means=w_solved, variances=variances)
            model = GeneralizedLinearModel(
                coefficients=to_original_space(coeffs, normalization),
                task=task)
            out.append(TrainedModel(float(lam), model, result))
            if warm_start:
                # an injected-NaN solve must not poison the NEXT lambda's
                # warm start (nan init never recovers); the finiteness sync
                # runs only when a fault actually corrupted the value, so
                # the healthy path keeps its async dispatch untouched
                if w_solved is result.w or bool(jnp.isfinite(w_solved).all()):
                    w = w_solved
            sweep_boundary(regularization_weight=float(lam))
    return out


def train_glm_sweep_batched(
    task: TaskType,
    data: GLMData,
    regularization_weights: Sequence[float],
    config: GLMOptimizationConfiguration = GLMOptimizationConfiguration(),
    normalization: NormalizationContext = NoNormalization,
    reg_mask: Optional[Array] = None,
) -> list[TrainedModel]:
    """ALL-lambda batched sweep: one vmapped solve over the lambda axis.

    The TPU-first alternative to :func:`train_glm_sweep`'s sequential
    warm-started loop (the reference's ``ModelTraining.scala`` semantics):
    every optimizer iteration touches the design ONCE for all lambdas, so
    per-element design costs amortize K-fold. The trade: no warm starts
    (lanes are independent, each runs from zero to its own masked
    convergence) and the batched program runs until the SLOWEST lane
    stops. Results are returned in the same descending-lambda order.

    Which of the two is faster depends on the LAYOUT, by what an iteration
    costs and what a warm start saves:

    - dense: the sequential path runs the fused Pallas kernel once an
      evaluation and warm starts cut the later lanes' iterations, which
      lockstep lanes give up. The multi-row-margin kernel
      (``ops/pallas_glm.py::fused_value_and_grad_multi``, dispatched through a
      custom-vmap rule when the solve vmaps over lambda) reads the design
      once for all lanes and still runs as long as the slowest lane.
    - chunked-sparse: an evaluation is two gathers whose indices do not
      depend on lambda, so under the vmap the index traffic is shared by the
      K lanes and only the gathered tables grow K-fold.

    Neither has been measured on the present chip; what one sequential
    sparse evaluation takes there, and where, is in PERF.md (sections 5 and
    6, PR 32), and no cell runs this function.

    Use batched for wide-sparse sweeps; keep sequential (the default, and
    the reference's exact semantics) for dense designs.
    """
    for lam in regularization_weights:
        config.regularization.check_weight(lam)
    problem = build_problem(task, config, normalization, reg_mask)
    lams = sorted((float(l) for l in regularization_weights), reverse=True)

    run = _sweep_solve_fn(task, config, None, True)
    batched = run(data, jnp.zeros((data.dim,)),
                  jnp.asarray(lams, jnp.float32), normalization, reg_mask)

    out: list[TrainedModel] = []
    for i, lam in enumerate(lams):
        result = jax.tree.map(lambda x: x[i], batched)
        variances = problem.compute_variances(result.w, data, lam)
        coeffs = Coefficients(means=result.w, variances=variances)
        model = GeneralizedLinearModel(
            coefficients=to_original_space(coeffs, normalization), task=task)
        out.append(TrainedModel(float(lam), model, result))
    return out


def to_original_space(coeffs: Coefficients, normalization: NormalizationContext
                      ) -> Coefficients:
    """Map transformed-space coefficients (and variances, which scale by the
    squared factors) back to raw feature space for model output."""
    if normalization.is_identity:
        return coeffs
    means = normalization.model_to_original(coeffs.means)
    variances = coeffs.variances
    if variances is not None and normalization.factors is not None:
        variances = variances * jnp.square(normalization.factors)
    return Coefficients(means=means, variances=variances)


def validate_and_select(
    trained: Sequence[TrainedModel],
    evaluators: Sequence[Evaluator],
    validation: GLMData,
    id_tags=None,
) -> tuple[int, list[TrainedModel]]:
    """Score every swept model on validation data and pick the best by the
    FIRST evaluator (reference ``ModelSelection.selectBestModel``).

    Returns ``(best_index, trained_with_evaluations)``.
    """
    labels = np.asarray(validation.labels)
    weights = np.asarray(validation.weights)
    best_idx, best_val = 0, None
    evaluated: list[TrainedModel] = []
    primary = evaluators[0]
    for i, tm in enumerate(trained):
        scores = np.asarray(tm.model.score(validation.design, validation.offsets))
        ev = evaluate_all(evaluators, scores, labels, weights, id_tags)
        evaluated.append(dataclasses.replace(tm, evaluation=ev))
        val = ev.primary[1]
        if primary.better_than(val, best_val):
            best_idx, best_val = i, val
    return best_idx, evaluated
