"""GAME coordinates: one trainable block of the additive model.

Re-design of ``photon-api/.../algorithm/{Coordinate, FixedEffectCoordinate,
RandomEffectCoordinate}.scala``. A coordinate owns its dataset and
optimization problem; ``train(offsets, warm_start)`` fits against the
residual offsets coordinate descent supplies and returns (model, scores)
where ``scores`` is this coordinate's margin contribution per global sample.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.game.data import (
    FixedEffectDataset,
    GameData,
    RandomEffectDataset,
)
from photon_ml_tpu.game.model import (
    FixedEffectModel,
    RandomEffectModel,
)
from photon_ml_tpu.game.random_effect import RandomEffectSolver
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration, OptimizationProblem
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.sampling import DownSampler
from photon_ml_tpu.telemetry import profiling
from photon_ml_tpu.types import TaskType

CoordinateModel = Union[FixedEffectModel, RandomEffectModel]


@lru_cache(maxsize=None)
def _fixed_train_fn(task: TaskType, config: GLMOptimizationConfiguration):
    """One compiled fixed-effect train step per (task, config).

    ``fused=True`` engages the one-pass Pallas value+grad (and Hvp) kernels
    on TPU for dense designs (transparent fallback otherwise —
    ops/pallas_glm.py). The mesh-sharded variant below enables them inside
    its shard_map bodies too, both validated on-chip through a mesh.
    ``profile_jit`` (vs a bare ``jax.jit``) adds the compile/execute
    accounting the flat-recompile contract asserts on — the solve program
    must compile once per (task, config, shapes) and never again across
    sweeps or grid points."""
    problem = OptimizationProblem(
        GLMObjective(loss=loss_for_task(task), fused=True), config)

    def train(data, w0, lam):
        result = problem.run(data, w0, lam)
        variances = problem.compute_variances(result.w, data, lam)
        scores = data.design.matvec(result.w)
        return result, variances, scores

    return profiling.profile_jit(train, "game.fixed_effect")


@lru_cache(maxsize=None)
def _fixed_train_fn_dist(task: TaskType, config: GLMOptimizationConfiguration,
                         mesh):
    """Mesh-sharded variant: the same OptimizationProblem drives the
    shard_map/psum objective (the collapse of the reference's Distributed vs
    SingleNode class split — SURVEY.md §2.3). ``data`` is the stacked
    per-device layout from ``shard_glm_data``. ``fused=True``: the one-pass
    Pallas value+grad kernel runs inside the shard_map body too (validated
    on-chip through a mesh: 1.31x over the XLA closed form per shard; the
    kernel's out_shapes carry the block's vma so the checker accepts it)."""
    from photon_ml_tpu.parallel.distributed import DistributedGLMObjective

    dist = DistributedGLMObjective(
        objective=GLMObjective(loss=loss_for_task(task), fused=True),
        mesh=mesh)
    problem = OptimizationProblem(dist, config)

    def train(data, w0, lam):
        result = problem.run(data, w0, lam)
        variances = problem.compute_variances(result.w, data, lam)
        # offset-free margins: CD owns the additive-score accounting
        no_off = dataclasses.replace(
            data, offsets=jnp.zeros_like(data.offsets))
        scores = dist.margins(result.w, no_off)  # (n_shards, per)
        return result, variances, scores

    return profiling.profile_jit(train, "game.fixed_effect.dist")


@lru_cache(maxsize=None)
def _factored_projection_cache(task: TaskType,
                               config: GLMOptimizationConfiguration, mesh):
    """One compiled distributed projection solve per (task, config, mesh)
    for the multi-process factored coordinate: the implicit Khatri-Rao
    design shards over the data axis and the solve psums — the same
    machinery as the distributed fixed effect, driving ``vec(P)``."""
    from photon_ml_tpu.parallel.distributed import DistributedGLMObjective

    dist = DistributedGLMObjective(
        objective=GLMObjective(loss=loss_for_task(task)), mesh=mesh)
    problem = OptimizationProblem(dist, config)

    def run(data, w0, lam):
        return problem.run(data, w0, lam)

    return profiling.profile_jit(run, "game.factored_projection")


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinate:
    """Cluster-wide GLM solve for the global coordinate
    (reference ``algorithm/FixedEffectCoordinate.scala``).

    The solve is a single compiled on-device optimizer run; per-CD-iteration
    down-sampling (reference behavior for dominant-class data) reweights via
    the coordinate's :class:`DownSampler`, applied to a fresh weight vector
    each sweep.
    """

    coordinate_id: str
    dataset: FixedEffectDataset
    task: TaskType
    config: GLMOptimizationConfiguration
    lam: float = 0.0
    downsampler: Optional[DownSampler] = None

    def __post_init__(self):
        self.config.regularization.check_weight(self.lam)

    def train(self, offsets,
              warm_start: Optional[FixedEffectModel] = None,
              sweep: int = 0) -> tuple[FixedEffectModel, jax.Array]:
        """``offsets`` may be host numpy or a device array (coordinate
        descent keeps the residual accounting on device); the returned
        ``scores`` is a device vector."""
        data = self.dataset.glm_data(offsets)
        if self.downsampler is not None:
            # uids = global row ids in the data's layout (the stacked dp
            # layout is contiguous row blocks, so a plain arange reshape is
            # the id map; padded tail rows draw too but carry weight 0).
            # Keyed draws make the sample identical across 1-chip, dp, and
            # multi-process runs of the same data.
            labels_np = np.asarray(data.labels)
            uids = np.arange(labels_np.size, dtype=np.int64).reshape(
                labels_np.shape)
            weights = self.downsampler.downsample(
                labels_np, np.asarray(data.weights), sweep=sweep, uids=uids)
            data = dataclasses.replace(data, weights=jnp.asarray(weights))
        w0 = (jnp.zeros((self.dataset.dim,), jnp.float32)
              if warm_start is None
              else jnp.asarray(warm_start.model.coefficients.means))
        if self.dataset.n_shards > 1:
            from photon_ml_tpu.parallel.mesh import replicated

            # the cold start (zeros on one device) and the warm start (the
            # previous solve's output, replicated over the mesh) must reach
            # the program under ONE placement, or sweep 1 recompiles it
            w0 = jax.device_put(w0, replicated(self.dataset.mesh))
            train_fn = _fixed_train_fn_dist(self.task, self.config,
                                            self.dataset.mesh)
        else:
            train_fn = _fixed_train_fn(self.task, self.config)
        from photon_ml_tpu.telemetry import tracing

        # the counts ride the span as device values (resolved when the
        # record is read or written): the step dispatches without a wait
        with tracing.span("glm.solve", coordinate=self.coordinate_id,
                          regularization_weight=float(self.lam),
                          rows=self.dataset.n_samples,
                          dim=self.dataset.dim) as solve:
            result, variances, scores = train_fn(
                data, w0, jnp.asarray(self.lam, jnp.float32))
            solve.set(iterations=result.iterations,
                      evaluations=result.evaluations,
                      hvps=result.hvps,
                      converged=result.converged)
        tracing.set_on_enclosing("cd.step", evaluations=result.evaluations)
        if tracing.enabled():
            # the reference's OptimizationStatesTracker table, folded into
            # trace.jsonl + the metrics registry. Gated: reading the trace
            # arrays syncs the device, which a bare run's async dispatch
            # must not pay.
            from photon_ml_tpu.telemetry import record_optimizer_trace

            record_optimizer_trace(self.coordinate_id, result, sweep=sweep)
        scores = scores.reshape(-1)
        if self.dataset.n_shards > 1:
            scores = scores[:self.dataset.n_samples]  # drop tail padding
        model = FixedEffectModel(
            model=GeneralizedLinearModel(
                coefficients=Coefficients(means=result.w, variances=variances),
                task=self.task),
            feature_shard_id=self.dataset.feature_shard_id)
        return model, scores


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinate:
    """Per-entity solves for one random-effect coordinate
    (reference ``algorithm/RandomEffectCoordinate.scala``).

    Active samples are scored in the bucket layout on device; passive
    samples score on device too via the cached static key-table join
    (:meth:`_passive_scores_device`), with the model's host-side join as
    the fallback for projected/loaded models. Unseen future data goes
    through the model/transformer host path.
    """

    coordinate_id: str
    dataset: RandomEffectDataset
    data: GameData  # for passive scoring
    task: TaskType
    config: GLMOptimizationConfiguration
    lam: float = 0.0
    #: optional mesh with an ``"entity"`` axis → entity-parallel solves
    #: (reference ``RandomEffectDatasetPartitioner`` sharding).
    mesh: Optional[object] = None
    #: "float32" or "bfloat16" — see RandomEffectCoordinateConfig
    design_dtype: str = "float32"

    def __post_init__(self):
        self.config.regularization.check_weight(self.lam)

    @property
    def solver(self) -> RandomEffectSolver:
        return RandomEffectSolver(task=self.task, config=self.config,
                                  mesh=self.mesh,
                                  design_dtype=self.design_dtype)

    def train(self, offsets,
              warm_start: Optional[RandomEffectModel] = None,
              sweep: int = 0) -> tuple[RandomEffectModel, jax.Array]:
        shard_dim = self.data.shards[self.dataset.config.feature_shard_id].dim
        model, scores = self.solver.train(
            self.dataset, offsets, self.lam, warm_start, dim=shard_dim)
        passive = self.dataset.passive_sample_idx
        if len(passive):
            # reference passiveData scoring: trained model, scored-only rows
            if (model.coeffs_device is not None and len(model.keys)
                    and model.projector is None):
                scores = self._passive_scores_device(model, scores)
            else:
                # host join fallback (projected / loaded / empty models)
                scores = scores.at[passive].set(
                    jnp.asarray(model.score(self.data, sample_idx=passive)))
        return model, scores

    def _passive_scores_device(self, model: RandomEffectModel,
                               scores: jax.Array) -> jax.Array:
        """Passive rows scored on device: the (entity, feature) → table-slot
        join is STATIC across sweeps (the model's key set is determined by
        the dataset, not the coefficients), so the searchsorted positions,
        found-masks and per-row segment ids are computed once on host and
        cached; each sweep is then one gather from the model's device
        coefficient table + a segment-sum — no host join, no per-sweep H2D
        of O(passive) scores."""
        cache = self.dataset._device_cache
        entry = cache.get(("passive",))
        if entry is not None:
            # the join is only static for THIS model's key table — a model
            # trained from a different dataset in-process must not reuse it
            # (mirrors the warm-start cache's key-table guard)
            keys_cached, ctx = entry
            if not np.array_equal(keys_cached, model.keys):
                entry = None
        if entry is None:
            from photon_ml_tpu.game.model import key_join

            passive = self.dataset.passive_sample_idx
            shard = self.data.shards[self.dataset.config.feature_shard_id]
            sub = shard.take(passive)
            rows = sub.rows()
            ents = self.data.id_columns[
                self.dataset.config.random_effect_type][passive][rows]
            pos, found = key_join(model.keys, model.dim, ents, sub.cols)
            ctx = (jnp.asarray(sub.vals), jnp.asarray(pos),
                   jnp.asarray(found), jnp.asarray(rows),
                   jnp.asarray(passive), len(passive))
            cache[("passive",)] = (np.array(model.keys, copy=True), ctx)
        vals_d, pos_d, found_d, rows_d, passive_d, n_passive = ctx
        sc = _passive_segment_scores(
            model.coeffs_device, vals_d, pos_d, found_d, rows_d, n_passive)
        return scores.at[passive_d].set(sc)


@partial(jax.jit, static_argnames=("n_passive",))
def _passive_segment_scores(coeffs_device, vals_d, pos_d, found_d, rows_d,
                            n_passive: int):
    coeff = jnp.where(found_d,
                      jnp.take(coeffs_device, pos_d, mode="clip"), 0.0)
    return jax.ops.segment_sum(
        (vals_d * coeff).astype(jnp.float32), rows_d,
        num_segments=n_passive, indices_are_sorted=True)


Coordinate = Union[FixedEffectCoordinate, RandomEffectCoordinate]
