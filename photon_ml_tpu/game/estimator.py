"""GameEstimator: build datasets once, fit many configurations, pick the best.

Re-design of ``photon-api/.../estimators/GameEstimator.scala``: the estimator
owns the (expensive) dataset construction — fixed-effect device arrays and
random-effect bucketing happen once — then loops over hyperparameter
configurations (a grid of per-coordinate regularization weights, or points
suggested by the Bayesian search), running coordinate descent per
configuration and evaluating validation data. Returns one
:class:`GameResult` per configuration; the first validation evaluator is the
model-selection criterion (reference ``ModelSelection``).
"""

from __future__ import annotations

import dataclasses
import json
import logging
from typing import Mapping, Optional, Sequence

import jax.numpy as jnp

from photon_ml_tpu.evaluation import EvaluationResults, Evaluator
from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
from photon_ml_tpu.game.data import (
    FixedEffectDataset,
    GameData,
    RandomEffectDataset,
    RandomEffectDatasetConfig,
)
from photon_ml_tpu.game.model import GameModel
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.sampling import DownSampler
from photon_ml_tpu.telemetry import tracing
from photon_ml_tpu.types import TaskType

logger = logging.getLogger(__name__)


@dataclasses.dataclass(frozen=True)
class FixedEffectCoordinateConfig:
    """Static definition of a fixed-effect coordinate
    (reference ``FixedEffectDataConfiguration`` +
    ``FixedEffectOptimizationConfiguration``)."""

    feature_shard_id: str
    optimization: GLMOptimizationConfiguration = GLMOptimizationConfiguration()
    downsampler: Optional[DownSampler] = None
    #: "float32" (default) or "bfloat16" — the dtype the dense design is
    #: stored in on device. bfloat16 halves the HBM traffic of the
    #: dominant payload (the same trade the GLM driver's --design-dtype
    #: offers: ~1.4-1.5x solve speed for ~1e-3-digit design rounding).
    design_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class RandomEffectCoordinateConfig:
    """Static definition of a random-effect coordinate
    (reference ``RandomEffectDataConfiguration`` +
    ``RandomEffectOptimizationConfiguration``)."""

    dataset: RandomEffectDatasetConfig
    optimization: GLMOptimizationConfiguration = GLMOptimizationConfiguration()
    #: "float32" (default) or "bfloat16" — dtype of the per-entity designs
    #: on device AND on the host↔device wire (the shared dense shard image
    #: ships its values at 2 bytes under bfloat16); labels/weights/
    #: coefficients stay float32, margins accumulate in float32.
    design_dtype: str = "float32"


@dataclasses.dataclass(frozen=True)
class FactoredRandomEffectCoordinateConfig:
    """Static definition of a factored random-effect coordinate (legacy
    reference ``FactoredRandomEffectCoordinate`` — SURVEY.md §2.4).
    ``dataset.projector_type`` must be RANDOM; ``projected_dim`` is the
    latent dim."""

    dataset: RandomEffectDatasetConfig
    optimization: GLMOptimizationConfiguration = GLMOptimizationConfiguration()
    projection_optimization: GLMOptimizationConfiguration = (
        GLMOptimizationConfiguration())
    lam_projection: float = 0.0
    n_factored_iterations: int = 2


CoordinateConfig = (FixedEffectCoordinateConfig | RandomEffectCoordinateConfig
                    | FactoredRandomEffectCoordinateConfig)


@dataclasses.dataclass(frozen=True)
class GameOptimizationConfiguration:
    """One hyperparameter point: per-coordinate regularization weights
    (reference ``GameEstimator.GameOptimizationConfiguration``)."""

    regularization_weights: Mapping[str, float]

    def lam(self, coordinate_id: str) -> float:
        return float(self.regularization_weights.get(coordinate_id, 0.0))


@dataclasses.dataclass
class GameResult:
    """(model, validation evaluation, configuration) triple."""

    model: GameModel
    configuration: GameOptimizationConfiguration
    evaluation: Optional[EvaluationResults]
    validation_history: list[dict[str, float]]


@dataclasses.dataclass
class GameEstimator:
    """Fits GAME models over a training set for many configurations.

    ``mesh`` turns on multi-chip training: a ``"data"`` axis shards every
    fixed-effect solve (psum gradients inside the compiled optimizer), an
    ``"entity"`` axis shards every random-effect coordinate's bucket lanes.
    A 2D ``{"data": a, "entity": b}`` mesh does both — the layout
    ``dryrun_multichip`` validates.
    """

    task: TaskType
    coordinate_configs: Mapping[str, CoordinateConfig]
    update_sequence: Sequence[str]
    n_cd_iterations: int = 1
    mesh: Optional[object] = None
    #: plumbed to CoordinateDescent's score-memory guard (None = half the
    #: device's memory; the guard's error message names this knob)
    max_score_memory_bytes: Optional[int] = None

    def __post_init__(self):
        # coordinates may be absent from configs only if locked at fit time
        # (partial retrain); prepare()/fit() validate against ``locked``
        pass

    def _check_sequence(self, locked: Sequence[str]) -> None:
        locked = set(locked)
        for cid in self.update_sequence:
            if cid not in self.coordinate_configs and cid not in locked:
                raise KeyError(
                    f"update sequence names unknown coordinate {cid!r} "
                    f"(not configured, not locked)")
        # a locked coordinate outside the update sequence would silently
        # vanish from the model and the residual accounting — reject it
        missing = locked - set(self.update_sequence)
        if missing:
            raise ValueError(
                f"locked coordinates {sorted(missing)} must appear in the "
                f"update sequence to stay part of the model")

    # --- dataset construction (once) --------------------------------------
    def _prefetch_device_feed(self, data: GameData,
                              locked: Sequence[str]) -> None:
        """Dispatch the async host→device uploads the coordinates will need
        BEFORE the host-side bucket builds start: jax transfers are
        asynchronous, so the dense shard images / labels / weights stream
        to the device while the host packs buckets. Without this the
        transfer only starts when the first solve asks for the image —
        fully serialized after the builds."""
        from photon_ml_tpu.game.data import (
            choose_dense_design,
            design_dtype_of,
        )

        if self.mesh is not None:
            return  # sharded paths build their own per-device feeds
        seen: set = set()
        for cid in self.update_sequence:
            if cid in locked:
                continue
            cfg = self.coordinate_configs.get(cid)
            if isinstance(cfg, FixedEffectCoordinateConfig):
                sid, dt = cfg.feature_shard_id, cfg.design_dtype
            elif isinstance(cfg, RandomEffectCoordinateConfig):
                if not cfg.dataset.reads_shared_image:
                    continue
                sid, dt = cfg.dataset.feature_shard_id, cfg.design_dtype
            else:
                continue
            if (sid, dt) in seen:
                continue
            seen.add((sid, dt))
            dtype = design_dtype_of(dt)
            # same itemsize-aware rule as FixedEffectDataset.build — a
            # mismatch would skip the prefetch exactly when it matters
            if choose_dense_design(data.shards[sid], n_shards=1,
                                   itemsize=dtype.itemsize):
                data.device_dense_shard(sid, dtype=dtype)
            data.device_labels()
            data.device_weights()

    def _entity_shards(self) -> int:
        if self.mesh is None:
            return 1
        from photon_ml_tpu.parallel.mesh import ENTITY_AXIS

        return int(getattr(self.mesh, "shape", {}).get(ENTITY_AXIS, 1))

    def prepare(self, data: GameData,
                locked: Sequence[str] = ()) -> dict[str, object]:
        with tracing.span("game.prepare"):
            return self._prepare(data, locked)

    def _prepare(self, data: GameData,
                 locked: Sequence[str]) -> dict[str, object]:
        self._check_sequence(locked)
        self._prefetch_device_feed(data, locked)
        datasets: dict[str, object] = {}
        ep = self._entity_shards()
        for cid in self.update_sequence:
            if cid in locked:
                continue  # frozen coordinate: no dataset, no training
            cfg = self.coordinate_configs[cid]
            if isinstance(cfg, FixedEffectCoordinateConfig):
                with tracing.span("game.dataset.build", coordinate=cid,
                                  kind="fixed_effect", rows=data.n_samples):
                    datasets[cid] = FixedEffectDataset.build(
                        cid, data, cfg.feature_shard_id, mesh=self.mesh,
                        dtype=(jnp.bfloat16 if cfg.design_dtype == "bfloat16"
                               else jnp.float32))
            elif isinstance(cfg, FactoredRandomEffectCoordinateConfig):
                # rebuilt each alternation around the learned projection
                datasets[cid] = None
            else:
                with tracing.span("game.dataset.build", coordinate=cid,
                                  kind="random_effect",
                                  rows=data.n_samples) as sp:
                    datasets[cid] = RandomEffectDataset.build(
                        cid, data, cfg.dataset, n_entity_shards=ep)
                    sp.set(entities=datasets[cid].n_active_entities,
                           buckets=len(datasets[cid].buckets))
                logger.info(
                    "coordinate %s: %d active entities in %d buckets, "
                    "%d passive rows", cid, datasets[cid].n_active_entities,
                    len(datasets[cid].buckets),
                    len(datasets[cid].passive_sample_idx))
        # cross-coordinate residency budget BEFORE warm compiles: the warm
        # threads must compile the signatures the final (possibly flipped-
        # to-streaming) datasets will actually solve with
        self._apply_fat_budget(data, datasets)
        for cid, ds in datasets.items():
            if isinstance(ds, RandomEffectDataset):
                self._start_warm_compile(ds, self.coordinate_configs[cid],
                                         data.n_samples)
        return datasets

    def _apply_fat_budget(self, data: GameData, datasets) -> None:
        """Cross-coordinate HBM accounting (the per-build guard can't see
        it): several coordinates can each pass the per-device fat cap while
        their SUM exceeds it. Flip the largest offenders to streaming until
        the total fits, then drop any prefetched dense shard images that no
        remaining resident consumer will read — a dead multi-GiB pin in the
        memory-tight regime would defeat the guard's purpose."""
        from photon_ml_tpu.game.data import (
            RE_FAT_CACHE_MAX_BYTES,
            resident_fat_bytes,
        )

        ep = self._entity_shards()
        resident = [
            (cid, ds, resident_fat_bytes(ds.buckets) // ep)
            for cid, ds in datasets.items()
            if isinstance(ds, RandomEffectDataset) and ds.config.resident]
        total = sum(f for _, _, f in resident)
        for cid, ds, f in sorted(resident, key=lambda t: -t[2]):
            if total <= RE_FAT_CACHE_MAX_BYTES:
                break
            logger.warning(
                "coordinate %s: flipping to upload-and-drop streaming — "
                "the coordinates' combined resident fat tensors "
                "(%.1f GiB/device) exceed the %.1f GiB cap",
                cid, total / 2**30, RE_FAT_CACHE_MAX_BYTES / 2**30)
            datasets[cid] = dataclasses.replace(
                ds, config=dataclasses.replace(
                    ds.config, cache_device_buckets=False))
            total -= f
        # evict dense images with no resident consumer (streaming solvers
        # never touch the shared image; fixed effects keep theirs)
        keep = set()
        for cid, cfg in self.coordinate_configs.items():
            if isinstance(cfg, FixedEffectCoordinateConfig):
                keep.add(cfg.feature_shard_id)
            elif isinstance(cfg, RandomEffectCoordinateConfig):
                ds = datasets.get(cid)
                if (isinstance(ds, RandomEffectDataset)
                        and ds.config.reads_shared_image):
                    keep.add(cfg.dataset.feature_shard_id)
        for key in list(data._device_cache):
            if (isinstance(key, tuple) and key
                    and key[0] == "dense_shard" and key[1] not in keep):
                del data._device_cache[key]

    def _start_warm_compile(self, dataset, cfg, n: int) -> None:
        """Kick off a resident coordinate's uploads and the compile of its
        sweep program in the background so they overlap the fixed-effect
        stage (a warm driver run measured ~2.8 s of compile-cache loading
        serialized inside the first RE sweep). The solver hash (task,
        optimization config, mesh) matches the one RandomEffectCoordinate
        builds, so train() hits the same program cache, and joins this
        thread first."""
        import contextvars
        import threading

        from photon_ml_tpu.game.random_effect import RandomEffectSolver

        solver = RandomEffectSolver(task=self.task, config=cfg.optimization,
                                    mesh=self.mesh,
                                    design_dtype=cfg.design_dtype)
        # under the caller's span context: the thread's jit.compile spans
        # belong to the stage that started it, not to the trace's roots
        ctx = contextvars.copy_context()
        th = threading.Thread(
            target=lambda: ctx.run(solver._warm_compile, dataset, n),
            daemon=True)
        object.__setattr__(dataset, "_warm_thread", th)
        th.start()

    def _coordinates(self, data: GameData, datasets: Mapping[str, object],
                     config: GameOptimizationConfiguration,
                     locked: Sequence[str] = ()):
        out = {}
        for cid in self.update_sequence:
            if cid in locked:
                continue
            ccfg = self.coordinate_configs[cid]
            if isinstance(ccfg, FixedEffectCoordinateConfig):
                out[cid] = FixedEffectCoordinate(
                    coordinate_id=cid, dataset=datasets[cid], task=self.task,
                    config=ccfg.optimization, lam=config.lam(cid),
                    downsampler=ccfg.downsampler)
            elif isinstance(ccfg, FactoredRandomEffectCoordinateConfig):
                from photon_ml_tpu.game.factored import (
                    FactoredRandomEffectCoordinate,
                )

                out[cid] = FactoredRandomEffectCoordinate(
                    coordinate_id=cid, data=data,
                    dataset_config=ccfg.dataset, task=self.task,
                    config=ccfg.optimization,
                    projection_config=ccfg.projection_optimization,
                    lam=config.lam(cid),
                    lam_projection=ccfg.lam_projection,
                    n_factored_iterations=ccfg.n_factored_iterations,
                    mesh=self.mesh)
            else:
                out[cid] = RandomEffectCoordinate(
                    coordinate_id=cid, dataset=datasets[cid], data=data,
                    task=self.task, config=ccfg.optimization,
                    lam=config.lam(cid), mesh=self.mesh,
                    design_dtype=ccfg.design_dtype)
        return out

    # --- fit ---------------------------------------------------------------
    def fit(
        self,
        data: GameData,
        configurations: Sequence[GameOptimizationConfiguration],
        validation: Optional[tuple[GameData, Sequence[Evaluator]]] = None,
        datasets: Optional[Mapping[str, object]] = None,
        initial_models: Optional[Mapping[str, object]] = None,
        locked: Sequence[str] = (),
        checkpoint=None,
        resume: bool = False,
        guard=None,  # Optional[photon_ml_tpu.resilience.DivergenceGuard]
        on_result=None,  # Optional[Callable[[int, GameResult], None]]
    ) -> list[GameResult]:
        """``datasets`` (from :meth:`prepare`) lets callers that fit many
        times over the same data — e.g. a tuning loop — build the coordinate
        datasets once. ``initial_models``/``locked`` are the reference's
        partial-retrain path (warm-start from a saved GameModel; frozen
        coordinates keep their model and skip training);
        ``checkpoint``/``resume`` persist/restore coordinate-boundary state
        (single-configuration fits only — a resumed grid would mis-attribute
        the restored state to every configuration). ``guard`` is the
        resilience subsystem's divergence guard (rollback / regularization
        backoff / freeze at coordinate boundaries; see RESILIENCE.md) —
        shared across configurations so a tuning loop's failure budget is
        per-run, not per-point. ``validation`` may be a zero-arg callable
        returning the ``(GameData, evaluators)`` tuple — resolved at first
        use, so a driver can keep the validation read in flight while
        early sweeps run. ``on_result(index, result)`` fires the moment
        each configuration finishes — the async I/O pipeline's hook for
        submitting that model's background save while the remaining grid
        points still train. One ``game.fit`` span around the whole call."""
        with tracing.span("game.fit", configurations=len(configurations)):
            return self._fit(data, configurations, validation, datasets,
                             initial_models, locked, checkpoint, resume,
                             guard, on_result)

    def _fit(self, data, configurations, validation, datasets,
             initial_models, locked, checkpoint, resume, guard,
             on_result) -> list[GameResult]:
        self._check_sequence(locked)
        if checkpoint is not None and len(configurations) != 1:
            raise ValueError("checkpointing supports exactly one configuration")
        if datasets is None:
            datasets = self.prepare(data, locked=locked)
        cd = CoordinateDescent(
            update_sequence=self.update_sequence,
            n_iterations=self.n_cd_iterations,
            max_score_memory_bytes=self.max_score_memory_bytes)
        results: list[GameResult] = []
        for config in configurations:
            coordinates = self._coordinates(data, datasets, config, locked)
            # identify the whole run shape, not just the lambdas: a resumed
            # checkpoint with a different update sequence / sweep count /
            # locked set / dataset would silently mis-attribute state
            fingerprint = json.dumps({
                "weights": sorted(config.regularization_weights.items()),
                "update_sequence": list(self.update_sequence),
                "n_cd_iterations": self.n_cd_iterations,
                "locked": sorted(locked),
                "n_samples": data.n_samples,
                # every coordinate's full configuration (optimizer, bounds,
                # regularization, design dtype) — resuming under a changed
                # config must fail loudly, not blend incompatible state
                # (the multi-process fingerprint has always done this)
                "configs": {c: repr(self.coordinate_configs.get(c))
                            for c in self.update_sequence},
            }, sort_keys=True)
            cd_result = cd.run(coordinates, data, self.task,
                               validation=validation,
                               initial_models=initial_models,
                               checkpoint=checkpoint, resume=resume,
                               locked=locked,
                               config_fingerprint=fingerprint,
                               guard=guard)
            # the final CD sweep already evaluated this exact model
            evaluation = cd_result.final_evaluation
            results.append(GameResult(
                model=cd_result.model, configuration=config,
                evaluation=evaluation,
                validation_history=cd_result.validation_history))
            logger.info("configuration %s -> %s",
                        dict(config.regularization_weights), evaluation)
            if on_result is not None:
                on_result(len(results) - 1, results[-1])
        return results

    @staticmethod
    def select_best(results: Sequence[GameResult]) -> GameResult:
        """Best by the first validation evaluator (reference ModelSelection)."""
        scored = [r for r in results if r.evaluation is not None]
        if not scored:
            return results[0]
        best = scored[0]
        for r in scored[1:]:
            ev, val = r.evaluation.primary
            if ev.better_than(val, best.evaluation.primary[1]):
                best = r
        return best
