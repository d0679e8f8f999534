"""Block coordinate descent over GAME coordinates.

Re-design of ``photon-api/.../algorithm/CoordinateDescent.scala``: for each
sweep, for each coordinate in the update sequence, subtract the coordinate's
previous scores from the total, train on the residual offsets, add the new
scores back, and (optionally) evaluate validation metrics. Warm starts flow
from each coordinate's previous-sweep model.

The score-accounting invariant (SURVEY.md §7 hard-parts #6): at any point,
``total = data.offsets + Σ_c scores[c]`` — verified cheaply after every
sweep; a property test asserts it to float tolerance.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Mapping, Optional, Sequence

import numpy as np

from photon_ml_tpu.evaluation import evaluate_all
from photon_ml_tpu.game.coordinate import Coordinate, CoordinateModel
from photon_ml_tpu.game.data import GameData
from photon_ml_tpu.game.model import GameModel
from photon_ml_tpu.resilience import fault_point, fault_value, heartbeat
from photon_ml_tpu.telemetry import metrics as _tmetrics
from photon_ml_tpu.types import TaskType

logger = logging.getLogger(__name__)

#: host-side dispatch wall per coordinate step (device work may still be in
#: flight — async dispatch is what lets the next coordinate's host prep
#: overlap; the sweep span is the honest total). A registry timer, not a
#: raw perf_counter pair, so the number lands in /metrics (hygiene rule 5).
_STEP_DISPATCH = _tmetrics.histogram(
    "photon_game_step_dispatch_seconds",
    "Host-side dispatch wall per committed coordinate-descent step "
    "(async: device work may continue past it)", labels=("coordinate",))


from collections.abc import Mapping as _Mapping


class _LazyScores(_Mapping):
    """The result's coordinate-score decomposition, pulled device→host on
    first access in ONE concatenated transfer. The training driver never
    reads it (it saves the model), so the common path pays neither the
    transfer nor the pipeline drain; consumers that do read it (tests, the
    accounting invariant) see a plain mapping."""

    def __init__(self, device_scores: dict, n: int):
        self._device = device_scores
        self._n = n
        self._host: dict | None = None

    def _pull(self) -> dict:
        if self._host is None:
            import jax.numpy as jnp

            keys = list(self._device)
            if keys:
                flat = np.asarray(
                    jnp.concatenate([self._device[k] for k in keys]),
                    np.float32)
                self._host = {k: flat[i * self._n:(i + 1) * self._n]
                              for i, k in enumerate(keys)}
            else:
                self._host = {}
            self._device = {}
        return self._host

    def __getitem__(self, k):
        return self._pull()[k]

    def __iter__(self):
        return iter(self._pull())

    def __len__(self):
        return len(self._device) if self._host is None else len(self._host)


@dataclasses.dataclass
class CoordinateDescentResult:
    model: GameModel
    #: this coordinate-score decomposition of the training data
    scores: dict[str, np.ndarray]
    #: per-sweep validation metric dicts (empty when no validation set)
    validation_history: list[dict[str, float]]
    #: final sweep's full evaluation (None without a validation set)
    final_evaluation: object = None  # Optional[EvaluationResults]


#: what the score-memory guard assumes per device where the backend keeps no
#: memory accounting (the CPU backend: ``memory_stats()`` is None) — host
#: memory is not the guarded resource there
_UNACCOUNTED_DEVICE_BYTES = 16 << 30


def _device_memory_bytes() -> int:
    """Per-device memory limit for the score-memory guard: the smallest
    ``bytes_limit`` over the local devices (the decomposition must fit on
    each). A TPU backend that does not report one is an error, not a
    guess."""
    import jax

    limits = []
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats and "bytes_limit" in stats:
            limits.append(int(stats["bytes_limit"]))
        elif d.platform == "tpu":
            raise RuntimeError(
                f"{d} reports no memory_stats()['bytes_limit'] (got "
                f"{stats!r}): cannot size the score-memory guard — pass "
                f"max_score_memory_bytes explicitly")
    return min(limits) if limits else _UNACCOUNTED_DEVICE_BYTES


@dataclasses.dataclass(frozen=True)
class CoordinateDescent:
    """Drives the sweep loop over an ordered update sequence.

    ``max_score_memory_bytes`` guards the memory cliff of the
    device-resident score decomposition: the run holds K+1 vectors of
    ``n_samples`` f32 on device (K coordinate scores + the running total).
    The DESIGN hits HBM first in practice (≥8x the footprint — ROADMAP
    item 5), but past ~2-3 B samples/chip the decomposition itself stops
    fitting; rather than an opaque allocator failure mid-sweep, the run
    refuses up front with guidance. ``None`` → half the device's memory;
    the sharded-score prototype (tests/test_sharded_scores.py) is the
    escape hatch when a workload genuinely crosses the cliff.
    """

    update_sequence: Sequence[str]
    n_iterations: int = 1
    max_score_memory_bytes: Optional[int] = None

    def run(
        self,
        coordinates: Mapping[str, Coordinate],
        data: GameData,
        task: TaskType,
        validation=None,  # (GameData, evaluators) | zero-arg callable -> same
        initial_models: Optional[Mapping[str, CoordinateModel]] = None,
        checkpoint=None,  # Optional[photon_ml_tpu.io.checkpoint.CheckpointManager]
        resume: bool = False,
        locked: Sequence[str] = (),
        config_fingerprint: Optional[str] = None,
        guard=None,  # Optional[photon_ml_tpu.resilience.DivergenceGuard]
    ) -> CoordinateDescentResult:
        """``locked`` coordinates (reference partial retrain via
        ``--model-input-dir``: freeze some coordinates, retrain others) keep
        their ``initial_models`` entry; their scores participate in the
        residual accounting but they are never retrained — so they need no
        entry in ``coordinates`` (and no dataset build).

        ``guard`` (a :class:`~photon_ml_tpu.resilience.DivergenceGuard`)
        checks each coordinate step's outputs for NaN/Inf: on divergence
        the step is rolled back to the last good state (re-read from
        ``checkpoint`` when one is present — the same path a crash-restart
        takes), the coordinate's regularization is bumped, and the step
        retries; past the policy's retry budget the coordinate is frozen
        at its last good model (the ``locked`` mechanism) and the run
        continues degraded. ``guard=None`` (default) is the exact
        pre-guard code path; a healthy guarded run is bit-identical since
        the checks are pure reads."""
        locked = set(locked)
        coordinates = dict(coordinates)  # guard retries may bump a lam
        for cid in locked:
            if not initial_models or cid not in initial_models:
                raise KeyError(
                    f"locked coordinate {cid!r} needs an initial model")
        for cid in self.update_sequence:
            if cid not in coordinates and cid not in locked:
                raise KeyError(f"update sequence names unknown coordinate {cid!r}")

        import jax.numpy as jnp

        # memory-cliff guard: K coordinate score vectors + the running
        # total, all device-resident f32 for the whole run
        score_bytes = (len(self.update_sequence) + 1) * data.n_samples * 4
        budget = (self.max_score_memory_bytes
                  if self.max_score_memory_bytes is not None
                  else _device_memory_bytes() // 2)
        if score_bytes > budget:
            raise ValueError(
                f"score decomposition needs {score_bytes / 2**30:.1f} GiB "
                f"device memory ({len(self.update_sequence)}+1 vectors x "
                f"{data.n_samples} samples x 4 B) — over the "
                f"{budget / 2**30:.1f} GiB budget. Shard the run across "
                f"more chips/processes (game/multiprocess.py), or raise "
                f"max_score_memory_bytes if you know the design fits; the "
                f"data-sharded score prototype is "
                f"tests/test_sharded_scores.py (ROADMAP item 5)")

        models: dict[str, CoordinateModel] = dict(initial_models or {})
        # The score decomposition lives ON DEVICE for the whole run (ROADMAP
        # "score-path device residency"): residual arithmetic and the
        # coordinates' score gathers/scatters happen where the margins are
        # computed, so a CD sweep moves no O(n_samples) vectors host↔device.
        # Host copies are made only at checkpoint saves and in the result.
        scores: dict[str, jnp.ndarray] = {
            cid: jnp.zeros(data.n_samples, jnp.float32)
            for cid in self.update_sequence}
        # host mirror for checkpointing: synced incrementally (only the
        # just-trained coordinate is copied back per step) so a checkpointed
        # run still moves one score vector D2H per coordinate step, not K
        host_scores: dict[str, np.ndarray] = {
            cid: np.zeros(data.n_samples, np.float32)
            for cid in self.update_sequence}
        # seed scores from initial models (partial-retrain warm start path)
        for cid, model in models.items():
            if cid in scores:
                host_scores[cid] = model.score(data).astype(np.float32)
                scores[cid] = jnp.asarray(host_scores[cid])

        start_sweep, start_coord = 0, 0
        if resume and checkpoint is not None and checkpoint.latest_step() is not None:
            state = checkpoint.restore(expected_fingerprint=config_fingerprint)
            models = dict(state.model.coordinates)
            for k, v in state.scores.items():
                if k in scores:
                    host_scores[k] = np.asarray(v, np.float32)
                    scores[k] = jnp.asarray(host_scores[k])
            start_sweep, start_coord = state.sweep, state.coordinate_index
            logger.info("resumed from checkpoint: sweep %d coordinate %d",
                        start_sweep, start_coord)
        # all-zero offsets (no base margin — the common case) skip their
        # 4 B/row upload; the host scan costs ~0.5 ms/1M rows
        if data.offsets.size and not data.offsets.any():
            total = sum(scores.values()) + jnp.zeros(
                data.n_samples, jnp.float32)
        else:
            total = jnp.asarray(data.offsets, jnp.float32) \
                + sum(scores.values())

        # --- telemetry (live only under --telemetry-dir: the loss/grad-norm
        # reads force a device sync per step, which a bare run's async
        # dispatch pipeline must not pay) ---------------------------------
        from photon_ml_tpu.telemetry import aggregate as fleet
        from photon_ml_tpu.telemetry import tracing
        telemetry_on = tracing.enabled()
        if telemetry_on:
            from photon_ml_tpu.ops.losses import loss_for_task
            from photon_ml_tpu.telemetry import metrics as tmetrics

            _loss = loss_for_task(task)
            _labels_d = jnp.asarray(data.labels, jnp.float32)
            _weights_d = jnp.asarray(data.weights, jnp.float32)
            _loss_gauge = tmetrics.gauge(
                "photon_game_coordinate_loss",
                "Weighted data objective (no regularizer) after the "
                "coordinate's step", labels=("coordinate",))
            _gnorm_gauge = tmetrics.gauge(
                "photon_game_coordinate_grad_norm",
                "Norm of the weighted margin gradient after the "
                "coordinate's step", labels=("coordinate",))
            _steps_total = tmetrics.counter(
                "photon_game_coordinate_steps_total",
                "Committed coordinate-descent steps",
                labels=("coordinate",))

        history: list[dict[str, float]] = []
        final_evaluation = None
        for sweep in range(start_sweep, self.n_iterations):
            heartbeat("cd.sweep")
            fault_point("worker.stall", sweep=sweep)
            with tracing.span("cd.sweep", sweep=sweep) as sweep_span:
                if telemetry_on:
                    # the training flat-recompile contract, trace-visible:
                    # every cd.sweep span carries the number of profiled-jit
                    # compiles it triggered — 0 for every sweep after the
                    # first (tests/test_telemetry.py hard-asserts this)
                    from photon_ml_tpu.telemetry import profiling

                    _compiles_at_sweep_start = profiling.total_compiles()
                for ci, cid in enumerate(self.update_sequence):
                    if sweep == start_sweep and ci < start_coord:
                        continue
                    if cid in locked:
                        continue  # frozen: scores stay as seeded
                    if (guard is not None and cid in guard.frozen
                            and cid in models):
                        # diverged earlier THIS fit: locked at last good
                        # model. A fresh configuration (no model yet — e.g.
                        # the next grid point sharing the guard) retrains:
                        # its new regularization may well not diverge.
                        continue
                    heartbeat("cd.step")
                    with tracing.span("cd.step", coordinate=cid, sweep=sweep,
                                      rows=data.n_samples) as step_span, \
                            _STEP_DISPATCH.labels(
                                coordinate=cid).time() as dispatch_timer:
                        while True:
                            residual = total - scores[cid]
                            try:
                                model, new_scores = coordinates[cid].train(
                                    residual, models.get(cid), sweep=sweep)
                                new_scores = fault_value(
                                    "optimizer.step", new_scores,
                                    coordinate=cid, sweep=sweep)
                                step_error = None
                            except FloatingPointError as e:
                                # jax_debug_nans (--debug-nans) reports a
                                # non-finite value by raising: that IS
                                # divergence. Any other exception — a
                                # compiler refusal, an out-of-memory, a bug
                                # — is not, and propagates: freezing on it
                                # would end the run with exit 0 and an
                                # untrained coordinate.
                                if guard is None:
                                    raise
                                model, new_scores, step_error = None, None, e
                            if guard is None or (step_error is None
                                                 and guard.healthy(
                                                     model, new_scores)):
                                break  # healthy: commit below
                            action = guard.on_divergence(
                                cid, sweep=sweep,
                                has_good_model=cid in models,
                                error=step_error)
                            if action == "freeze":
                                new_scores = None  # keep last good state
                                break
                            # roll back to the last durable state: nothing
                            # was committed in-process, and when a
                            # checkpoint manager is present the state is
                            # re-read from disk so recovery exercises the
                            # exact crash-restart path
                            if (checkpoint is not None
                                    and checkpoint.latest_step() is not None):
                                state = checkpoint.restore(
                                    expected_fingerprint=config_fingerprint)
                                models = dict(state.model.coordinates)
                                for k, v in state.scores.items():
                                    if k in scores:
                                        host_scores[k] = np.asarray(
                                            v, np.float32)
                                        scores[k] = jnp.asarray(
                                            host_scores[k])
                                total = jnp.asarray(data.offsets,
                                                    jnp.float32) \
                                    + sum(scores.values())
                            # regularization backoff: stronger curvature is
                            # the standard fix for a diverged GLM solve
                            coord = coordinates[cid]
                            if hasattr(coord, "lam"):
                                coordinates[cid] = dataclasses.replace(
                                    coord, lam=guard.next_lam(coord.lam))
                        if new_scores is None:
                            continue  # frozen mid-sweep: nothing to commit
                        models[cid] = model
                        total = residual + new_scores
                        scores[cid] = new_scores
                        if telemetry_on:
                            # progress of the BLOCK objective CD minimizes:
                            # loss of the committed total margin, and the
                            # norm of its margin gradient (≈ how much signal
                            # is left for later coordinates to absorb)
                            margins = total.astype(jnp.float32)
                            obj = float(jnp.sum(
                                _weights_d * _loss.loss(margins, _labels_d)))
                            gnorm = float(jnp.linalg.norm(
                                _weights_d * _loss.d1(margins, _labels_d)))
                            step_span.set(loss=obj, grad_norm=gnorm)
                            _loss_gauge.labels(coordinate=cid).set(obj)
                            _gnorm_gauge.labels(coordinate=cid).set(gnorm)
                            _steps_total.labels(coordinate=cid).inc()
                        # dispatch time: device work may still be in flight
                        # (async dispatch is what lets the next coordinate's
                        # host prep overlap); the sweep wall is the honest
                        # total. The timer's running read keeps the log line
                        # inside the step without a second clock.
                        logger.info(
                            "sweep %d coordinate %s dispatched in %.2fs",
                            sweep, cid, dispatch_timer.elapsed())
                        if checkpoint is not None:
                            from photon_ml_tpu.io.checkpoint import (
                                CoordinateDescentState,
                            )

                            # sync ONLY the trained coordinate to the mirror
                            host_scores[cid] = np.asarray(new_scores,
                                                          np.float32)
                            next_ci = (ci + 1) % len(self.update_sequence)
                            checkpoint.save(
                                sweep * len(self.update_sequence) + ci + 1,
                                CoordinateDescentState(
                                    sweep=sweep + (next_ci == 0),
                                    coordinate_index=next_ci,
                                    model=GameModel(
                                        coordinates=dict(models), task=task),
                                    scores=dict(host_scores)),
                                fingerprint=config_fingerprint)

                if validation is not None:
                    if callable(validation):
                        # async-ingest join point: the driver kicked the
                        # validation read off in the background; the first
                        # sweep's evaluation is its first (and only) wait
                        validation = validation()
                    vdata, evaluators = validation
                    with tracing.span("cd.validate", sweep=sweep):
                        gm = GameModel(coordinates=dict(models), task=task)
                        vscores = gm.score(vdata)
                        results = evaluate_all(
                            evaluators, vscores, vdata.labels,
                            weights=vdata.weights, id_tags=vdata.id_columns)
                    history.append(results.as_dict())
                    final_evaluation = results
                    logger.info("sweep %d validation: %s", sweep, results)
                if telemetry_on:
                    sweep_span.set(compiles=profiling.total_compiles()
                                   - _compiles_at_sweep_start)
            # fleet-metrics fold point (no-op unless --metrics-port
            # installed a hook; placed outside the cd.sweep span so the
            # fold's own wall time never pollutes the sweep timing)
            fleet.sweep_boundary(sweep=sweep)

        model = GameModel(
            coordinates={cid: models[cid] for cid in self.update_sequence},
            task=task)
        if validation is not None and final_evaluation is None:
            # sweep loop fully skipped (resume from a completed checkpoint):
            # the model is final but unevaluated — evaluate it now so the
            # caller still gets metrics
            if callable(validation):
                validation = validation()
            vdata, evaluators = validation
            vscores = model.score(vdata)
            final_evaluation = evaluate_all(
                evaluators, vscores, vdata.labels, weights=vdata.weights,
                id_tags=vdata.id_columns)
            history.append(final_evaluation.as_dict())
        return CoordinateDescentResult(
            model=model,
            scores=_LazyScores(dict(scores), data.n_samples),
            validation_history=history,
            final_evaluation=final_evaluation)
