"""GAME training driver.

Re-design of ``photon-client/.../cli/game/training/GameTrainingDriver.scala``
(+ shared params on ``GameDriver.scala``): read train/validation Avro →
assemble feature shards + index maps → build the estimator's coordinate
datasets once → fit every hyperparameter configuration (explicit grid or
Bayesian GP search) → select best by the first validation evaluator → write
best (+ optionally all) models in the reference directory layout.
"""

from __future__ import annotations

import argparse
import os
from typing import Optional, Sequence

from photon_ml_tpu.cli.config import (
    add_resilience_flags,
    add_supervision_flags,
    add_telemetry_flags,
    install_resilience,
    install_telemetry,
    parse_coordinate_config,
    parse_feature_shard_config,
    parse_grid,
    resilience_from_args,
    telemetry_from_args,
)
from photon_ml_tpu.data_validation import validate_game_data
from photon_ml_tpu.evaluation import parse_evaluators
from photon_ml_tpu.game.estimator import (
    FactoredRandomEffectCoordinateConfig,
    FixedEffectCoordinateConfig,
    GameEstimator,
    GameOptimizationConfiguration,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.io import AvroDataReader
from photon_ml_tpu.logging_util import RunLogger, timed
from photon_ml_tpu.types import DataValidationType, TaskType


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_ml_tpu train_game",
        description="Train a GAME mixed-effect model (TPU)")
    p.add_argument("--training-data", required=True)
    p.add_argument("--validation-data")
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task", default="LOGISTIC_REGRESSION",
                   choices=[t.value for t in TaskType])
    p.add_argument("--feature-shards", required=True,
                   help="comma-separated shard specs, e.g. "
                        "'global=fixed|intercept,user=user+item|noIntercept'")
    p.add_argument("--coordinates", required=True, nargs="+",
                   help="coordinate specs, e.g. "
                        "'global=fixed,shard=global,reg=L2' "
                        "'perUser=random,entity=userId,shard=user,reg=L2'")
    p.add_argument("--update-sequence", required=True,
                   help="comma-separated coordinate ids")
    p.add_argument("--cd-iterations", type=int, default=1)
    p.add_argument("--grid", nargs="*", default=[],
                   help="per-coordinate lambda lists 'coordId=0.1;1;10'")
    p.add_argument("--tuning", choices=["NONE", "RANDOM", "BAYESIAN"],
                   default="NONE")
    p.add_argument("--tuning-iterations", type=int, default=10)
    p.add_argument("--tuning-range", default="1e-4:1e4",
                   help="lambda search range 'low:high' for tuning")
    p.add_argument("--evaluators", default="AUC",
                   help="comma-separated; first drives model selection")
    p.add_argument("--output-all-models", action="store_true")
    p.add_argument("--data-validation", default="VALIDATE_FULL",
                   choices=[v.value for v in DataValidationType])
    p.add_argument("--model-input-dir",
                   help="warm-start from a previous train_game output dir "
                        "(reference partial-retrain path); its feature "
                        "indexes are reused so coefficients line up")
    p.add_argument("--locked-coordinates", default="",
                   help="comma-separated coordinate ids to FREEZE (kept "
                        "from --model-input-dir, never retrained)")
    p.add_argument("--checkpoint", action="store_true",
                   help="write coordinate-boundary checkpoints under "
                        "<output-dir>/checkpoints (single-config grids)")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in "
                        "<output-dir>/checkpoints")
    p.add_argument("--debug-nans", action="store_true",
                   help="enable jax_debug_nans (fail fast on NaN; §5.2 "
                        "sanitizer equivalent)")
    p.add_argument("--design-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="storage dtype for the dense designs (fixed-effect "
                        "AND random-effect bucket tensors), on device and "
                        "on the host-device wire: bfloat16 halves the "
                        "dominant payload (~1.4-1.5x solve, ~2x feed) for "
                        "~3-digit design rounding; labels, weights and "
                        "coefficients stay float32 and margins accumulate "
                        "in float32")
    p.add_argument("--model-sparsity-threshold", type=float, default=0.0,
                   help="drop |coefficient| <= threshold from written "
                        "models (reference model-sparsity threshold)")
    p.add_argument("--input-columns", default="",
                   help="remap record fields, e.g. 'response=label,"
                        "weight=w' (reference InputColumnsNames)")
    p.add_argument("--profile", action="store_true",
                   help="write a jax.profiler trace of the training stage "
                        "to <output-dir>/profile (view with TensorBoard)")
    p.add_argument("--multihost", action="store_true",
                   help="form a multi-controller job before touching any "
                        "device (jax.distributed.initialize from "
                        "PHOTON_COORDINATOR_ADDRESS/NUM_PROCESSES/PROCESS_ID "
                        "env vars, or JAX cluster auto-detection on TPU "
                        "pods). Every process runs this same command; with "
                        ">1 process, training routes through the entity-"
                        "partitioned multi-process path: each process reads "
                        "its share of the input FILE LIST (provide at least "
                        "one file per process on a shared filesystem), "
                        "feature indexes and entity vocabularies are unioned "
                        "globally, the fixed effect trains on one global "
                        "data mesh (built automatically — do not pass "
                        "--mesh), random effects solve process-locally, and "
                        "only process 0 writes outputs. --checkpoint/"
                        "--resume persist per-process sweep-boundary state "
                        "(single-config grid). No --locked-coordinates/"
                        "--model-input-dir/--tuning yet")
    p.add_argument("--mesh", default="",
                   help="device mesh axes, e.g. 'data=4,entity=2': shards "
                        "fixed-effect samples over 'data' (psum'd compiled "
                        "optimizer) and random-effect entity lanes over "
                        "'entity'. Default: single device")
    add_resilience_flags(p)
    add_supervision_flags(p)
    add_telemetry_flags(p)
    return p


def parse_mesh(spec: str):
    """'data=4,entity=2' → Mesh (None when empty)."""
    if not spec:
        return None
    from photon_ml_tpu.parallel.mesh import make_mesh

    axes = {}
    for part in spec.split(","):
        name, _, size = part.partition("=")
        name = name.strip()
        if name in axes:
            raise SystemExit(f"duplicate mesh axis {name!r}")
        try:
            axes[name] = int(size)
        except ValueError:
            raise SystemExit(f"bad --mesh entry {part!r}; want axis=<int>")
        if name not in ("data", "entity", "feature"):
            raise SystemExit(
                f"unknown mesh axis {name!r}; choose from data/entity/feature")
        if axes[name] < 1:
            raise SystemExit(f"mesh axis {name!r} must be >= 1, got {axes[name]}")
    try:
        return make_mesh(axes)
    except ValueError as e:  # e.g. more devices requested than available
        raise SystemExit(f"--mesh {spec!r}: {e}")


# canonical home is the io layer, next to InputColumnsNames; re-exported
# here for backward compatibility
from photon_ml_tpu.io.data_reader import parse_input_columns  # noqa: E402,F401


def _process_index() -> int:
    import jax

    return jax.process_index()


def _resolve_model_dir(path: str) -> str:
    """Accept a run dir (containing best/) or a model dir directly."""
    path = os.path.normpath(path)
    if os.path.exists(os.path.join(path, "model-metadata.json")):
        return path
    nested = os.path.join(path, "best")
    if os.path.exists(os.path.join(nested, "model-metadata.json")):
        return nested
    raise FileNotFoundError(f"no model-metadata.json under {path!r}")


def _run_supervised(raw_argv: Sequence[str], args) -> dict:
    """The ``--supervise N`` branch: relaunch this command as an N-process
    supervised fleet (workers get ``--checkpoint --resume`` so every
    restart resumes from the latest agreed checkpoint, and ``--multihost``
    at N > 1) and return the chief's result dict + the restart count.
    Runs BEFORE any jax/backend touch — the supervisor process itself
    never trains."""
    from photon_ml_tpu.cli.config import install_supervisor_telemetry
    from photon_ml_tpu.resilience.supervisor import supervise_from_args

    if args.tuning != "NONE" or len(parse_grid(args.grid)) != 1:
        raise SystemExit(
            "--supervise needs a single-config grid and no --tuning: "
            "restart-from-checkpoint resumes ONE training (the same "
            "constraint as --checkpoint/--resume)")
    worker_flags = ["--checkpoint", "--resume"]
    if args.supervise > 1:
        worker_flags.append("--multihost")
    telemetry = install_supervisor_telemetry(args)
    try:
        return supervise_from_args("train_game", raw_argv, args,
                                   worker_flags=worker_flags)
    finally:
        telemetry.close()


def run(argv: Optional[Sequence[str]] = None) -> dict:
    import sys

    from photon_ml_tpu.events import GLOBAL_BUS

    raw_argv = list(sys.argv[1:] if argv is None else argv)
    args = build_parser().parse_args(raw_argv)
    if args.supervise:
        return _run_supervised(raw_argv, args)
    task = TaskType(args.task)
    # install the retry policy BEFORE anything that might retry (multihost
    # initialization is the first candidate)
    guard = install_resilience(resilience_from_args(args))
    if args.multihost:
        # must precede parse_mesh: forming the job is only possible before
        # the first backend-touching call
        from photon_ml_tpu.parallel import multihost

        multihost.initialize(auto=True)
    from photon_ml_tpu.parallel.multihost import is_chief

    chief = is_chief()
    import jax

    # >1 process: route training through the entity-partitioned
    # multi-process path (game/multiprocess.py) — per-process file reads,
    # global id agreement, dp fixed effect on the global mesh,
    # process-local random-effect solves, allgathered model
    multiproc = args.multihost and jax.process_count() > 1
    if multiproc and args.mesh:
        raise SystemExit(
            "multi-process --multihost training does not take --mesh: the "
            "global data mesh is built automatically, the entity axis is "
            "subsumed by the entity->process partition, and TP-across-"
            "processes has no photon-scale workload — see PARALLELISM.md "
            "\"Why --mesh is refused at >1 process\" for the full rationale")
    # fail fast on a bad mesh spec / device-count mismatch, BEFORE the
    # (potentially long) Avro reads
    mesh = parse_mesh(args.mesh)
    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)
    # non-chief processes log under a per-process subdir: on the shared
    # filesystem --multihost mandates, N processes appending to one
    # photon.log/metrics.jsonl would interleave and duplicate every line
    log_dir = args.output_dir if chief else os.path.join(
        args.output_dir, "workers", f"proc-{_process_index()}")
    run_logger = RunLogger(log_dir)
    # telemetry before the first event post, so the bridge sees the whole
    # run; non-chief processes trace under their own workers/ subdir
    telemetry = install_telemetry(telemetry_from_args(
        args, subdir=None if chief
        else os.path.join("workers", f"proc-{_process_index()}")))
    # the async I/O pipeline's writer service: feature indexes and model
    # part-files are written on background threads and joined before exit,
    # so "Save models" shrinks to the join wall (chief-only — only the
    # chief writes outputs)
    saver = None
    if chief:
        from photon_ml_tpu.io.pipeline import BackgroundSaver

        saver = BackgroundSaver()
    from photon_ml_tpu.telemetry import emit_build_info, tracing

    # photon_build_info{version, process, jax_version}: every process
    # stamps itself so a fleet scrape exposes mixed-version fleets
    emit_build_info()
    import contextlib as _contextlib

    _root_span = _contextlib.ExitStack()
    _root_span.enter_context(tracing.span("train_game"))
    GLOBAL_BUS.post("training_started", driver="train_game",
                    task=task.value, output_dir=args.output_dir)
    try:
        shard_configs = tuple(parse_feature_shard_config(s)
                              for s in args.feature_shards.split(","))
        coordinate_configs = dict(parse_coordinate_config(s)
                                  for s in args.coordinates)
        if args.design_dtype != "float32":
            import dataclasses as _dc

            if any(isinstance(c, FactoredRandomEffectCoordinateConfig)
                   for c in coordinate_configs.values()):
                # factored coordinates solve in the RANDOM-projected space
                # and keep f32 designs; silently training them f32 under a
                # bf16 request would fake the promised speedup
                raise SystemExit(
                    "--design-dtype bfloat16 does not apply to factored "
                    "random-effect coordinates (their projected designs "
                    "are float32); drop the flag or the factored "
                    "coordinate")
            coordinate_configs = {
                cid: (_dc.replace(c, design_dtype=args.design_dtype)
                      if isinstance(c, (FixedEffectCoordinateConfig,
                                        RandomEffectCoordinateConfig))
                      else c)
                for cid, c in coordinate_configs.items()}
        update_sequence = [c for c in args.update_sequence.split(",") if c]
        locked = [c for c in args.locked_coordinates.split(",") if c]
        if locked and not args.model_input_dir:
            raise SystemExit("--locked-coordinates needs --model-input-dir")
        re_types = {
            c.dataset.random_effect_type
            for c in coordinate_configs.values()
            if isinstance(c, (RandomEffectCoordinateConfig,
                              FactoredRandomEffectCoordinateConfig))}
        if args.model_input_dir:
            # locked coordinates have no config entry, but their entity-id
            # columns must still be read so the loaded model's entity keys
            # resolve (model-metadata.json records each coordinate's type)
            import json as _json

            with open(os.path.join(_resolve_model_dir(args.model_input_dir),
                                   "model-metadata.json")) as f:
                for info in _json.load(f)["coordinates"].values():
                    if info["type"] == "random-effect":
                        re_types.add(info["randomEffectType"])
        re_types = sorted(re_types)
        evaluators = parse_evaluators(
            [e for e in args.evaluators.split(",") if e])
        id_columns = tuple(dict.fromkeys(
            re_types + [e.id_tag for e in evaluators if e.id_tag]))

        preset_maps = None
        if args.model_input_dir:
            from photon_ml_tpu.io.index import IndexMap

            model_dir = _resolve_model_dir(args.model_input_dir)
            index_dir = os.path.join(os.path.dirname(model_dir)
                                     if os.path.basename(model_dir) == "best"
                                     else model_dir, "feature-indexes")
            if not os.path.isdir(index_dir):
                index_dir = os.path.join(model_dir, "feature-indexes")
            preset_maps = {
                cfg.shard_id: IndexMap.load(
                    os.path.join(index_dir, f"{cfg.shard_id}.json"))
                for cfg in shard_configs}

        reader = AvroDataReader(shard_configs=shard_configs,
                                index_maps=preset_maps,
                                input_columns=parse_input_columns(
                                    args.input_columns))
        with timed("Read training data", run_logger):
            if multiproc:
                # each process reads its share of the file list (the
                # reference's executor-local reads), then ids are unioned
                # into one global feature index / entity vocabulary
                from photon_ml_tpu.game.multiprocess import (
                    process_file_share,
                    reconcile_global_ids,
                )

                data, index_maps, vocabs = reader.read(
                    process_file_share(reader, args.training_data),
                    id_columns=id_columns)
                data, index_maps, vocabs = reconcile_global_ids(
                    data, index_maps, vocabs, id_columns)
            else:
                data, index_maps, vocabs = reader.read(
                    args.training_data, id_columns=id_columns)
        if saver is not None:
            # the index maps are final from here on: their JSON files write
            # on the background pool, fully hidden under the stages below
            os.makedirs(args.output_dir, exist_ok=True)
            for shard_id, imap in index_maps.items():
                saver.submit_file_write(
                    imap.save,
                    os.path.join(args.output_dir, "feature-indexes",
                                 f"{shard_id}.json"),
                    label="io.save.index", shard=shard_id)

        initial_models = None
        if args.model_input_dir:
            from photon_ml_tpu.io import load_game_model

            with timed("Load initial model", run_logger):
                initial_models = dict(load_game_model(
                    model_dir, index_maps, vocabs).coordinates)
            missing = set(locked) - set(initial_models)
            if missing:
                raise SystemExit(
                    f"locked coordinates {sorted(missing)} not present in "
                    f"the input model")

        # --- continuous-training lineage + data manifest ----------------
        # every published model records where it came from (parentModel /
        # trainedAt) and a per-entity fingerprint manifest of its training
        # data, so refresh_game can warm-start from it and re-solve only
        # the entities whose data changed. Chief-only and single-process:
        # a multi-process share sees a partial row set, so its manifest
        # would mis-flag every remotely-read entity as changed.
        lineage = None
        if chief:
            import datetime as _dt

            manifest_digest = None
            if not multiproc:
                from photon_ml_tpu.continuous import delta as _delta

                re_coords = {
                    cid: (c.dataset.random_effect_type,
                          c.dataset.feature_shard_id)
                    for cid, c in coordinate_configs.items()
                    if isinstance(c, RandomEffectCoordinateConfig)}
                _manifest = _delta.build_manifest(data, re_coords, vocabs)
                manifest_digest = _delta.manifest_digest(_manifest)
                saver.submit_file_write(
                    lambda path, m=_manifest: _delta.save_manifest(path, m),
                    os.path.join(args.output_dir, _delta.MANIFEST_NAME),
                    label="io.save.manifest")
            parent_lineage = None
            if args.model_input_dir:
                from photon_ml_tpu.io.model_io import model_lineage_id

                parent_lineage = model_lineage_id(model_dir)
            lineage = {
                "parentModel": parent_lineage,
                "trainedAt": _dt.datetime.now(
                    _dt.timezone.utc).isoformat(),
                "dataManifest": manifest_digest,
            }
        with timed("Validate data", run_logger):
            validate_game_data(data, task,
                               DataValidationType(args.data_validation))

        validation = None
        if args.validation_data:
            reader_v = AvroDataReader(shard_configs=shard_configs,
                                      index_maps=index_maps,
                                      input_columns=reader.input_columns)
            if multiproc:
                # collective path: every process must hold the data before
                # the symmetric training starts — read it here
                with timed("Read validation data", run_logger):
                    vdata, _, _ = reader_v.read(
                        args.validation_data, id_columns=id_columns,
                        entity_vocabs=vocabs)
                validation = (vdata, evaluators)
            else:
                # async ingest: the read runs in the background while the
                # training data uploads and the first sweep trains; the
                # callable joins it at first use (sweep 1's evaluation),
                # and the "Read validation data" stage records the JOIN
                # wall — the visible (unhidden) part of the read
                from photon_ml_tpu.io.pipeline import read_in_background

                _v_future = read_in_background(
                    reader_v.read, args.validation_data,
                    id_columns=id_columns, entity_vocabs=vocabs,
                    label="io.read.validation")
                _v_cell: list = []

                def validation():
                    if not _v_cell:
                        with timed("Read validation data", run_logger):
                            vdata, _, _ = _v_future.result()
                        _v_cell.append((vdata, evaluators))
                    return _v_cell[0]

        est = GameEstimator(task=task, coordinate_configs=coordinate_configs,
                            update_sequence=update_sequence,
                            n_cd_iterations=args.cd_iterations, mesh=mesh)

        # async model publication: each configuration's model save is
        # submitted the moment that configuration finishes, overlapping
        # the remaining grid points and best-selection. With
        # --output-all-models every config lands under all/config-i (and
        # best/ is published later as a hardlink alias of the winner —
        # the model is serialized ONCE); a single-config grid's only
        # result IS best, so it saves straight to best/ while the driver
        # finishes bookkeeping.
        _single_config = [False]
        _best_pre_submitted = [False]

        def _note_result(i, r):
            if saver is None:
                return
            if args.output_all_models:
                saver.submit_game_save(
                    os.path.join(args.output_dir, "all", f"config-{i}"),
                    r.model, index_maps, vocabs,
                    sparsity_threshold=args.model_sparsity_threshold,
                    lineage=lineage)
            elif _single_config[0] and i == 0:
                saver.submit_game_save(
                    os.path.join(args.output_dir, "best"),
                    r.model, index_maps, vocabs,
                    sparsity_threshold=args.model_sparsity_threshold,
                    lineage=lineage)
                _best_pre_submitted[0] = True

        def _mp_fit(config, mp_ckpt=None):
            """One collective-symmetric multi-process fit, evaluated and
            wrapped as a GameResult — shared by the grid and tuning paths
            so their result assembly can never drift apart."""
            from photon_ml_tpu.evaluation import evaluate_all
            from photon_ml_tpu.game.estimator import GameResult
            from photon_ml_tpu.game.multiprocess import (
                train_game_multiprocess,
            )

            mp = train_game_multiprocess(
                data, task, coordinate_configs, update_sequence,
                config.regularization_weights,
                n_cd_iterations=args.cd_iterations,
                checkpoint_dir=mp_ckpt, resume=args.resume,
                initial_models=initial_models, locked=locked,
                validation=validation, guard=guard)
            evaluation = None
            if validation is not None:
                vdata, evs = validation
                # per-sweep history is tracked inside the run; the final
                # EvaluationResults object is re-derived for model selection
                evaluation = evaluate_all(
                    evs, mp.model.score(vdata), vdata.labels,
                    weights=vdata.weights, id_tags=vdata.id_columns)
            return GameResult(
                model=mp.model, configuration=config, evaluation=evaluation,
                validation_history=list(mp.validation_history))

        checkpoint = None
        if (args.checkpoint or args.resume) and not multiproc:
            # multiproc uses its own per-process sweep-boundary state files
            # (created in the training branch below), not this manager
            from photon_ml_tpu.io.checkpoint import CheckpointManager

            # non-chief: read-only, so --resume stays in lockstep with the
            # chief's checkpoints without racing its writes
            checkpoint = CheckpointManager(
                os.path.join(args.output_dir, "checkpoints"),
                read_only=not chief)
            if jax.process_count() > 1:
                # agree on the resume point ONCE, before training: each
                # process polling the shared filesystem independently would
                # race the chief's own saves (collective: all processes
                # must reach this broadcast)
                import numpy as _np
                from jax.experimental import multihost_utils

                step = checkpoint.latest_step() if chief else None
                agreed = int(multihost_utils.broadcast_one_to_all(
                    _np.int64(-1 if step is None else step)))
                checkpoint.pin_step(None if agreed < 0 else agreed)
        profile_dir = (os.path.join(args.output_dir, "profile")
                       if args.profile else None)

        if args.tuning == "NONE":
            grid = parse_grid(args.grid)
            unknown = {cid for g in grid for cid in g} - set(update_sequence)
            if unknown:
                raise SystemExit(
                    f"--grid names unknown coordinates {sorted(unknown)}; "
                    f"update sequence is {update_sequence}")
            configurations = [GameOptimizationConfiguration(g) for g in grid]
            if ((checkpoint is not None
                 or (multiproc and (args.checkpoint or args.resume)))
                    and len(configurations) != 1):
                raise SystemExit("--checkpoint/--resume need a single-config "
                                 "grid (got %d configs)" % len(configurations))
            from photon_ml_tpu.logging_util import profiled

            if multiproc:
                # multi-process checkpoints are per-process sweep-boundary
                # state files (game/multiprocess.py), not the single-process
                # CheckpointManager format
                mp_ckpt = None
                if args.checkpoint or args.resume:
                    mp_ckpt = os.path.join(args.output_dir,
                                           "checkpoints-mp")
                results = []
                with timed("Train (grid, multi-process)", run_logger), \
                        profiled(profile_dir):
                    # grid points run sequentially — each is one
                    # collective-symmetric training all processes join
                    for config in configurations:
                        results.append(_mp_fit(config, mp_ckpt))
                        _note_result(len(results) - 1, results[-1])
            else:
                _single_config[0] = len(configurations) == 1
                with timed("Train (grid)", run_logger), profiled(profile_dir):
                    results = est.fit(
                        data, configurations, validation=validation,
                        initial_models=initial_models, locked=locked,
                        checkpoint=checkpoint, resume=args.resume,
                        guard=guard, on_result=_note_result)
                    # drain the async solve queue inside the timed block:
                    # without this the final sweep's device programs finish
                    # during "Save models", which then reports compute as
                    # IO (stages get reference Timed semantics; the wall is
                    # unchanged — save's materialize would wait anyway)
                    results[-1].model.device_wait()
        else:
            if validation is None:
                raise SystemExit("--tuning needs --validation-data")
            if (checkpoint is not None
                    or (multiproc and (args.checkpoint or args.resume))):
                raise SystemExit("--checkpoint/--resume don't combine with "
                                 "--tuning")
            from photon_ml_tpu.hyperparameter.search import (
                GaussianProcessSearch,
                ParamRange,
                RandomSearch,
            )

            low, high = (float(x) for x in args.tuning_range.split(":"))
            # locked coordinates are frozen — tuning their lambda would
            # explore a dead axis
            space = {cid: ParamRange(low, high) for cid in update_sequence
                     if cid not in locked}
            results = []
            if multiproc:
                # every process runs the IDENTICAL search loop: the search
                # is deterministic (seeded) and each observation — the
                # validation metric of a collective-symmetric training —
                # is computed identically on every process, so the
                # candidate sequence never diverges
                def evaluate(config: dict) -> float:
                    r = _mp_fit(GameOptimizationConfiguration(config))
                    results.append(r)
                    _note_result(len(results) - 1, r)
                    return r.evaluation.primary[1]

                def release_datasets():
                    pass  # per-fit datasets are process-local temporaries
            else:
                datasets = est.prepare(data, locked=locked)  # build once

                def evaluate(config: dict) -> float:
                    r = est.fit(data, [GameOptimizationConfiguration(config)],
                                validation=validation, datasets=datasets,
                                initial_models=initial_models, locked=locked,
                                guard=guard)[0]
                    results.append(r)
                    _note_result(len(results) - 1, r)
                    return r.evaluation.primary[1]

                def release_datasets():
                    # tuning holds the datasets across fits; drop the cached
                    # device placements (HBM) once the search is done —
                    # including GameData's (dense shard image, labels/weights
                    # uploaded by device_dense_shard)
                    for ds in datasets.values():
                        if hasattr(ds, "clear_device_cache"):
                            ds.clear_device_cache()
                    data.clear_device_cache()

            maximize = evaluators[0].maximize
            search_cls = (GaussianProcessSearch if args.tuning == "BAYESIAN"
                          else RandomSearch)
            from photon_ml_tpu.logging_util import profiled

            with timed(f"Train ({args.tuning} tuning)", run_logger), \
                    profiled(profile_dir):
                if args.tuning == "BAYESIAN":
                    search_cls(space, maximize=maximize).find(
                        evaluate, args.tuning_iterations)
                else:
                    search_cls(space).find(evaluate, args.tuning_iterations)
            release_datasets()

        best = GameEstimator.select_best(results)
        for i, r in enumerate(results):
            GLOBAL_BUS.post(
                "configuration_evaluated", index=i,
                config=dict(r.configuration.regularization_weights),
                evaluation=r.evaluation.as_dict() if r.evaluation else None)
        if best.evaluation is not None:
            run_logger.metric(stage="best", **best.evaluation.as_dict(),
                              config=dict(best.configuration.regularization_weights))

        if chief:
            best_dir = os.path.join(args.output_dir, "best")
            # train-time quality baseline (quality/baseline.py): profile
            # the winner's score distribution on the validation set (the
            # training set when the run has none — still a reference
            # distribution for online drift) and publish it at the run
            # root next to best/ and data-manifest.json. The whole
            # computation rides the background writer pool: score-side
            # work never touches the training wall, and the serving
            # registry rediscovers the artifact at load time.
            from photon_ml_tpu.quality import (
                BASELINE_NAME,
                baseline_from_game,
                save_baseline,
            )

            if validation is not None:
                _b_source = (validation() if callable(validation)
                             else validation)[0]
            else:
                _b_source = data

            def _write_baseline(path, model=best.model, bdata=_b_source,
                                blineage=lineage):
                save_baseline(path, baseline_from_game(
                    model, bdata, task=task, lineage=blineage))

            saver.submit_file_write(
                _write_baseline,
                os.path.join(args.output_dir, BASELINE_NAME),
                label="quality.baseline")
            if not args.output_all_models and not _best_pre_submitted[0]:
                # multi-config grid / tuning without --output-all-models:
                # the winner is only known now — submit its (sole) save
                saver.submit_game_save(
                    best_dir, best.model, index_maps, vocabs,
                    sparsity_threshold=args.model_sparsity_threshold,
                    lineage=lineage)
            # the stage is now the JOIN wall: whatever the background
            # writers didn't finish under train/selection (plus, under
            # --output-all-models, the hardlink alias publish)
            with timed("Save models", run_logger):
                saver.join()
                if args.output_all_models:
                    from photon_ml_tpu.io.pipeline import publish_model_alias

                    best_i = next(i for i, r in enumerate(results)
                                  if r is best)
                    publish_model_alias(
                        os.path.join(args.output_dir, "all",
                                     f"config-{best_i}"), best_dir)
            GLOBAL_BUS.post("model_saved", path=best_dir)
        result = {
            "best_config": dict(best.configuration.regularization_weights),
            "best_evaluation": (best.evaluation.as_dict()
                                if best.evaluation else None),
            "n_configurations": len(results),
            "output_dir": args.output_dir,
        }
        if chief:
            # supervised runs: hand the result dict back to the supervisor
            # (no-op unsupervised)
            from photon_ml_tpu.resilience.supervisor import write_result_file

            write_result_file(result)
        return result
    finally:
        if saver is not None:
            # happy path already join()ed (errors propagated there); this
            # waits out any writer a failing run left in flight so no
            # thread outlives the driver into a dir being torn down
            saver.close()
        _root_span.close()
        GLOBAL_BUS.post("training_finished", driver="train_game")
        telemetry.close()
        run_logger.close()


if __name__ == "__main__":
    run()
