"""Legacy single-model GLM training driver.

Re-design of the reference's original pipeline (``Driver.scala`` +
``PhotonMLCmdLineParser.scala`` + ``ModelTraining.scala``; BASELINE configs
1–3): read Avro → validate rows → optional feature summarization +
normalization → train one model per regularization weight (descending, warm
starts) → validate each → select best → write best + all models and the
summary log. The staged state machine (INIT → ... → VALIDATED) collapses to
straight-line host code; each stage is a ``timed`` section in the run log.
"""

from __future__ import annotations

import argparse
import os
from typing import Optional, Sequence

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.cli.config import (
    add_resilience_flags,
    add_supervision_flags,
    add_telemetry_flags,
    install_resilience,
    install_telemetry,
    resilience_from_args,
    telemetry_from_args,
)
from photon_ml_tpu.data_validation import validate_game_data
from photon_ml_tpu.evaluation import parse_evaluators
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.glm.training import train_glm_sweep, validate_and_select
from photon_ml_tpu.io import (
    AvroDataReader,
    FeatureShardConfig,
    save_glm_model,
    save_glm_model_text,
)
from photon_ml_tpu.io.data_reader import parse_input_columns
from photon_ml_tpu.io.avro import write_avro_file
from photon_ml_tpu.io.schemas import FEATURE_SUMMARIZATION_RESULT_AVRO
from photon_ml_tpu.logging_util import RunLogger, timed
from photon_ml_tpu.ops.design import ChunkedSparseDesign, DenseDesign
from photon_ml_tpu.ops.normalization import NoNormalization, build_normalization
from photon_ml_tpu.ops.objective import GLMData
from photon_ml_tpu.ops.regularization import RegularizationContext
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.stat import FeatureDataStatistics
from photon_ml_tpu.types import (
    DataValidationType,
    INTERCEPT_KEY,
    NormalizationType,
    OptimizerType,
    RegularizationType,
    TaskType,
)

DENSE_MAX_DIM = 4096


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon_ml_tpu train_glm",
        description="Train a single GLM over a regularization sweep (TPU)")
    p.add_argument("--training-data", required=True)
    p.add_argument("--validation-data")
    p.add_argument("--output-dir", required=True)
    p.add_argument("--task", default="LOGISTIC_REGRESSION",
                   choices=[t.value for t in TaskType])
    p.add_argument("--optimizer", default="LBFGS",
                   choices=[o.value for o in OptimizerType])
    p.add_argument("--regularization-type", default="L2",
                   choices=[r.value for r in RegularizationType])
    p.add_argument("--elastic-net-alpha", type=float, default=0.5)
    p.add_argument("--regularization-weights", default="1.0",
                   help="semicolon-separated, e.g. '10;1;0.1'")
    p.add_argument("--normalization", default="NONE",
                   choices=[n.value for n in NormalizationType])
    p.add_argument("--evaluators", default="",
                   help="comma-separated evaluator specs (first selects the model)")
    p.add_argument("--max-iterations", type=int, default=80)
    p.add_argument("--tolerance", type=float, default=1e-6)
    p.add_argument("--no-intercept", action="store_true")
    p.add_argument("--variance-computation", default="NONE",
                   choices=["NONE", "SIMPLE", "FULL"])
    p.add_argument("--data-validation", default="VALIDATE_FULL",
                   choices=[v.value for v in DataValidationType])
    p.add_argument("--summarization-output", action="store_true",
                   help="write per-feature summary stats avro")
    p.add_argument("--training-diagnostics", action="store_true",
                   help="write diagnostics/report.html (bootstrap CIs, "
                        "Hosmer-Lemeshow, feature importance, fitting curve)")
    p.add_argument("--diagnostic-bootstrap-replicates", type=_positive_int,
                   default=16)
    p.add_argument("--profile", action="store_true",
                   help="write a jax.profiler trace of the training stage "
                        "to <output-dir>/profile (view with TensorBoard)")
    p.add_argument("--debug-nans", action="store_true",
                   help="enable jax_debug_nans (fail fast on NaN). Strict "
                        "debugging mode: also flags the line search's "
                        "legitimate NaN-probing on overflowing trial steps, "
                        "so use to LOCATE a NaN, not for production runs")
    p.add_argument("--input-columns", default="",
                   help="remap record fields, e.g. 'response=label' "
                        "(reference InputColumnsNames)")
    p.add_argument("--warm-start", metavar="DIR",
                   help="continuous-training warm start: seed the sweep's "
                        "FIRST solve from a previous run's best model "
                        "(DIR is a train_glm output dir containing "
                        "best/model.avro, or a model.avro's directory). "
                        "Coefficients join by feature NAME, so the prior "
                        "model aligns even if this run's feature index "
                        "orders differently; the warm-started solve "
                        "converges in strictly fewer iterations on "
                        "unchanged data. Sequential sweep mode only")
    p.add_argument("--design-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="storage dtype of a DENSE design matrix. bfloat16 "
                        "halves HBM traffic (the solve is bandwidth-bound; "
                        "~1.4x faster with the fused kernel) but rounds the "
                        "features to ~3 decimal digits, perturbing the "
                        "optimum — keep float32 where exact reference "
                        "parity matters")
    p.add_argument("--sweep-mode", default="sequential",
                   choices=["sequential", "batched"],
                   help="sequential (default): warm-started descending "
                        "lambda sweep, the reference's ModelTraining "
                        "semantics — fastest for DENSE designs (fused "
                        "kernel + warm starts). batched: one vmapped solve "
                        "over all lambdas — the lanes share a wide "
                        "CHUNKED-SPARSE design's index traffic but run in "
                        "lockstep without warm starts; see "
                        "glm/training.py::train_glm_sweep_batched")
    p.add_argument("--multihost", action="store_true",
                   help="form a multi-controller job before touching any "
                        "device (jax.distributed.initialize from PHOTON_* "
                        "env vars or cluster auto-detection). With >1 "
                        "process: each process reads its share of the input "
                        "FILE LIST (at least one file per process), the "
                        "feature index and summary statistics are unioned "
                        "globally, every lambda solves as ONE psum'd sweep "
                        "over the global data mesh, and only process 0 "
                        "writes outputs. Not combinable with "
                        "--training-diagnostics or --design-dtype bfloat16 "
                        "yet")
    add_resilience_flags(p)
    add_supervision_flags(p)
    add_telemetry_flags(p)
    return p


def _positive_int(s: str) -> int:
    v = int(s)
    if v < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {v}")
    return v


def _to_glm_data(data, shard_id: str, dtype=jnp.float32) -> GLMData:
    shard = data.shards[shard_id]
    if shard.dim <= DENSE_MAX_DIM:
        design = DenseDesign(x=jnp.asarray(shard.to_dense(), dtype))
    else:
        # sparse chunked layouts keep f32 values (nnz dominates memory far
        # less than a dense design; bf16 applies to the dense path only)
        design = ChunkedSparseDesign.from_coo(
            shard.rows(), shard.cols, shard.vals,
            n_rows=shard.n_samples, n_cols=shard.dim)
    return GLMData(design=design, labels=jnp.asarray(data.labels),
                   offsets=jnp.asarray(data.offsets),
                   weights=jnp.asarray(data.weights))


def _run_diagnostics(args, task, best, glm_train, glm_val, shard, stats, imap,
                     config, normalization, reg_mask, run_logger) -> str:
    """The reference driver's DIAGNOSED stage (``--training-diagnostics``):
    bootstrap CIs, Hosmer-Lemeshow (logistic only), feature importance, and
    the fitting curve, written as ``diagnostics/report.html``."""
    from photon_ml_tpu.diagnostics import (
        bootstrap_coefficients,
        expected_magnitude_importance,
        fitting_curve,
        hosmer_lemeshow,
        variance_importance,
        write_report,
    )
    from photon_ml_tpu.glm.training import build_problem

    problem = build_problem(task, config, normalization, reg_mask)
    lam = best.regularization_weight
    w_t = best.result.w  # transformed-space solution from the sweep

    # replicate solutions live in transformed (normalized) space; report CIs
    # in original feature space to match the published model coefficients
    transform = (None if normalization.is_identity
                 else normalization.model_to_original)
    boot = bootstrap_coefficients(
        problem, glm_train, w_t, lam,
        n_replicates=args.diagnostic_bootstrap_replicates,
        transform=transform)

    hl = None
    if task == TaskType.LOGISTIC_REGRESSION:
        ev_data = glm_val if glm_val is not None else glm_train
        probs = np.asarray(best.model.predict_mean(ev_data.design,
                                                   ev_data.offsets))
        hl = hosmer_lemeshow(probs, np.asarray(ev_data.labels),
                             np.asarray(ev_data.weights))
        run_logger.metric(stage="diagnostics", hl_chi_square=hl.chi_square,
                          hl_p_value=hl.p_value)

    if stats is None:
        stats = FeatureDataStatistics.from_shard(shard)
    names = imap.names()
    coefs = np.asarray(best.model.coefficients.means)
    importance = [variance_importance(coefs, stats, names=names),
                  expected_magnitude_importance(coefs, stats, names=names)]

    fitting = None
    if glm_val is not None:
        # warm-start every portion from the trained solution (portion optima
        # are near it; solves still run to their own convergence)
        fitting = fitting_curve(problem, glm_train, glm_val, w_t, lam)

    return write_report(
        os.path.join(args.output_dir, "diagnostics", "report.html"),
        model_summary={
            "task": task.value,
            "best lambda": lam,
            "optimizer": config.optimizer.value,
            "iterations": int(best.result.iterations),
            "converged": bool(best.result.converged),
        },
        bootstrap=boot, hosmer_lemeshow=hl, importance=importance,
        fitting=fitting, feature_names=names)


def run(argv: Optional[Sequence[str]] = None) -> dict:
    import sys

    raw_argv = list(sys.argv[1:] if argv is None else argv)
    args = build_parser().parse_args(raw_argv)
    if args.supervise:
        # supervised fleet: relaunch this command N times under the
        # FleetSupervisor (before any jax/backend touch). The GLM sweep
        # has no checkpoint — a restarted fleet re-solves from scratch,
        # which the deterministic sweep makes exactly repeatable.
        from photon_ml_tpu.cli.config import install_supervisor_telemetry
        from photon_ml_tpu.resilience.supervisor import supervise_from_args

        telemetry = install_supervisor_telemetry(args)
        try:
            return supervise_from_args(
                "train_glm", raw_argv, args,
                worker_flags=(("--multihost",) if args.supervise > 1
                              else ()))
        finally:
            telemetry.close()
    task = TaskType(args.task)
    if args.warm_start and args.sweep_mode == "batched":
        # fail fast, before any read: batched lanes solve independently
        # from zero by design — there is nothing to warm-start
        raise SystemExit(
            "--warm-start needs --sweep-mode sequential (batched lanes "
            "solve independently from zero by design)")
    # install the retry policy BEFORE anything that might retry (multihost
    # initialization is the first candidate)
    install_resilience(resilience_from_args(args))
    if args.multihost:
        from photon_ml_tpu.parallel import multihost

        multihost.initialize(auto=True)
    import jax

    multiproc = args.multihost and jax.process_count() > 1
    chief = jax.process_index() == 0
    if multiproc:
        bad = [msg for flag, msg in (
            (args.training_diagnostics, "--training-diagnostics"),
            (args.sweep_mode == "batched", "--sweep-mode batched (vmap "
             "over the lambda axis does not compose with the multi-process "
             "mesh yet)"),
        ) if flag]
        if bad:
            raise SystemExit("multi-process --multihost training does not "
                             "support: " + ", ".join(bad))
    if args.debug_nans:
        jax.config.update("jax_debug_nans", True)
    run_logger = RunLogger(
        args.output_dir if chief else os.path.join(
            args.output_dir, "workers", f"proc-{jax.process_index()}"))
    telemetry = install_telemetry(telemetry_from_args(
        args, subdir=None if chief
        else os.path.join("workers", f"proc-{jax.process_index()}")))
    # async I/O pipeline: model/index writes run on background threads and
    # are joined before exit — "Save models" is the join wall (chief-only)
    saver = None
    if chief:
        from photon_ml_tpu.io.pipeline import BackgroundSaver

        saver = BackgroundSaver()
    from photon_ml_tpu.telemetry import emit_build_info, tracing

    emit_build_info()

    import contextlib as _contextlib

    _root_span = _contextlib.ExitStack()
    _root_span.enter_context(tracing.span("train_glm"))
    from photon_ml_tpu.events import GLOBAL_BUS

    GLOBAL_BUS.post("training_started", driver="train_glm",
                    task=task.value, output_dir=args.output_dir)
    try:
        evaluators = parse_evaluators(
            [e for e in args.evaluators.split(",") if e])
        id_columns = tuple(dict.fromkeys(
            e.id_tag for e in evaluators if e.id_tag))
        reader = AvroDataReader(
            shard_configs=(
                FeatureShardConfig("global", feature_bags=None,
                                   has_intercept=not args.no_intercept),),
            input_columns=parse_input_columns(args.input_columns))
        with timed("Read training data", run_logger):
            if multiproc:
                from photon_ml_tpu.game.multiprocess import (
                    process_file_share,
                    reconcile_global_ids,
                )

                data, index_maps, vocabs = reader.read(
                    process_file_share(reader, args.training_data),
                    id_columns=id_columns)
                # vocabs reconciled for grouped-evaluator id tags only (the
                # GLM driver has no entity models)
                data, index_maps, _ = reconcile_global_ids(
                    data, index_maps, vocabs, id_columns)
            else:
                data, index_maps, _ = reader.read(args.training_data,
                                                  id_columns=id_columns)
        imap = index_maps["global"]

        with timed("Validate data", run_logger):
            validate_game_data(data, task,
                               DataValidationType(args.data_validation))

        shard = data.shards["global"]
        norm_type = NormalizationType(args.normalization)
        normalization = NoNormalization
        stats = None
        if norm_type != NormalizationType.NONE or args.summarization_output:
            with timed("Summarize features", run_logger):
                # allreduce: global statistics when rows span processes
                # (identity single-process), so the normalization context —
                # part of the OBJECTIVE — is identical everywhere
                stats = FeatureDataStatistics.from_shard(shard).allreduce()
            if args.summarization_output and chief:
                write_avro_file(
                    os.path.join(args.output_dir, "summary.avro"),
                    stats.to_records(imap.names()),
                    FEATURE_SUMMARIZATION_RESULT_AVRO)
            if norm_type != NormalizationType.NONE:
                intercept_idx = imap.key_to_index.get(INTERCEPT_KEY)
                normalization = build_normalization(
                    norm_type, mean=stats.mean, variance=stats.variance,
                    max_magnitude=stats.max_magnitude,
                    intercept_index=intercept_idx)

        from photon_ml_tpu.types import VarianceComputationType

        lambdas = [float(x) for x in args.regularization_weights.split(";") if x]
        config = GLMOptimizationConfiguration(
            optimizer=OptimizerType(args.optimizer),
            regularization=RegularizationContext(
                RegularizationType(args.regularization_type),
                alpha=args.elastic_net_alpha),
            optimizer_config=OptimizerConfig(
                max_iterations=args.max_iterations, tolerance=args.tolerance),
            variance_type=VarianceComputationType(args.variance_computation),
        )

        reg_mask = None
        if imap.has_intercept:
            mask = np.ones(len(imap), np.float32)
            mask[imap.key_to_index[INTERCEPT_KEY]] = 0.0
            reg_mask = jnp.asarray(mask)

        design_dtype = (jnp.bfloat16 if args.design_dtype == "bfloat16"
                        else jnp.float32)
        fe_mesh = None
        if multiproc:
            # global data-axis mesh; every process feeds its own rows
            from photon_ml_tpu.game.data import host_design_for_shard
            from photon_ml_tpu.parallel.multihost import (
                global_glm_data_multihost,
                make_multihost_mesh,
            )

            fe_mesh = make_multihost_mesh()
            from photon_ml_tpu.game.data import cast_dense_design

            # the budget-reconciled feed preserves leaf dtypes, so the
            # bf16 cast here rides the wire at 2 bytes on every process
            # (same flag everywhere -> symmetric layout)
            host = GLMData(
                design=cast_dense_design(
                    host_design_for_shard(shard,
                                          dense_max_dim=DENSE_MAX_DIM),
                    design_dtype),
                labels=data.labels,
                offsets=data.offsets,
                weights=data.weights)
            glm_train = global_glm_data_multihost(host, fe_mesh)
        else:
            glm_train = _to_glm_data(data, "global", dtype=design_dtype)
        from photon_ml_tpu.logging_util import log_optimizer_trace, profiled

        # per-process profile dir: same-host processes tracing into one
        # directory overwrite each other's xplane files
        profile_dir = None
        if args.profile:
            profile_dir = os.path.join(
                args.output_dir if chief else os.path.join(
                    args.output_dir, "workers",
                    f"proc-{jax.process_index()}"),
                "profile")
        initial = None
        if args.warm_start:
            from photon_ml_tpu.io.model_io import load_glm_model

            warm_path = os.path.join(args.warm_start, "best", "model.avro")
            if not os.path.exists(warm_path):
                warm_path = os.path.join(args.warm_start, "model.avro")
            with timed("Load warm start", run_logger):
                prior = load_glm_model(warm_path, imap)
            # the sweep optimizes in TRANSFORMED space; a saved model's
            # coefficients are original-space (export back-transforms)
            w_orig = jnp.asarray(prior.coefficients.means)
            initial = (w_orig if normalization.is_identity
                       else normalization.original_to_model(w_orig))

        with timed("Train", run_logger), profiled(profile_dir):
            if args.sweep_mode == "batched":
                # multiproc + batched already rejected up front
                from photon_ml_tpu.glm.training import train_glm_sweep_batched

                trained = train_glm_sweep_batched(
                    task, glm_train, lambdas, config,
                    normalization=normalization, reg_mask=reg_mask)
            else:
                trained = train_glm_sweep(
                    task, glm_train, lambdas, config,
                    normalization=normalization, reg_mask=reg_mask,
                    initial=initial,
                    mesh=fe_mesh, dim=len(imap) if multiproc else None)
        for tm in trained:
            run_logger.metric(stage="train", regularization_weight=tm.regularization_weight,
                              value=float(tm.result.value),
                              iterations=int(tm.result.iterations),
                              converged=bool(tm.result.converged))
            # the reference's OptimizationStatesTracker iteration table
            log_optimizer_trace(
                tm.result, f"lambda={tm.regularization_weight:g}", run_logger)
        # every solve has been waited for above: the glm.solve spans' device
        # counts can go to trace.jsonl without a wait of their own
        tracing.flush()

        # divergence guard over the sweep (pure reads: finiteness of the
        # trained coefficients). The GLM sweep has no rollback target —
        # each lambda is an independent solve — so non-"fail" modes drop
        # the diverged lambdas from model selection and continue degraded.
        diverged = [tm for tm in trained
                    if not np.isfinite(
                        np.asarray(tm.model.coefficients.means)).all()]
        if diverged:
            from photon_ml_tpu.events import GLOBAL_BUS
            from photon_ml_tpu.resilience import DivergenceError

            bad = [tm.regularization_weight for tm in diverged]
            for w in bad:
                GLOBAL_BUS.post("divergence_detected", driver="train_glm",
                                regularization_weight=w)
            if args.on_divergence == "fail":
                raise DivergenceError(
                    f"GLM sweep diverged at lambda(s) {bad} (non-finite "
                    f"coefficients); re-run with --on-divergence=rollback "
                    f"to drop them from selection, or raise the "
                    f"regularization / lower the normalization scale")
            if len(diverged) == len(trained):
                raise DivergenceError(
                    f"every lambda in the sweep diverged ({bad}); nothing "
                    f"to select — fix the optimization configuration")
            for w in bad:
                GLOBAL_BUS.post("coordinate_frozen", driver="train_glm",
                                regularization_weight=w)
            trained = [tm for tm in trained if tm not in diverged]

        # async model publication: every lambda's model is final here —
        # submit the all/ writes NOW so they overlap the validation read,
        # scoring and selection below (evaluation is not part of the
        # written artifact, so writing before selection is byte-equivalent)
        def _save_glm(model, out_dir, model_id):
            save_glm_model(os.path.join(out_dir, "model.avro"),
                           model, imap, model_id=model_id)
            # the reference driver writes text AND Avro models
            save_glm_model_text(os.path.join(out_dir, "model.txt"),
                                model, imap)

        if chief:
            saver.submit_file_write(
                imap.save,
                os.path.join(args.output_dir, "feature-index.json"),
                label="io.save.index")
            for tm in trained:
                model_id = f"lambda-{tm.regularization_weight:g}"
                out_dir = os.path.join(args.output_dir, "all", model_id)
                saver.submit(
                    lambda tm=tm, out_dir=out_dir, model_id=model_id:
                        _save_glm(tm.model, out_dir, model_id),
                    label="io.save.model", path=out_dir)

        best_idx = 0
        glm_val = None
        # diagnostics need validation data too (fitting curve, out-of-sample
        # HL), so read it even when no evaluators are configured
        if args.validation_data and (evaluators or args.training_diagnostics):
            reader_v = AvroDataReader(shard_configs=reader.shard_configs,
                                      index_maps=index_maps,
                                      input_columns=reader.input_columns)
            with timed("Read validation data", run_logger):
                vdata, _, _ = reader_v.read(args.validation_data,
                                            id_columns=id_columns)
            glm_val = _to_glm_data(vdata, "global", dtype=design_dtype)
        if glm_val is not None and evaluators:
            with timed("Validate models", run_logger):
                best_idx, trained = validate_and_select(
                    trained, evaluators, glm_val,
                    id_tags=vdata.id_columns)
            for tm in trained:
                run_logger.metric(stage="validate",
                                  regularization_weight=tm.regularization_weight,
                                  **tm.evaluation.as_dict())

        best = trained[best_idx]
        if chief:
            # the winner is known only now; everything else has been
            # writing in the background since the sweep ended — the stage
            # is the join wall
            saver.submit(
                lambda: _save_glm(best.model,
                                  os.path.join(args.output_dir, "best"),
                                  "best"),
                label="io.save.model", path=os.path.join(args.output_dir,
                                                         "best"))
            with timed("Save models", run_logger):
                saver.join()
        report_path = None
        if args.training_diagnostics:
            # the DIAGNOSED stage of the reference driver's state machine
            with timed("Diagnostics", run_logger):
                report_path = _run_diagnostics(
                    args, task, best, glm_train, glm_val, shard, stats, imap,
                    config, normalization, reg_mask, run_logger)

        result = {
            "best_lambda": best.regularization_weight,
            "best_evaluation": (best.evaluation.as_dict()
                                if best.evaluation else None),
            "output_dir": args.output_dir,
            "diagnostics_report": report_path,
        }
        if chief:
            # supervised runs: hand the result dict back to the supervisor
            from photon_ml_tpu.resilience.supervisor import write_result_file

            write_result_file(result)
        return result
    finally:
        if saver is not None:
            # happy path already join()ed; this waits out writers a
            # failing run left in flight
            saver.close()
        _root_span.close()
        GLOBAL_BUS.post("training_finished", driver="train_glm")
        telemetry.close()
        run_logger.close()


if __name__ == "__main__":
    run()
