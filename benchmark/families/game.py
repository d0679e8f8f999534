"""GAME GLMix resident on one chip: ``GameEstimator.fit`` on data from the
generator.

Set-up draws the problem on the host, builds the program's ``GameData`` and
calls ``GameEstimator.prepare`` once (the bucket builds, the uploads, the
warm compiles: the program's own, all of it set-up). A unit is one
``GameEstimator.fit(data, [configuration], datasets=datasets)``: one
coordinate-descent sweep from zero coefficients over the configuration's
update sequence, exactly as ``bench.py::bench_cd_sweep`` and
``cli/train_game.py`` reach ``CoordinateDescent.run``, ending in a barrier on
the returned models (a random effect's coefficient table comes out of the
program that also scatters its scores, so the barrier covers the sweep).
Every unit is the same work: nothing is warm-started between units.

What ``fit`` returns is coefficients, so coefficients are what is held
against the plain reference (``reference/game.py``, its own sweep after the
program's state is dropped): the reference evaluates its own objectives and
gradients at the program's coefficients. The solves' own reports (first
gradient norm, loss where they stopped) do not leave ``fit`` and are not
compared.

The window's counts come from the program's spans (``glm.solve`` for the
fixed effect, ``game.re.solve`` per bucket), which exist while a profiler
runs: the traced run's work model and readers read them; an untraced run
needs none. A program without those spans cannot be measured by this family,
and fails here at the import of the span's name, before any set-up.
"""

from __future__ import annotations

import gc
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.families.common import Comparison, rel_gap
from benchmark.reference import game as reference
from benchmark.reference import glm as reference_glm
from benchmark.reference.lbfgs import options as lbfgs_options
from benchmark.work import game as work

FAULTS = reference.FAULTS
FIXED_SPAN = "glm.solve"


def _program():
    """The program's names, imported late: a checkout without the program,
    or with one that records no ``game.re.solve`` spans, fails here."""
    from photon_ml_tpu.game.data import GameData, RandomEffectDatasetConfig
    from photon_ml_tpu.game.estimator import (
        FixedEffectCoordinateConfig,
        GameEstimator,
        GameOptimizationConfiguration,
        RandomEffectCoordinateConfig,
    )
    from photon_ml_tpu.game.random_effect import SOLVE_SPAN
    from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
    from photon_ml_tpu.ops.regularization import L2Regularization
    from photon_ml_tpu.optimize import OptimizerConfig
    from photon_ml_tpu.telemetry import tracing
    from photon_ml_tpu.testing import dense_shard
    from photon_ml_tpu.types import OptimizerType, TaskType

    return locals()


def _estimator(p: dict, config: dict):
    """The configuration as the program's own objects."""
    opt = config["optimizer"]
    if (opt["type"], config["regularization"]) != ("LBFGS", "L2"):
        raise ValueError("the game family drives L-BFGS with L2 only")
    optimization = p["GLMOptimizationConfiguration"](
        optimizer=p["OptimizerType"].LBFGS,
        regularization=p["L2Regularization"],
        optimizer_config=p["OptimizerConfig"](
            **lbfgs_options(opt), track_states=bool(opt["track_states"])))
    coordinates = {}
    for cid, c in config["coordinates"].items():
        if c["type"] == "fixed_effect":
            coordinates[cid] = p["FixedEffectCoordinateConfig"](
                feature_shard_id=c["feature_shard"], optimization=optimization,
                design_dtype=config["design_dtype"])
        else:
            coordinates[cid] = p["RandomEffectCoordinateConfig"](
                dataset=p["RandomEffectDatasetConfig"](
                    c["entity"], c["feature_shard"],
                    bucket_strategy=config["buckets"]["strategy"],
                    max_sample_buckets=int(
                        config["buckets"]["max_sample_buckets"])),
                optimization=optimization,
                design_dtype=config["design_dtype"])
    return p["GameEstimator"](
        task=p["TaskType"][config["task"]], coordinate_configs=coordinates,
        update_sequence=list(config["update_sequence"]),
        n_cd_iterations=int(config["cd_iterations"]))


class Cell:
    def __init__(self, seed: int, config: dict, workload: dict, devices):
        p = _program()
        self._tracing, self._solve_span = p["tracing"], p["SOLVE_SPAN"]
        self.config, self.workload = config, workload
        if len(devices) != 1 or int(workload["chips"]) != 1:
            raise ValueError("the game family drives one chip")
        gen = importlib.import_module(f"benchmark.gen.{workload['generator']}")
        arrays = gen.generate(seed, workload, config)
        coordinates = config["coordinates"]
        self.random_ids = [c for c in config["update_sequence"]
                           if coordinates[c]["type"] == "random_effect"]
        self.fixed_id = config["update_sequence"][0]
        shards, ids = arrays["shards"], arrays["ids"]
        #: the benchmark's own data, from the seed: what the reference reads
        self.host = {**shards, **ids, "y": arrays["y"]}
        self._reference_data = None
        self.rows = int(arrays["y"].shape[0])
        self.rows_per_unit = self.rows
        self.game_data = p["GameData"].build(
            labels=arrays["y"],
            shards={k: p["dense_shard"](v) for k, v in shards.items()},
            id_columns=ids)
        self.estimator = _estimator(p, config)
        self.datasets = self.estimator.prepare(self.game_data)
        self.configuration = p["GameOptimizationConfiguration"](
            dict(config["regularization_weights"]))
        self.buckets = sum(len(self.datasets[c].buckets)
                           for c in self.random_ids)
        self.units = 0
        self.last = None  # the last unit's GameModel
        self._outputs = None

    # --- the timed path ----------------------------------------------------
    def unit(self) -> None:
        result = self.estimator.fit(self.game_data, [self.configuration],
                                    datasets=self.datasets)[0]
        models = result.model.coordinates
        jax.block_until_ready(
            [models[self.fixed_id].model.coefficients.means]
            + [models[c].coeffs_device for c in self.random_ids])
        self.last, self._outputs = result.model, None
        self.units += 1

    def reset_counts(self) -> None:
        self.units = 0

    # --- what the readers and the work model see ---------------------------
    def counters(self) -> dict:
        return {"units": self.units, "fixed_solves": self.units,
                "re_solves": self.units * self.buckets,
                "steps": self.units * len(self.config["update_sequence"])}

    def _window_solves(self) -> tuple[list[dict], list[dict]] | None:
        """The window's ``glm.solve`` and ``game.re.solve`` records, or None
        where the ring does not hold exactly the window's (``readers/
        program_records.py``'s check, by count)."""
        records = self._tracing.recorded()
        fixed = [r for r in records if r["name"] == FIXED_SPAN]
        lanes = [r for r in records if r["name"] == self._solve_span]
        counted = self.counters()
        if (len(fixed), len(lanes)) != (counted["fixed_solves"],
                                       counted["re_solves"]):
            return None
        return fixed, lanes

    def required_work(self) -> dict:
        """Least work for the window's sweeps (``work/game.py``), and beside
        it what the entity kernel's buckets were asked for, rejected trial
        points included: the kernel's share of its roofline is held against
        that."""
        solves = self._window_solves()
        if solves is None:
            return {"flops_per_chip": 0.0, "bytes_per_chip": 0.0, "passes": 0}
        fixed, lanes = solves
        itemsize = jnp.dtype(self.config["design_dtype"]).itemsize
        f_fixed, b_fixed = work.fixed_work(fixed, itemsize)
        f_lanes, b_lanes = work.random_work(lanes, count="iterations",
                                            itemsize=itemsize)
        f_kernel, b_kernel = work.random_work(
            [s for s in lanes if s["kernel"] == "pallas"],
            count="evaluations", itemsize=itemsize)
        return {"flops_per_chip": f_fixed + f_lanes,
                "bytes_per_chip": b_fixed + b_lanes,
                "passes": sum(int(s["iterations"]) + 1 for s in fixed),
                "fixed_flops": f_fixed, "fixed_bytes": b_fixed,
                "entity_kernel_flops": f_kernel,
                "entity_kernel_bytes": b_kernel}

    def describe(self) -> dict:
        """The buckets the program built, which path serves each, and what
        the window's last unit counted in each: ``[entities, s_max, dim,
        kernel, rows, iterations, evaluations, max_lane_evaluations]``."""
        solves = self._window_solves()
        last = {} if solves is None else {
            (s["coordinate"], s["bucket"]): s for s in solves[1]}
        counted = ("kernel", "rows", "iterations", "evaluations",
                   "max_lane_evaluations")
        return {"rows": self.rows, "buckets": {
            c: [[*b.tensor_shape,
                 *(last.get((c, i), {}).get(k) for k in counted)]
                for i, b in enumerate(self.datasets[c].buckets)]
            for c in self.random_ids}}

    # --- after the window ---------------------------------------------------
    def outputs(self) -> list[dict]:
        if self._outputs is None:
            self._outputs = outputs_of(self.last, self.config, self.workload)
        return self._outputs

    def release(self) -> None:
        """Drop the program's state (its data, buckets and device caches);
        the last unit's coefficients come to the host first, and the
        generator's arrays stay for the reference."""
        self.outputs()
        self.last = self.game_data = self.datasets = self.estimator = None
        gc.collect()

    def reference_data(self) -> dict:
        """The generator's arrays as the reference reads them: shards and
        labels on the device, id columns on the host. Placed at the first
        call, which comes after the window and :meth:`release`: they are no
        part of the program's footprint."""
        if self._reference_data is None:
            self._reference_data = {
                k: v if v.dtype == np.int64 else jnp.asarray(v)
                for k, v in self.host.items()}
        return self._reference_data

    def check(self) -> list[Comparison]:
        return compare(self.outputs(), self.reference_data(), self.config,
                       self.workload)


def outputs_of(model, config: dict, workload: dict) -> list[dict]:
    """Host copies of the coefficients ``fit`` returned, one dict a
    coordinate in the update sequence: the fixed effect's vector; a random
    effect's ``(entities, dim)`` table with ``has`` marking the entities that
    got coefficients."""
    out = []
    for cid in config["update_sequence"]:
        c, m = config["coordinates"][cid], model.coordinates[cid]
        if c["type"] == "fixed_effect":
            out.append({"coordinate": cid, "w": np.asarray(
                m.model.coefficients.means, np.float64)})
            continue
        n = int(workload[c["count"]])
        keys = np.asarray(m.keys, np.int64)
        table, has = np.zeros((n, m.dim)), np.zeros(n, bool)
        table[keys // m.dim, keys % m.dim] = np.asarray(m.coeffs, np.float64)
        has[keys // m.dim] = True
        out.append({"coordinate": cid, "w": table, "has": has})
    return out


def compare(outputs: list[dict], data: dict, config: dict, workload: dict,
            ref: list[dict] | None = None) -> list[Comparison]:
    """The last unit's coefficients against the plain reference's own sweep.

    Fixed effect: the reference's gradient norm at the program's vector over
    the first gradient's norm (``kkt``), its loss there against the loss the
    reference's own solve reached, and the distance between the two vectors.
    Every random effect, entity by entity, each entity's objective taken with
    the margins of the PROGRAM's earlier coordinates as offsets (so a
    coordinate answers for its own solve): the reference's gradient norm at
    the program's coefficients over the larger of 1 and the entity's first
    gradient norm (the scale of the solver's own test), the worst entity and
    the mean weighted by rows; the entity's objective there against the value
    the reference's solve reached, over the larger of 1 and that value,
    worst and row-weighted mean; the distance between the two coefficient
    vectors over the larger of 1 and the reference's norm, worst over the
    entities whose reference solve converged; the share of entities with
    rows and no coefficients. The sweep: the final margins and the data loss
    that the program's coefficients give against the reference's.
    """
    if ref is None:
        ref = reference.sweep(data, config, workload)
    by_id = {o["coordinate"]: o for o in outputs}
    weights = config["regularization_weights"]
    coordinates = config["coordinates"]
    fixed_id, *random_ids = config["update_sequence"]
    y = data["y"]
    numbers = {}

    ours, theirs = by_id[fixed_id], ref[0]
    xf = data[coordinates[fixed_id]["feature_shard"]]
    f, g = reference_glm.value_and_grad(
        xf, y, jnp.asarray(ours["w"], jnp.float32),
        jnp.float32(weights[fixed_id]), chunk=int(workload["row_chunk"]))
    numbers["fixed_kkt_gap"] = rel_gap(
        float(jnp.linalg.norm(g)), 0.0, scale=theirs["grad0_norm"])
    numbers["fixed_loss_gap"] = rel_gap(float(f), theirs["value"])
    numbers["fixed_coef_gap"] = rel_gap(
        np.linalg.norm(ours["w"] - theirs["w"]), 0.0,
        scale=np.linalg.norm(theirs["w"]))

    total = reference.margins_of(xf, ours["w"])
    for k, cid in enumerate(random_ids, start=1):
        c, ours, theirs = coordinates[cid], by_id[cid], ref[k]
        x, ids = data[c["feature_shard"]], data[c["entity"]]
        n = int(workload[c["count"]])
        groups = reference.groups_of(ids, n)
        rows = np.bincount(ids, minlength=n).astype(np.float64)
        live = rows > 0
        values, gnorms = reference.evaluate_entities(
            x, y, total, groups, ours["w"], weights[cid])
        _, g0 = reference.evaluate_entities(
            x, y, total, groups, np.zeros_like(ours["w"]), weights[cid])
        share = rows / rows.sum()
        kkt = gnorms / np.maximum(g0, 1.0)
        loss = np.abs(values - theirs["value"]) / np.maximum(
            theirs["value"], 1.0)
        apart = np.linalg.norm(ours["w"] - theirs["w"], axis=1) / np.maximum(
            np.linalg.norm(theirs["w"], axis=1), 1.0)
        short = c["short"]
        numbers[f"{short}_kkt_worst"] = _finite(kkt[live].max())
        numbers[f"{short}_kkt_mean"] = _finite(kkt @ share)
        numbers[f"{short}_loss_gap_worst"] = _finite(loss[live].max())
        numbers[f"{short}_loss_gap_mean"] = _finite(loss @ share)
        numbers[f"{short}_coef_gap"] = _finite(
            apart[live & theirs["converged"]].max())
        numbers[f"{short}_missing_share"] = float(
            np.mean(~ours["has"][live]))
        total = total + reference.margins_of(x, ours["w"], ids)

    theirs = ref[-1]
    numbers["margin_gap"] = rel_gap(
        np.linalg.norm(np.asarray(total, np.float64) - theirs["margins"]),
        0.0, scale=np.linalg.norm(theirs["margins"].astype(np.float64)))
    numbers["total_loss_gap"] = rel_gap(
        reference.total_loss(total, y), theirs["loss"])
    limits = workload["limits"]
    return [Comparison(n, v, float(limits[n])) for n, v in numbers.items()]


def _finite(value) -> float:
    value = float(value)
    return value if np.isfinite(value) else np.inf


def setup(seed: int, config: dict, workload: dict, devices) -> Cell:
    return Cell(seed, config, workload, devices)


# --- what the selfcheck and the readings ask besides (families/common.py) ---
def reference_outputs(cell: Cell) -> list[dict]:
    return reference.sweep(cell.reference_data(), cell.config, cell.workload)


def compare_outputs(cell: Cell, outputs: list[dict],
                    ref: list[dict] | None = None) -> list[Comparison]:
    return compare(outputs, cell.reference_data(), cell.config,
                   cell.workload, ref)


def stand_ins(cell: Cell, faults, ref: list[dict]):
    """``(name, outputs)`` of the lower-precision control (the reference's
    sweep on designs rounded to bfloat16, in the program's place) and of each
    of ``faults`` planted in the reference (``reference/game.py``)."""
    data = cell.reference_data()
    yield "control_bfloat16", reference.sweep(
        data, cell.config, cell.workload, round_to="bfloat16")[:-1]
    for kind in faults:
        yield f"fault_{kind}", reference.sweep(
            data, cell.config, cell.workload, fault=kind)[:-1]
