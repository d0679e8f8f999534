"""GAME GLMix (fixed + per-user + per-song) through ``GameEstimator.fit``
against the plain reference sweep (``benchmark/reference/game.py``), at the
benchmark configuration's settings and a small scale: a few thousand rows,
some hundreds of entities under a Zipf law (a head entity with hundreds of
rows, most with under ten). And the spans a traced fit records of its solves.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.gen import game_user_song
from benchmark.reference import game as reference
from benchmark.reference import glm as reference_glm
from photon_ml_tpu.game.data import GameData, RandomEffectDatasetConfig
from photon_ml_tpu.game.estimator import (
    FixedEffectCoordinateConfig,
    GameEstimator,
    GameOptimizationConfiguration,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.ops.regularization import L2Regularization
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.telemetry import tracing
from photon_ml_tpu.testing import dense_shard
from photon_ml_tpu.types import TaskType

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "game_glmix_user_song.json")) as f:
    CONFIG = json.load(f)
WORKLOAD = dict(rows=4000, users=300, songs=120, key_skew=1.0,
                row_chunk=1000, problem_seed=20261002)
ENTITIES = {"perUser": ("userId", 300), "perSong": ("songId", 120)}


def _fit(arrays, design_dtype="float32"):
    """One sweep on the normal path, as ``bench.py::bench_cd_sweep`` and
    ``cli/train_game.py`` reach it: ``prepare`` once, then ``fit``."""
    opt = CONFIG["optimizer"]
    optimization = GLMOptimizationConfiguration(
        regularization=L2Regularization,
        optimizer_config=OptimizerConfig(
            max_iterations=opt["max_iterations"], tolerance=opt["tolerance"],
            history=opt["history"], max_line_search=opt["max_line_search"],
            track_states=opt["track_states"]))
    random = lambda entity: RandomEffectCoordinateConfig(
        dataset=RandomEffectDatasetConfig(
            entity, "item", bucket_strategy=CONFIG["buckets"]["strategy"],
            max_sample_buckets=CONFIG["buckets"]["max_sample_buckets"]),
        optimization=optimization, design_dtype=design_dtype)
    estimator = GameEstimator(
        task=TaskType.LOGISTIC_REGRESSION,
        coordinate_configs={
            "global": FixedEffectCoordinateConfig(
                feature_shard_id="fixed", optimization=optimization,
                design_dtype=design_dtype),
            "perUser": random("userId"), "perSong": random("songId")},
        update_sequence=CONFIG["update_sequence"], n_cd_iterations=1)
    data = GameData.build(
        labels=arrays["y"],
        shards={k: dense_shard(v) for k, v in arrays["shards"].items()},
        id_columns=arrays["ids"])
    datasets = estimator.prepare(data)
    result = estimator.fit(
        data, [GameOptimizationConfiguration(CONFIG["regularization_weights"])],
        datasets=datasets)[0]
    return result.model, datasets


def _table(model, n):
    keys = np.asarray(model.keys, np.int64)
    table, has = np.zeros((n, model.dim)), np.zeros(n, bool)
    table[keys // model.dim, keys % model.dim] = model.coeffs
    has[keys // model.dim] = True
    return table, has


@pytest.fixture(scope="module")
def problem():
    arrays = game_user_song.generate(2**31 + 9, WORKLOAD, CONFIG)
    data = {**{k: jnp.asarray(v) for k, v in arrays["shards"].items()},
            **arrays["ids"], "y": jnp.asarray(arrays["y"])}
    return arrays, data, reference.sweep(data, CONFIG, WORKLOAD)


def _gaps(model, data, ref):
    """What the fit is held to, each number against the reference's sweep.
    A coordinate's entities are judged with the margins of the fit's own
    earlier coordinates as offsets, so each solve answers for itself."""
    fixed, theirs = np.asarray(model.coordinates["global"].model
                               .coefficients.means, np.float64), ref[0]
    _, g = reference_glm.value_and_grad(
        data["fixed"], data["y"], jnp.asarray(fixed, jnp.float32),
        jnp.float32(CONFIG["regularization_weights"]["global"]),
        chunk=WORKLOAD["row_chunk"])
    gaps = {"fixed_coefficients": np.linalg.norm(fixed - theirs["w"])
            / np.linalg.norm(theirs["w"]),
            "fixed_gradient": float(jnp.linalg.norm(g))
            / theirs["grad0_norm"]}
    total = reference.margins_of(data["fixed"], fixed)
    for k, (cid, (entity, n)) in enumerate(ENTITIES.items(), start=1):
        table, has = _table(model.coordinates[cid], n)
        rows = np.bincount(data[entity], minlength=n)
        assert np.array_equal(has, rows > 0), cid  # every entity with a row
        groups = reference.groups_of(data[entity], n)
        lam = CONFIG["regularization_weights"][cid]
        _, gnorm = reference.evaluate_entities(
            data["item"], data["y"], total, groups, table, lam)
        _, g0 = reference.evaluate_entities(
            data["item"], data["y"], total, groups, np.zeros_like(table), lam)
        kkt = gnorm / np.maximum(g0, 1.0)
        apart = np.linalg.norm(table - ref[k]["w"], axis=1) / np.maximum(
            np.linalg.norm(ref[k]["w"], axis=1), 1.0)
        gaps[f"{cid}_gradient_worst"] = kkt.max()
        gaps[f"{cid}_gradient_mean"] = kkt @ rows / rows.sum()
        gaps[f"{cid}_coefficients_worst"] = apart.max()
        total = total + reference.margins_of(data["item"], table,
                                             data[entity])
    gaps["margins"] = np.linalg.norm(
        np.asarray(total, np.float64) - ref[-1]["margins"]) \
        / np.linalg.norm(ref[-1]["margins"].astype(np.float64))
    return gaps


#: Each tolerance with its reason. Program and reference both run float32
#: objectives under an Armijo search on float32 VALUES: a step whose decrease
#: is under the value's last bit is refused, which leaves a gradient of about
#: sqrt(2 * l2 * ulp(f)): 1e-3 absolute at f ~ 20, l2 = 1, so no solve meets
#: the configuration's 1e-6 and two sound solves stop a few 1e-4 apart.
TOLERANCES = {
    # both fixed-effect solves run the same rule on the same objective; on
    # this float64-enabled test backend they end 8e-8 apart (1e-4 where the
    # program runs pure float32, in the benchmark's selfcheck: the cap of 25
    # stops both short); bfloat16 reads 3.0e-4
    "fixed_coefficients": 1.5e-4,
    # reference gradient at the fit's vector over the first gradient: where
    # the cap leaves a sound solve (read 1.9e-5; bfloat16 2.4e-4)
    "fixed_gradient": 1e-4,
    # an entity's gradient at the fit's coefficients over max(1, its first):
    # the float32 floor above over a first gradient of a few units (read
    # 4.3e-4 and 5.3e-4 at worst, 3.7e-5 and 6.4e-5 in the row-weighted
    # mean; bfloat16: 4.8e-3, 6.2e-3 and 1.3e-3, 1.8e-3)
    "perUser_gradient_worst": 1.5e-3, "perUser_gradient_mean": 2e-4,
    "perSong_gradient_worst": 1.5e-3, "perSong_gradient_mean": 3e-4,
    # two solves that each stop within the floor of one optimum, the
    # curvature at least l2 = 1 (read 1.1e-3 and 5.6e-4 at worst)
    "perUser_coefficients_worst": 4e-3, "perSong_coefficients_worst": 4e-3,
    # the sum of the three coordinates' differences (read 6.8e-5; bfloat16
    # 7.9e-4)
    "margins": 3e-4,
}


def test_fit_agrees_with_the_reference_sweep(problem):
    arrays, data, ref = problem
    model, _ = _fit(arrays)
    gaps = _gaps(model, data, ref)
    assert set(gaps) == set(TOLERANCES)
    over = {k: (v, TOLERANCES[k]) for k, v in gaps.items()
            if not v <= TOLERANCES[k]}
    assert not over, over


def test_a_bfloat16_design_is_not_within_the_tolerances(problem):
    """The tolerances are tight enough to tell the stated precision from the
    next one down: the same fit on bfloat16 designs fails at least one (the
    per-entity gradients: few rows an entity, so the rounding does not
    average out as it does over the fixed effect's thousands)."""
    arrays, data, ref = problem
    model, _ = _fit(arrays, design_dtype="bfloat16")
    gaps = _gaps(model, data, ref)
    over = {k for k, v in gaps.items() if not v <= TOLERANCES[k]}
    assert over & {"perUser_gradient_mean", "perSong_gradient_mean"}, gaps


@pytest.fixture
def records():
    """Completed span records through a tap: a sink, so spans are kept, and
    not ``--telemetry-dir``'s, whose steps read losses and wait."""
    got = []
    remove = tracing.GLOBAL_TRACER.add_tap(got.append)
    try:
        yield got
    finally:
        remove()


def test_a_traced_fit_records_one_solve_span_a_bucket(problem, records):
    arrays, _, _ = problem
    assert not tracing.enabled()
    _, datasets = _fit(arrays)
    tracing.flush()
    steps = {r["coordinate"]: r for r in records if r["name"] == "cd.step"}
    assert all(s["rows"] == WORKLOAD["rows"] for s in steps.values())
    fixed = [r for r in records if r["name"] == "glm.solve"]
    assert len(fixed) == 1 and fixed[0]["coordinate"] == "global"
    assert fixed[0]["rows"] == WORKLOAD["rows"] and fixed[0]["dim"] == 32
    assert 1 <= fixed[0]["iterations"] <= 25
    assert fixed[0]["evaluations"] >= fixed[0]["iterations"] + 1
    assert steps["global"]["evaluations"] == fixed[0]["evaluations"]
    for cid, (entity, _) in ENTITIES.items():
        spans = [r for r in records if r["name"] == "game.re.solve"
                 and r["coordinate"] == cid]
        buckets = datasets[cid].buckets
        assert sorted(s["bucket"] for s in spans) == list(range(len(buckets)))
        present = len(np.unique(arrays["ids"][entity]))
        assert sum(s["lanes"] for s in spans) == present
        assert sum(s["rows"] for s in spans) == WORKLOAD["rows"]
        assert steps[cid]["evaluations"] == sum(s["evaluations"]
                                                for s in spans)
        for s in spans:
            e, smax, d = buckets[s["bucket"]].tensor_shape
            assert (s["lanes"], s["s_max"], s["dim"]) == (e, smax, d)
            assert s["kernel"] == "closed_form"  # no TPU here
            assert "unresolved" not in s
            assert 0 <= s["converged"] <= s["lanes"]
            assert s["lanes"] <= s["evaluations"] \
                <= s["lanes"] * s["max_lane_evaluations"]
            # the bucket ran what its slowest lane evaluated, and no more
            assert s["passes"] == s["max_lane_evaluations"]
            # over the lanes still running, in stages (none under 1,024)
            assert s["evaluations"] <= s["lane_passes"] \
                <= e * s["passes"]
            assert e >= 1024 or s["compactions"] == 0
            assert s["iterations"] <= s["evaluations"] - s["lanes"]
            # every lane has a row at least and s_max at most
            assert s["evaluations"] <= s["row_evaluations"] \
                <= s["evaluations"] * smax
            assert s["row_iterations"] <= s["row_evaluations"] - s["rows"]


def test_the_set_up_and_the_unit_spans_nest_as_declared(problem, records):
    """``game.data.build`` under nothing, ``game.prepare`` > one
    ``game.dataset.build`` a coordinate, ``game.fit`` > ``cd.sweep``: the
    spans ``benchmark/readers/setup_spans.py`` reads the set-up by."""
    arrays, _, _ = problem
    _, datasets = _fit(arrays)
    tracing.flush()
    by_id = {r["span_id"]: r for r in records}
    parent = lambda r: by_id.get(r["parent_id"], {}).get("name")
    named = lambda name: [r for r in records if r["name"] == name]
    (build,) = named("game.data.build")
    assert build["parent_id"] is None
    assert (build["rows"], build["shards"], build["id_columns"]) \
        == (WORKLOAD["rows"], 2, 2)
    (prepare,) = named("game.prepare")
    assert prepare["parent_id"] is None
    builds = {r["coordinate"]: r for r in named("game.dataset.build")}
    assert set(builds) == set(CONFIG["update_sequence"])
    assert all(parent(r) == "game.prepare" for r in builds.values())
    assert builds["global"]["kind"] == "fixed_effect"
    for cid in ENTITIES:
        assert builds[cid]["kind"] == "random_effect"
        assert builds[cid]["rows"] == WORKLOAD["rows"]
        assert builds[cid]["buckets"] == len(datasets[cid].buckets)
        assert builds[cid]["entities"] == datasets[cid].n_active_entities
    (fit,) = named("game.fit")
    assert fit["parent_id"] is None and fit["configurations"] == 1
    sweeps = named("cd.sweep")
    assert sweeps and all(parent(r) == "game.fit" for r in sweeps)
    # a warm compile that outlives prepare re-parents to root, never to a
    # span that closed before it did
    for r in named("jit.compile"):
        assert r["parent_id"] is None or r["parent_id"] in by_id
    for outer in (build, prepare, fit):
        assert all(outer["t1"] <= r["t0"] or r["t1"] <= outer["t0"]
                   for r in (build, prepare, fit) if r is not outer)


def test_set_on_enclosing_reaches_the_nearest_open_span_of_that_name(records):
    with tracing.span("cd.step", coordinate="a") as outer:
        with tracing.span("cd.step", coordinate="b"):
            with tracing.span("glm.solve"):
                tracing.set_on_enclosing("cd.step", evaluations=7)
            tracing.set_on_enclosing("no.such.span", evaluations=1)
        assert "evaluations" not in outer.attrs
    tracing.set_on_enclosing("cd.step", evaluations=9)  # none open: nothing
    steps = {r["coordinate"]: r for r in records if r["name"] == "cd.step"}
    assert steps["b"]["evaluations"] == 7
    assert "evaluations" not in steps["a"]


def test_no_span_is_kept_with_tracing_off(problem):
    arrays, _, _ = problem
    tracing.GLOBAL_TRACER._ring.clear()
    _fit(arrays)
    assert tracing.recorded() == []
    assert not tracing.GLOBAL_TRACER._pending


@pytest.mark.parametrize("kernel", ["closed_form", "pallas_interpreter"])
def test_a_buckets_counts_are_its_lanes_results_summed(kernel):
    """``_solve_bucket_impl``'s counts against the same lanes' own results
    (``problem.run_lanes``, the flat loop the bucket runs, its per-lane
    ``OptimizerResult`` kept): weight-0 lanes, which solve nothing, are not
    counted, and ``passes``, the loop's own count, is the slowest lane's
    evaluations. The coefficients are those of ``problem.run`` under
    ``vmap`` (the nested loops a bucket ran before it had a loop of its own)
    to the solve's tolerance: the same rules, a dot product summed in another
    order."""
    from photon_ml_tpu.game.random_effect import (
        RandomEffectSolver,
        _solve_bucket_impl,
    )
    from photon_ml_tpu.ops.design import DenseDesign
    from photon_ml_tpu.ops.objective import GLMData

    rng = np.random.default_rng(8)
    lanes, s, d = 7, 24, 4
    x = rng.normal(size=(lanes, s, d)).astype(np.float32) * 3
    y = (rng.random((lanes, s)) < 0.5).astype(np.float32)
    weights = (np.arange(s)[None, :]
               < np.array([24, 3, 9, 1, 17, 0, 0])[:, None]).astype(np.float32)
    x = x * weights[:, :, None]
    offsets = rng.normal(size=(lanes, s)).astype(np.float32) * weights
    solver = RandomEffectSolver(
        task=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=25,
                                             track_states=False)),
        fused_interpret=kernel == "pallas_interpreter")
    args = tuple(jnp.asarray(a) for a in (x, y, offsets, weights))
    w0, lam = jnp.zeros((lanes, d), jnp.float32), jnp.float32(1.0)
    w, _, converged, counts = jax.jit(
        _solve_bucket_impl, static_argnames="solver")(solver, *args, w0, lam)
    problem_ = solver._problem()
    # the kernel's block plan pads this bucket to its 128 lanes; the closed
    # form pads nothing
    assert problem_.objective.entity_pad(args[0]) \
        == (128 - lanes if kernel == "pallas_interpreter" else 0)
    data = GLMData(design=DenseDesign(x=args[0]), labels=args[1],
                   offsets=args[2], weights=args[3])
    per_lane, loop = jax.jit(problem_.run_lanes)(data, w0, lam)
    np.testing.assert_array_equal(w, per_lane.w)
    np.testing.assert_array_equal(converged, per_lane.converged)
    assert int(counts["passes"]) == int(loop.passes) \
        == int(counts["max_lane_evaluations"])
    # one stage under eight 128-lane rows: every lane rides every pass, the
    # bucket's pad lanes included
    padded = lanes + problem_.objective.entity_pad(args[0])
    assert int(counts["lane_passes"]) == padded * int(loop.passes)
    assert int(loop.lane_passes) == lanes * int(loop.passes)
    assert int(counts["compactions"]) == int(loop.compactions) == 0
    nested = jax.jit(jax.vmap(problem_.run, in_axes=(0, 0, None)))(
        data, w0, lam)
    np.testing.assert_allclose(w, nested.w, rtol=1e-3, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(nested.iterations) == 0,
                                  np.asarray(per_lane.iterations) == 0)
    real = slice(0, 5)
    rows = weights.sum(axis=1)[real]
    assert int(counts["lanes"]) == 5
    assert int(counts["iterations"]) == int(per_lane.iterations[real].sum())
    assert int(counts["evaluations"]) == int(per_lane.evaluations[real].sum())
    assert int(counts["max_lane_evaluations"]) \
        == int(per_lane.evaluations.max())
    assert int(counts["converged"]) == int(per_lane.converged[real].sum())
    assert float(counts["row_iterations"]) \
        == float(rows @ np.asarray(per_lane.iterations[real]))
    assert float(counts["row_evaluations"]) \
        == float(rows @ np.asarray(per_lane.evaluations[real]))
    assert len(set(np.asarray(per_lane.evaluations[real]))) >= 3
    assert np.all(np.asarray(per_lane.iterations[5:]) == 0)


@pytest.mark.parametrize("design_dtype", ["float32", "bfloat16"])
def test_the_kernels_bucket_solve_is_the_closed_forms_lane_for_lane(
        design_dtype):
    """A bucket's solve through the entities-last kernel (the interpreter)
    against the same solve on the closed-form route, lane by lane. The flat
    loop is the same and its rules are; the kernel sums a lane's rows and
    columns in another order, which moves last bits of values and gradients.
    So at a tolerance that ends every lane above float32's floor (1e-3: a
    gradient of about sqrt(2 x l2 x ulp(value)), some 1e-3 here, is what the
    Armijo search on function values leaves; at 1e-4 a tenth of these lanes
    end there, other lanes on either route) each lane converges in as many
    iterations and evaluations on either route,
    to the same coefficients by tolerance; and either way the bucket's
    ``passes`` are its slowest lane's evaluations. 140 lanes: two blocks of
    128, the second mostly padding."""
    from photon_ml_tpu.game.random_effect import RandomEffectSolver
    from photon_ml_tpu.ops.design import DenseDesign
    from photon_ml_tpu.ops.objective import GLMData

    rng = np.random.default_rng(31)
    lanes, s, d = 140, 21, 5
    dtype = jnp.dtype(design_dtype)
    x = np.asarray(jnp.asarray(rng.normal(size=(lanes, s, d)), dtype)
                   .astype(jnp.float32))
    rows = rng.integers(1, s + 1, size=lanes)
    rows[[3, 77]] = 0  # lanes that weigh nothing
    rows[5] = 1
    weights = (np.arange(s)[None, :] < rows[:, None]).astype(np.float32)
    x = x * weights[:, :, None]
    planted = rng.normal(size=(lanes, d))
    p = 1.0 / (1.0 + np.exp(-np.einsum("esd,ed->es", x, planted)))
    y = (rng.random((lanes, s)) < p).astype(np.float32)
    offsets = rng.normal(size=(lanes, s)).astype(np.float32) * weights
    data = GLMData(design=DenseDesign(x=jnp.asarray(x, dtype)),
                   labels=jnp.asarray(y), offsets=jnp.asarray(offsets),
                   weights=jnp.asarray(weights))
    w0, lam = jnp.zeros((lanes, d), jnp.float32), jnp.float32(1.0)

    def solve(**route):
        problem_ = RandomEffectSolver(
            task=TaskType.LOGISTIC_REGRESSION,
            config=GLMOptimizationConfiguration(
                regularization=L2Regularization,
                optimizer_config=OptimizerConfig(
                    max_iterations=25, tolerance=1e-3, track_states=False)),
            design_dtype=design_dtype, **route)._problem()
        kernel = problem_.objective.entity_kernel_evaluation(data, lam)
        assert (kernel is not None) == route.get("fused_interpret", False)
        return jax.jit(problem_.run_lanes)(data, w0, lam)

    (kernel, kernel_passes) = solve(fused_interpret=True)
    (closed, closed_passes) = solve(fused=False)
    assert np.asarray(kernel.converged).all()
    for name in ("iterations", "evaluations", "converged"):
        np.testing.assert_array_equal(getattr(kernel, name),
                                      getattr(closed, name), err_msg=name)
    np.testing.assert_allclose(kernel.w, closed.w, rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(kernel.value, closed.value, rtol=1e-5)
    evaluations = np.asarray(kernel.evaluations)
    assert (evaluations[[3, 77]] == 1).all()
    assert len(set(evaluations)) >= 4
    assert int(kernel_passes.passes) == int(closed_passes.passes) \
        == evaluations.max()
