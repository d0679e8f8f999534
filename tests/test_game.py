"""GAME stack tests: bucketing, vmapped random-effect solves, coordinate descent.

Mirrors the reference's integration-test strategy
(``RandomEffectDatasetIntegTest``, ``CoordinateDescentIntegTest``,
``GameEstimatorIntegTest``) on synthetic mixed-effect data: a global fixed
effect plus per-entity random intercept/slopes, so GAME must beat the
fixed-effect-only model.
"""

import numpy as np
import pytest

from photon_ml_tpu.evaluation import parse_evaluators
from photon_ml_tpu.game import (
    FixedEffectDataset,
    GameData,
    FeatureShard,
    RandomEffectDataset,
    RandomEffectDatasetConfig,
    GameEstimator,
    GameOptimizationConfiguration,
)
from photon_ml_tpu.game.coordinate import FixedEffectCoordinate, RandomEffectCoordinate
from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
from photon_ml_tpu.game.estimator import (
    FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.game.random_effect import RandomEffectSolver
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.testing import dense_shard
from photon_ml_tpu.ops.regularization import L2Regularization
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.types import TaskType


def make_mixed_data(n=2000, d_fixed=8, d_re=4, n_entities=37, seed=0,
                    param_seed=12345, labels_fn=None, effect_scale=1.5):
    """Mixed-effect data: global effect plus per-entity random slopes.

    ``param_seed`` fixes the true (w_fixed, u) so train/validation splits
    drawn with different ``seed`` share one distribution. ``labels_fn``
    maps ``(rng, margin) -> labels`` (default: sigmoid draw = logistic).
    """
    prng = np.random.default_rng(param_seed)
    w_fixed = prng.normal(size=d_fixed).astype(np.float32)
    u = (effect_scale * prng.normal(size=(n_entities, d_re))).astype(
        np.float32)
    rng = np.random.default_rng(seed)
    xf = rng.normal(size=(n, d_fixed)).astype(np.float32)
    xr = rng.normal(size=(n, d_re)).astype(np.float32)
    # power-law-ish entity sizes
    probs = 1.0 / np.arange(1, n_entities + 1)
    probs /= probs.sum()
    ent = rng.choice(n_entities, size=n, p=probs).astype(np.int64)
    margin = xf @ w_fixed + np.einsum("nd,nd->n", xr, u[ent])
    if labels_fn is None:
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
            np.float32)
    else:
        y = np.asarray(labels_fn(rng, margin), np.float32)

    data = GameData.build(
        labels=y,
        shards={"fixed": dense_shard(xf), "re": dense_shard(xr)},
        id_columns={"entityId": ent},
    )
    return data, (xf, xr, ent, w_fixed, u)


class TestRandomEffectDataset:
    def test_bucket_roundtrip(self):
        data, (xf, xr, ent, *_) = make_mixed_data(n=500, n_entities=11)
        ds = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig("entityId", "re"))
        # every sample appears exactly once (active xor passive)
        seen = np.concatenate(
            [b.sample_idx[b.sample_idx >= 0] for b in ds.buckets]
            + [ds.passive_sample_idx])
        assert sorted(seen.tolist()) == list(range(500))
        # bucket features reconstruct the original rows
        for b in ds.buckets:
            for e in range(b.n_entities):
                for s in range(b.x.shape[1]):
                    g = b.sample_idx[e, s]
                    if g < 0:
                        continue
                    dense = np.zeros(4, np.float32)
                    cols = b.feature_index[e]
                    m = cols >= 0
                    dense[cols[m]] = b.x[e, s, m]
                    np.testing.assert_allclose(dense, xr[g], rtol=1e-6)
                    assert ent[g] == b.entity_ids[e]

    def test_active_bounds(self):
        data, _ = make_mixed_data(n=800, n_entities=7)
        ds = RandomEffectDataset.build(
            "re", data,
            RandomEffectDatasetConfig("entityId", "re",
                                      active_data_upper_bound=20,
                                      active_data_lower_bound=5))
        for b in ds.buckets:
            per_entity = (b.sample_idx >= 0).sum(axis=1)
            assert (per_entity <= 20).all()
            assert (per_entity >= 5).all()
        # dropped + subsampled rows are passive
        n_active = sum((b.sample_idx >= 0).sum() for b in ds.buckets)
        assert n_active + len(ds.passive_sample_idx) == 800

    def test_feature_pruning(self):
        data, _ = make_mixed_data(n=300, n_entities=5)
        ds = RandomEffectDataset.build(
            "re", data,
            RandomEffectDatasetConfig("entityId", "re", max_active_features=2))
        for b in ds.buckets:
            assert ((b.feature_index >= 0).sum(axis=1) <= 2).all()

    def test_fat_cache_guard_degrades_to_streaming(self, monkeypatch,
                                                   caplog):
        """Past RE_FAT_CACHE_MAX_BYTES the build flips to upload-and-drop
        streaming (peak HBM = one bucket) with a warning, instead of
        pinning every fat tensor in HBM — the measured memory cliff
        (tools/re_scaling_probe.py). Training still works."""
        import logging

        import photon_ml_tpu.game.data as gdata

        data, _ = make_mixed_data(n=500, n_entities=11)
        monkeypatch.setattr(gdata, "RE_FAT_CACHE_MAX_BYTES", 1024)
        with caplog.at_level(logging.WARNING):
            ds = RandomEffectDataset.build(
                "re", data, RandomEffectDatasetConfig("entityId", "re"))
        assert not ds.config.cache_device_buckets
        assert any("upload-and-drop" in r.message for r in caplog.records)
        # under the cap the resident path stays on
        monkeypatch.setattr(gdata, "RE_FAT_CACHE_MAX_BYTES", 6 << 30)
        ds2 = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig("entityId", "re"))
        assert ds2.config.cache_device_buckets


class TestRandomEffectDatasetScale:
    def test_build_scales_to_many_entities(self):
        """The dataset build must stay vectorized (no per-entity Python
        loop): 300k rows / 50k entities with active bounds builds in
        seconds, not minutes — the path that has to survive the reference's
        hundreds-of-millions-of-entities regime."""
        import time

        rng = np.random.default_rng(0)
        n, d, n_entities = 300_000, 4, 50_000
        ent = rng.integers(0, n_entities, size=n)
        # 2 nnz per row keeps the synthetic build itself cheap
        rows = np.repeat(np.arange(n), 2)
        cols = rng.integers(0, d, size=2 * n).astype(np.int32)
        vals = rng.normal(size=2 * n).astype(np.float32)
        data = GameData.build(
            labels=(rng.uniform(size=n) < 0.5).astype(np.float32),
            shards={"re": FeatureShard.from_coo(rows, cols, vals, n, d)},
            id_columns={"e": ent})
        t0 = time.perf_counter()
        ds = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig(
                "e", "re", active_data_upper_bound=12,
                active_data_lower_bound=3))
        dt = time.perf_counter() - t0
        assert dt < 30.0, f"bucket build took {dt:.1f}s"
        # every row lands exactly once (active xor passive)
        n_active = sum(int((b.sample_idx >= 0).sum()) for b in ds.buckets)
        assert n_active + len(ds.passive_sample_idx) == n
        for b in ds.buckets:
            per_entity = (b.sample_idx >= 0).sum(axis=1)
            assert (per_entity <= 12).all() and (per_entity >= 3).all()


class TestRandomEffectSolver:
    def test_matches_independent_solves(self):
        """Bucketed vmapped solves == per-entity single solves."""
        data, _ = make_mixed_data(n=600, n_entities=9, d_re=4)
        ds = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig("entityId", "re"))
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=60, tolerance=1e-9))
        solver = RandomEffectSolver(task=TaskType.LOGISTIC_REGRESSION, config=cfg)
        model, scores = solver.train(
            ds, np.zeros(data.n_samples, np.float32), lam=0.5, dim=4)

        # independent reference solves on raw per-entity data
        import jax.numpy as jnp

        from photon_ml_tpu.glm.problem import OptimizationProblem
        from photon_ml_tpu.ops.design import DenseDesign
        from photon_ml_tpu.ops.losses import loss_for_task
        from photon_ml_tpu.ops.objective import GLMData, GLMObjective

        xr = data.shards["re"].to_dense()
        ent = data.id_columns["entityId"]
        problem = OptimizationProblem(
            GLMObjective(loss=loss_for_task(TaskType.LOGISTIC_REGRESSION)), cfg)
        for e in np.unique(ent):
            rows = np.flatnonzero(ent == e)
            gd = GLMData(
                design=DenseDesign(x=jnp.asarray(xr[rows])),
                labels=jnp.asarray(data.labels[rows]),
                offsets=jnp.zeros(len(rows)),
                weights=jnp.ones(len(rows)))
            ref = problem.run(gd, jnp.zeros(4), 0.5)
            got = np.zeros(4, np.float32)
            for j, v in model.entity_coefficients(int(e)).items():
                got[j] = v
            # bucket solve is f32 (production dtype); the reference solve here
            # promotes to f64 via x64 test mode — agreement is f32-limited
            np.testing.assert_allclose(got, np.asarray(ref.w), atol=2e-3)

    def test_entity_parallel_matches_single_device(self):
        """shard_map over the 'entity' mesh axis == unsharded solves.

        The EP analog of the reference sharding entities over executors
        (``RandomEffectDatasetPartitioner``): results must not depend on the
        number of devices. 37 entities over 8 devices exercises lane padding.
        """
        import jax

        from photon_ml_tpu.parallel.mesh import ENTITY_AXIS, make_mesh

        data, _ = make_mixed_data(n=900, n_entities=37, d_re=4)
        ds = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig("entityId", "re"))
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=40, tolerance=1e-8),
        )
        offsets = np.random.default_rng(3).normal(
            size=data.n_samples).astype(np.float32)

        base = RandomEffectSolver(
            task=TaskType.LOGISTIC_REGRESSION, config=cfg)
        model0, scores0 = base.train(ds, offsets, lam=0.3, dim=4)

        mesh = make_mesh({ENTITY_AXIS: 8}, devices=jax.devices())
        ep = RandomEffectSolver(
            task=TaskType.LOGISTIC_REGRESSION, config=cfg, mesh=mesh)
        model1, scores1 = ep.train(ds, offsets, lam=0.3, dim=4)

        np.testing.assert_array_equal(model0.keys, model1.keys)
        # f32 L-BFGS trajectories under different XLA partitionings diverge
        # at roundoff; same tolerance as the bucketed-vs-independent check
        np.testing.assert_allclose(model1.coeffs, model0.coeffs, atol=2e-3)
        np.testing.assert_allclose(scores1, scores0, atol=2e-3)

    def test_scores_match_model_score(self):
        data, _ = make_mixed_data(n=400, n_entities=6)
        ds = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig("entityId", "re"))
        solver = RandomEffectSolver(
            task=TaskType.LOGISTIC_REGRESSION,
            config=GLMOptimizationConfiguration(regularization=L2Regularization))
        model, scores = solver.train(
            ds, np.zeros(data.n_samples, np.float32), lam=1.0, dim=4)
        np.testing.assert_allclose(
            scores, model.score(data), rtol=1e-4, atol=1e-5)


class TestCoordinateDescent:
    def _coords(self, data, lam_f=0.01, lam_r=0.1, upper=None):
        fe_ds = FixedEffectDataset.build("global", data, "fixed")
        re_ds = RandomEffectDataset.build(
            "perEntity", data,
            RandomEffectDatasetConfig("entityId", "re",
                                      active_data_upper_bound=upper))
        cfg = GLMOptimizationConfiguration(regularization=L2Regularization)
        return {
            "global": FixedEffectCoordinate(
                coordinate_id="global", dataset=fe_ds,
                task=TaskType.LOGISTIC_REGRESSION, config=cfg, lam=lam_f),
            "perEntity": RandomEffectCoordinate(
                coordinate_id="perEntity", dataset=re_ds, data=data,
                task=TaskType.LOGISTIC_REGRESSION, config=cfg, lam=lam_r),
        }

    def test_score_accounting_invariant(self):
        data, _ = make_mixed_data(n=800, n_entities=13)
        coords = self._coords(data)
        cd = CoordinateDescent(update_sequence=["global", "perEntity"],
                               n_iterations=2)
        result = cd.run(coords, data, TaskType.LOGISTIC_REGRESSION)
        total = data.offsets + sum(result.scores.values())
        rebuilt = result.model.score(data)
        np.testing.assert_allclose(total, rebuilt, rtol=1e-3, atol=1e-4)

    def test_game_beats_fixed_only(self):
        data, _ = make_mixed_data(n=3000, n_entities=23)
        vdata, _ = make_mixed_data(n=1500, n_entities=23, seed=1)
        evaluators = parse_evaluators(["AUC", "LOGISTIC_LOSS"])
        coords = self._coords(data)
        cd = CoordinateDescent(update_sequence=["global", "perEntity"],
                               n_iterations=2)
        result = cd.run(coords, data, TaskType.LOGISTIC_REGRESSION,
                        validation=(vdata, evaluators))
        fixed_only = CoordinateDescent(update_sequence=["global"]).run(
            {"global": coords["global"]}, data, TaskType.LOGISTIC_REGRESSION,
            validation=(vdata, evaluators))
        auc_game = result.validation_history[-1]["AUC"]
        auc_fixed = fixed_only.validation_history[-1]["AUC"]
        assert auc_game > auc_fixed + 0.02, (auc_game, auc_fixed)


class TestGameEstimator:
    def test_fit_grid_and_select(self):
        data, _ = make_mixed_data(n=1200, n_entities=11)
        vdata, _ = make_mixed_data(n=600, n_entities=11, seed=3)
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={
                "global": FixedEffectCoordinateConfig(
                    feature_shard_id="fixed",
                    optimization=GLMOptimizationConfiguration(
                        regularization=L2Regularization)),
                "perEntity": RandomEffectCoordinateConfig(
                    dataset=RandomEffectDatasetConfig("entityId", "re"),
                    optimization=GLMOptimizationConfiguration(
                        regularization=L2Regularization)),
            },
            update_sequence=["global", "perEntity"],
            n_cd_iterations=2)
        grid = [
            GameOptimizationConfiguration({"global": 0.01, "perEntity": lam})
            for lam in (10.0, 0.1)
        ]
        evaluators = parse_evaluators(["AUC"])
        results = est.fit(data, grid, validation=(vdata, evaluators))
        assert len(results) == 2
        best = GameEstimator.select_best(results)
        assert best.evaluation is not None
        vals = [r.evaluation.primary[1] for r in results]
        assert best.evaluation.primary[1] == max(vals)

    def test_bf16_designs_match_f32_fit(self):
        """bfloat16 designs (fixed-effect AND random-effect buckets, wire
        included — cli --design-dtype) must track the f32 fit: same AUC to
        ~1e-3 and close coefficients. Locks the end-to-end bf16 path the
        e2e bench runs."""
        import dataclasses as dc

        data, _ = make_mixed_data(n=1500, n_entities=19)
        vdata, _ = make_mixed_data(n=800, n_entities=19, seed=7)
        cfg = GLMOptimizationConfiguration(regularization=L2Regularization)
        coords = {
            "global": FixedEffectCoordinateConfig(
                feature_shard_id="fixed", optimization=cfg),
            "perEntity": RandomEffectCoordinateConfig(
                dataset=RandomEffectDatasetConfig("entityId", "re"),
                optimization=cfg),
        }
        grid = [GameOptimizationConfiguration(
            {"global": 0.01, "perEntity": 1.0})]
        evaluators = parse_evaluators(["AUC"])

        def fit(dtype):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs={
                    cid: dc.replace(c, design_dtype=dtype)
                    for cid, c in coords.items()},
                update_sequence=["global", "perEntity"], n_cd_iterations=2)
            return est.fit(data, grid, validation=(vdata, evaluators))[0]

        r32, r16 = fit("float32"), fit("bfloat16")
        auc32 = r32.validation_history[-1]["AUC"]
        auc16 = r16.validation_history[-1]["AUC"]
        assert abs(auc32 - auc16) < 2e-3, (auc32, auc16)
        fe32 = np.asarray(
            r32.model.coordinates["global"].model.coefficients.means)
        fe16 = np.asarray(
            r16.model.coordinates["global"].model.coefficients.means)
        np.testing.assert_allclose(fe16, fe32, atol=5e-2)
        re32 = r32.model.coordinates["perEntity"]
        re16 = r16.model.coordinates["perEntity"]
        np.testing.assert_array_equal(re16.keys, re32.keys)
        # per-entity solves on few samples amplify design rounding; bound
        # the typical error, not the worst lane
        err = np.abs(np.asarray(re16.coeffs) - np.asarray(re32.coeffs))
        assert np.median(err) < 5e-2, float(np.median(err))

    def test_bf16_designs_score_parity_vs_f32(self):
        """The serving-facing half of the bf16 contract: a model FITTED
        with bfloat16 designs must SCORE (GameModel.score — the score_game
        / serving-parity core) within tolerance of the f32 fit on held-out
        data — the fit-quality assertions above can't see a scoring-path
        regression."""
        import dataclasses as dc

        data, _ = make_mixed_data(n=1500, n_entities=19)
        held_out, _ = make_mixed_data(n=600, n_entities=19, seed=13)
        cfg = GLMOptimizationConfiguration(regularization=L2Regularization)
        grid = [GameOptimizationConfiguration(
            {"global": 0.01, "perEntity": 1.0})]

        def fit(dtype):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs={
                    "global": dc.replace(
                        FixedEffectCoordinateConfig(
                            feature_shard_id="fixed", optimization=cfg),
                        design_dtype=dtype),
                    "perEntity": dc.replace(
                        RandomEffectCoordinateConfig(
                            dataset=RandomEffectDatasetConfig(
                                "entityId", "re"),
                            optimization=cfg),
                        design_dtype=dtype),
                },
                update_sequence=["global", "perEntity"], n_cd_iterations=2)
            return est.fit(data, grid)[0].model

        s32 = np.asarray(fit("float32").score(held_out))
        s16 = np.asarray(fit("bfloat16").score(held_out))
        rel = np.abs(s16 - s32) / np.maximum(np.abs(s32), 1.0)
        # design rounding perturbs every per-entity optimum a little; the
        # scored margins must still track f32 closely in the typical case
        # and stay bounded in the tail
        assert np.median(rel) < 1e-2, float(np.median(rel))
        assert rel.max() < 2e-1, float(rel.max())

    def test_fit_with_entity_mesh_matches_unsharded(self):
        """End-to-end estimator path with a 2D dp x ep mesh: the fixed
        effect shards samples over 'data' (psum'd compiled L-BFGS) and the
        random effect shards entity lanes over 'entity' — results must match
        the unsharded fit."""
        import jax

        from photon_ml_tpu.parallel.mesh import DATA_AXIS, ENTITY_AXIS, make_mesh

        data, _ = make_mixed_data(n=800, n_entities=11)

        def build(mesh, sweeps=1):
            return GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs={
                    "global": FixedEffectCoordinateConfig(
                        feature_shard_id="fixed",
                        optimization=GLMOptimizationConfiguration(
                            regularization=L2Regularization)),
                    "perEntity": RandomEffectCoordinateConfig(
                        dataset=RandomEffectDatasetConfig("entityId", "re"),
                        optimization=GLMOptimizationConfiguration(
                            regularization=L2Regularization)),
                },
                update_sequence=["global", "perEntity"],
                n_cd_iterations=sweeps, mesh=mesh)

        grid = [GameOptimizationConfiguration({"global": 0.01, "perEntity": 1.0})]
        r0 = build(None).fit(data, grid)[0]
        mesh = make_mesh({DATA_AXIS: 4, ENTITY_AXIS: 2}, devices=jax.devices())
        r1 = build(mesh).fit(data, grid)[0]
        s0 = r0.model.score(data)
        s1 = r1.model.score(data)
        np.testing.assert_allclose(s1, s0, atol=2e-3)
        fe0 = np.asarray(
            r0.model.coordinates["global"].model.coefficients.means)
        fe1 = np.asarray(
            r1.model.coordinates["global"].model.coefficients.means)
        np.testing.assert_allclose(fe1, fe0, atol=2e-3)
        # the flat-recompile contract holds on the mesh too: a warm sweep's
        # inputs (previous coefficients, replicated over the mesh) reach
        # the programs sweep 0 compiled under the cold start's placement
        from photon_ml_tpu.telemetry import profiling

        compiled = profiling.total_compiles()
        build(mesh, sweeps=2).fit(data, grid)
        assert profiling.total_compiles() == compiled

    def test_bf16_designs_on_mesh_match_unsharded_bf16(self):
        """bfloat16 designs through the DATA-SHARDED feed (shard_glm_data
        preserves the bf16 leaves; the psum'd compiled solver consumes
        them) must match the single-device bf16 fit — the sharded half of
        the --design-dtype story."""
        import dataclasses as dc

        import jax

        from photon_ml_tpu.parallel.mesh import (
            DATA_AXIS,
            ENTITY_AXIS,
            make_mesh,
        )

        data, _ = make_mixed_data(n=800, n_entities=11)
        cfg = GLMOptimizationConfiguration(regularization=L2Regularization)
        coords = {
            "global": FixedEffectCoordinateConfig(
                feature_shard_id="fixed", optimization=cfg,
                design_dtype="bfloat16"),
            "perEntity": RandomEffectCoordinateConfig(
                dataset=RandomEffectDatasetConfig("entityId", "re"),
                optimization=cfg, design_dtype="bfloat16"),
        }
        grid = [GameOptimizationConfiguration(
            {"global": 0.01, "perEntity": 1.0})]

        def fit(mesh):
            return GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs=coords,
                update_sequence=["global", "perEntity"],
                n_cd_iterations=1, mesh=mesh).fit(data, grid)[0]

        r0 = fit(None)
        mesh = make_mesh({DATA_AXIS: 4, ENTITY_AXIS: 2},
                         devices=jax.devices())
        r1 = fit(mesh)
        # identical arithmetic up to psum reassociation (bf16 designs both
        # sides; accumulation is f32)
        np.testing.assert_allclose(r1.model.score(data),
                                   r0.model.score(data), atol=5e-3)
        fe0 = np.asarray(
            r0.model.coordinates["global"].model.coefficients.means)
        fe1 = np.asarray(
            r1.model.coordinates["global"].model.coefficients.means)
        np.testing.assert_allclose(fe1, fe0, atol=5e-3)
        # the sharded design blocks must actually BE bf16 (no silent f32)
        import jax.numpy as jnp

        from photon_ml_tpu.game.data import FixedEffectDataset

        fe = FixedEffectDataset.build("global", data, "fixed", mesh=mesh,
                                      dtype=jnp.bfloat16)
        assert fe.design.x.dtype == jnp.bfloat16


def make_music_data(n=4000, d_global=6, d_item=3, n_users=25, n_songs=15,
                    n_artists=8, seed=0, param_seed=424242):
    """Yahoo!-Music-shaped data (BASELINE config 5): global features plus
    user, song, AND artist random effects; songs map many-to-one to artists."""
    prng = np.random.default_rng(param_seed)
    w = prng.normal(size=d_global).astype(np.float32)
    u_user = (1.2 * prng.normal(size=(n_users, d_item))).astype(np.float32)
    u_song = (0.8 * prng.normal(size=(n_songs, d_item))).astype(np.float32)
    u_artist = (0.6 * prng.normal(size=(n_artists, d_item))).astype(np.float32)
    song_artist = prng.integers(0, n_artists, size=n_songs)
    rng = np.random.default_rng(seed)
    xg = rng.normal(size=(n, d_global)).astype(np.float32)
    xi = rng.normal(size=(n, d_item)).astype(np.float32)
    users = rng.integers(0, n_users, size=n)
    songs = rng.integers(0, n_songs, size=n)
    artists = song_artist[songs]
    margin = (xg @ w + np.einsum("nd,nd->n", xi, u_user[users])
              + np.einsum("nd,nd->n", xi, u_song[songs])
              + np.einsum("nd,nd->n", xi, u_artist[artists]))
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)

    return GameData.build(
        labels=y,
        shards={"global": dense_shard(xg), "item": dense_shard(xi)},
        id_columns={"userId": users, "songId": songs, "artistId": artists})


class TestMultiRandomEffect:
    """BASELINE config 5: fixed effect + user + song + artist random effects
    through the full estimator (the reference's multi-coordinate GAME)."""

    def _estimator(self, update_sequence, mesh=None):
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=40))
        coords = {
            "global": FixedEffectCoordinateConfig(
                feature_shard_id="global", optimization=cfg),
            "perUser": RandomEffectCoordinateConfig(
                dataset=RandomEffectDatasetConfig("userId", "item"),
                optimization=cfg),
            "perSong": RandomEffectCoordinateConfig(
                dataset=RandomEffectDatasetConfig("songId", "item"),
                optimization=cfg),
            "perArtist": RandomEffectCoordinateConfig(
                dataset=RandomEffectDatasetConfig("artistId", "item"),
                optimization=cfg),
        }
        return GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={k: coords[k] for k in update_sequence},
            update_sequence=update_sequence, n_cd_iterations=2, mesh=mesh)

    def test_three_res_beat_one(self):
        data = make_music_data(n=4000)
        vdata = make_music_data(n=2000, seed=5)
        evaluators = parse_evaluators(["AUC"])
        lam = {"global": 0.01, "perUser": 1.0, "perSong": 1.0, "perArtist": 1.0}

        full_seq = ["global", "perUser", "perSong", "perArtist"]
        full = self._estimator(full_seq).fit(
            data, [GameOptimizationConfiguration(lam)],
            validation=(vdata, evaluators))[0]

        user_only = self._estimator(["global", "perUser"]).fit(
            data, [GameOptimizationConfiguration(lam)],
            validation=(vdata, evaluators))[0]

        auc_full = full.evaluation.primary[1]
        auc_user = user_only.evaluation.primary[1]
        assert auc_full > auc_user + 0.01, (auc_full, auc_user)
        assert auc_full > 0.75

        # score-accounting invariant across 4 coordinates
        total = data.offsets + sum(
            m.score(data) for m in full.model.coordinates.values())
        np.testing.assert_allclose(total, full.model.score(data),
                                   rtol=1e-3, atol=1e-4)

    def test_grouped_metrics_per_entity_type(self):
        """Sharded evaluators over different id columns (AUC:userId,
        AUC:songId) — the reference's MultiEvaluator on config 5."""
        data = make_music_data(n=3000)
        vdata = make_music_data(n=1500, seed=9)
        evaluators = parse_evaluators(["AUC", "AUC:userId", "AUC:songId"])
        lam = {"global": 0.01, "perUser": 1.0, "perSong": 1.0, "perArtist": 1.0}
        r = self._estimator(["global", "perUser", "perSong", "perArtist"]).fit(
            data, [GameOptimizationConfiguration(lam)],
            validation=(vdata, evaluators))[0]
        d = r.evaluation.as_dict()
        assert set(d) == {"AUC", "AUC:userId", "AUC:songId"}
        assert all(0.5 < v <= 1.0 for v in d.values()), d


class TestWideSparseFixedEffect:
    def test_csr_fixed_effect_sharded_matches_unsharded(self):
        """A wide sparse shard on the chunked path; the dp-sharded solve
        must match the unsharded one (the reference's sparse-feature fixed
        effect regime). ``dense_max_dim`` is pinned explicitly: the auto
        crossover rule (choose_dense_design) would pick DENSE at this
        (d=5000, k=10) point — 5000 < 512*10 — which is exactly its job;
        this test exists to exercise the sparse path."""
        import jax

        from photon_ml_tpu.ops.design import CsrDesign
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

        rng = np.random.default_rng(0)
        n, d, nnz_per_row = 600, 5000, 10
        rows = np.repeat(np.arange(n), nnz_per_row)
        cols = rng.integers(0, d, size=n * nnz_per_row).astype(np.int32)
        vals = rng.normal(size=n * nnz_per_row).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        data = GameData.build(
            labels=y,
            shards={"wide": FeatureShard.from_coo(rows, cols, vals, n, d)})

        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=30))

        ds0 = FixedEffectDataset.build("fe", data, "wide",
                               dense_max_dim=4096)
        from photon_ml_tpu.ops.design import ChunkedSparseDesign
        assert isinstance(ds0.design, ChunkedSparseDesign)
        c0 = FixedEffectCoordinate(
            coordinate_id="fe", dataset=ds0,
            task=TaskType.LOGISTIC_REGRESSION, config=cfg, lam=0.5)
        m0, s0 = c0.train(np.zeros(n, np.float32))

        mesh = make_mesh({DATA_AXIS: 8}, devices=jax.devices())
        ds1 = FixedEffectDataset.build("fe", data, "wide", mesh=mesh,
                               dense_max_dim=4096)
        assert ds1.n_shards == 8
        c1 = FixedEffectCoordinate(
            coordinate_id="fe", dataset=ds1,
            task=TaskType.LOGISTIC_REGRESSION, config=cfg, lam=0.5)
        m1, s1 = c1.train(np.zeros(n, np.float32))

        np.testing.assert_allclose(
            np.asarray(m1.model.coefficients.means),
            np.asarray(m0.model.coefficients.means), atol=5e-4)
        np.testing.assert_allclose(s1, s0, atol=5e-4)
        assert s1.shape == (n,)


class TestGameLinearRegression:
    def test_game_recovers_mixed_linear_model(self):
        """GAME is task-generic (the reference trains GAME with any GLM
        task): a linear-regression mixed model must recover the additive
        structure — validation RMSE near the noise floor and far below the
        fixed-only model's."""
        prng = np.random.default_rng(777)
        n, d_f, d_r, n_ent, noise = 3000, 6, 3, 15, 0.1
        w = prng.normal(size=d_f).astype(np.float32)
        u = prng.normal(size=(n_ent, d_r)).astype(np.float32)

        def make(seed):
            r = np.random.default_rng(seed)
            xf = r.normal(size=(n, d_f)).astype(np.float32)
            xr = r.normal(size=(n, d_r)).astype(np.float32)
            ent = r.integers(0, n_ent, size=n)
            y = (xf @ w + np.einsum("nd,nd->n", xr, u[ent])
                 + noise * r.normal(size=n)).astype(np.float32)
            return GameData.build(
                labels=y,
                shards={"fixed": dense_shard(xf), "re": dense_shard(xr)},
                id_columns={"entityId": ent})

        data, vdata = make(1), make(2)
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=60))
        evaluators = parse_evaluators(["RMSE"])

        def fit(seq):
            est = GameEstimator(
                task=TaskType.LINEAR_REGRESSION,
                coordinate_configs={
                    "global": FixedEffectCoordinateConfig("fixed", cfg),
                    "perEntity": RandomEffectCoordinateConfig(
                        RandomEffectDatasetConfig("entityId", "re"), cfg),
                },
                update_sequence=seq, n_cd_iterations=2)
            return est.fit(data, [GameOptimizationConfiguration(
                {"global": 1e-3, "perEntity": 0.1})],
                validation=(vdata, evaluators))[0]

        full = fit(["global", "perEntity"])
        fixed_only = fit(["global"])
        rmse_full = full.evaluation.primary[1]
        rmse_fixed = fixed_only.evaluation.primary[1]
        assert rmse_full < 0.35, rmse_full  # near the 0.1 noise floor
        assert rmse_full < 0.5 * rmse_fixed, (rmse_full, rmse_fixed)


class TestGameTaskBreadth:
    """The reference trains every task type through GAME (TaskType.scala ×
    GameEstimator); logistic and linear are covered elsewhere — these pin
    Poisson (exp link: CD's additive score accounting composes in
    log-rate space) and smoothed-hinge through the full CD path."""

    def _fit(self, task, labels_fn, evaluator, n=1200, n_ent=11, seed=3):
        kw = dict(n=n, d_fixed=5, d_re=3, n_entities=n_ent, param_seed=777,
                  labels_fn=labels_fn, effect_scale=0.8)
        data, _ = make_mixed_data(seed=seed, **kw)
        vdata, _ = make_mixed_data(seed=seed + 1, **kw)
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=60))
        evaluators = parse_evaluators([evaluator])

        def fit(seq):
            est = GameEstimator(
                task=task,
                coordinate_configs={
                    "global": FixedEffectCoordinateConfig("fixed", cfg),
                    "perEntity": RandomEffectCoordinateConfig(
                        RandomEffectDatasetConfig("entityId", "re"), cfg),
                },
                update_sequence=seq, n_cd_iterations=2)
            return est.fit(data, [GameOptimizationConfiguration(
                {"global": 1e-3, "perEntity": 0.1})],
                validation=(vdata, evaluators))[0]

        return fit(["global", "perEntity"]), fit(["global"])

    def test_poisson_game_cd(self):
        """Counts with per-entity rates: the random effect must cut the
        Poisson deviance loss vs the fixed effect alone."""
        def labels(r, margin):
            lam = np.exp(np.clip(margin, -6, 4))
            return r.poisson(lam).astype(np.float32)

        full, fixed_only = self._fit(TaskType.POISSON_REGRESSION, labels,
                                     "POISSON_LOSS")
        loss_full = full.evaluation.primary[1]
        loss_fixed = fixed_only.evaluation.primary[1]
        assert np.isfinite(loss_full)
        # sign-safe 10% margin: POISSON_LOSS (exp(m) - y*m) is negative on
        # this data, where `full < 0.9 * fixed` would tolerate degradation
        assert loss_full < loss_fixed - 0.1 * abs(loss_fixed), (
            loss_full, loss_fixed)

    def test_smoothed_hinge_game_cd(self):
        """Linear-SVM flavor: AUC through the full CD path must beat the
        fixed effect alone on mixed-effect data."""
        def labels(r, margin):
            return (r.uniform(size=len(margin))
                    < 1.0 / (1.0 + np.exp(-margin))).astype(np.float32)

        full, fixed_only = self._fit(
            TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM, labels, "AUC")
        auc_full = full.evaluation.primary[1]
        auc_fixed = fixed_only.evaluation.primary[1]
        assert auc_full > auc_fixed + 0.02, (auc_full, auc_fixed)
        assert auc_full > 0.75, auc_full


class TestGameTransformer:
    def test_transform_matches_model_score(self):
        data, _ = make_mixed_data(n=600, n_entities=9)
        est = GameEstimator(
            task=TaskType.LOGISTIC_REGRESSION,
            coordinate_configs={
                "global": FixedEffectCoordinateConfig(
                    feature_shard_id="fixed",
                    optimization=GLMOptimizationConfiguration(
                        regularization=L2Regularization)),
                "perEntity": RandomEffectCoordinateConfig(
                    dataset=RandomEffectDatasetConfig("entityId", "re"),
                    optimization=GLMOptimizationConfiguration(
                        regularization=L2Regularization)),
            },
            update_sequence=["global", "perEntity"])
        model = est.fit(data, [GameOptimizationConfiguration(
            {"global": 0.01, "perEntity": 1.0})])[0].model

        from photon_ml_tpu.game.transformer import GameTransformer

        evaluators = parse_evaluators(["AUC"])
        tf = GameTransformer(model=model, evaluators=evaluators,
                             score_breakdown=True, predict_response=True)
        out = tf.transform(data)
        np.testing.assert_allclose(out.scores, model.score(data), atol=1e-6)
        # breakdown sums (+offsets) to the total — hard-parts #6 invariant
        total = data.offsets + sum(out.by_coordinate.values())
        np.testing.assert_allclose(out.scores, total, atol=1e-5)
        # predictions = sigmoid(margin) for logistic
        np.testing.assert_allclose(
            out.predictions, 1 / (1 + np.exp(-out.scores.astype(np.float64))),
            atol=1e-6)
        assert out.evaluation is not None
        assert 0.5 < out.evaluation.primary[1] <= 1.0


class TestFactoredRandomEffect:
    def make_factored_data(self, n=2500, d_re=12, latent=3, n_entities=21,
                           seed=0):
        """Entity coefficients constrained to a shared latent subspace —
        the regime the factored coordinate is built for."""
        prng = np.random.default_rng(98765)
        p_true = prng.normal(size=(latent, d_re)).astype(np.float32)
        v_true = (1.5 * prng.normal(size=(n_entities, latent))).astype(np.float32)
        u = v_true @ p_true
        rng = np.random.default_rng(seed)
        xr = rng.normal(size=(n, d_re)).astype(np.float32)
        ent = rng.integers(0, n_entities, size=n)
        margin = np.einsum("nd,nd->n", xr, u[ent])
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(np.float32)

        return GameData.build(labels=y, shards={"re": dense_shard(xr)},
                              id_columns={"entityId": ent})

    def test_factored_design_matches_explicit_kron(self):
        import jax.numpy as jnp

        from photon_ml_tpu.game.factored import FactoredDesign

        rng = np.random.default_rng(0)
        n, d, l = 50, 6, 3
        x = rng.normal(size=(n, d)).astype(np.float32)
        v = rng.normal(size=(n, l)).astype(np.float32)
        w = rng.normal(size=(l * d,)).astype(np.float32)
        g = rng.normal(size=(n,)).astype(np.float32)
        design = FactoredDesign(x=jnp.asarray(x), v=jnp.asarray(v), latent_dim=l)
        explicit = np.einsum("nl,nd->nld", v, x).reshape(n, l * d)
        np.testing.assert_allclose(np.asarray(design.matvec(jnp.asarray(w))),
                                   explicit @ w, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(design.rmatvec(jnp.asarray(g))),
                                   explicit.T @ g, rtol=1e-4, atol=1e-4)

    def test_factored_beats_full_rank_on_low_rank_data(self):
        """With few samples per entity and low-rank truth, sharing the
        projection should out-generalize the unconstrained random effect."""
        from photon_ml_tpu.evaluation import evaluate_all
        from photon_ml_tpu.game.factored import FactoredRandomEffectCoordinate
        from photon_ml_tpu.game.projector import ProjectorType

        data = self.make_factored_data(n=2500)
        vdata = self.make_factored_data(n=1200, seed=7)
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=40))

        fact = FactoredRandomEffectCoordinate(
            coordinate_id="re", data=data,
            dataset_config=RandomEffectDatasetConfig(
                "entityId", "re", projector_type=ProjectorType.RANDOM,
                projected_dim=3),
            task=TaskType.LOGISTIC_REGRESSION, config=cfg,
            projection_config=cfg, lam=1.0, lam_projection=1.0,
            n_factored_iterations=2)
        model, scores = fact.train(np.zeros(data.n_samples, np.float32))
        assert np.isfinite(scores).all()
        # consistency: returned scores == model.score
        np.testing.assert_allclose(scores, model.score(data), atol=1e-5)

        evaluators = parse_evaluators(["AUC"])
        auc_factored = evaluate_all(
            evaluators, model.score(vdata), vdata.labels).primary[1]

        from photon_ml_tpu.game.random_effect import RandomEffectSolver

        full = RandomEffectSolver(task=TaskType.LOGISTIC_REGRESSION, config=cfg)
        ds = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig("entityId", "re"))
        fmodel, _ = full.train(ds, np.zeros(data.n_samples, np.float32),
                               lam=1.0, dim=12)
        auc_full = evaluate_all(
            evaluators, fmodel.score(vdata), vdata.labels).primary[1]
        # factored must be competitive (it matches the true low-rank model)
        assert auc_factored > auc_full - 0.01, (auc_factored, auc_full)
        assert auc_factored > 0.6


class TestDownSampling:
    def test_resamples_per_sweep(self):
        from photon_ml_tpu.sampling import BinaryClassificationDownSampler, DownSampler

        labels = np.zeros(1000, np.float32)
        weights = np.ones(1000, np.float32)
        ds = DownSampler(rate=0.5)
        w0, w1 = ds.downsample(labels, weights, 0), ds.downsample(labels, weights, 1)
        assert (w0 != w1).any()
        # unbiasedness: kept rows re-weighted 1/rate
        assert abs(w0.sum() / 1000 - 1.0) < 0.15
        bc = BinaryClassificationDownSampler(rate=0.25)
        labels[:100] = 1.0
        wb = bc.downsample(labels, weights, 0)
        np.testing.assert_array_equal(wb[:100], 1.0)  # positives kept

    def test_keyed_draw_identical_across_single_chip_and_dp_mesh(self):
        """The keyed per-global-row-id draw makes a down-sampled fixed
        effect train identically on one device and on a dp mesh (the
        stacked layout is contiguous rows, so the arange uid map agrees) —
        the invariance the multi-process equality also rests on."""
        import dataclasses as dc

        from photon_ml_tpu.game.data import FixedEffectDataset, GameData
        from photon_ml_tpu.game.coordinate import FixedEffectCoordinate
        from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
        from photon_ml_tpu.ops.regularization import L2Regularization
        from photon_ml_tpu.optimize import OptimizerConfig
        from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh
        from photon_ml_tpu.sampling import BinaryClassificationDownSampler
        from photon_ml_tpu.testing import dense_shard
        from photon_ml_tpu.types import TaskType

        rng = np.random.default_rng(3)
        n, d = 400, 6
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        game = GameData.build(labels=y, shards={"f": dense_shard(x)})
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=30))
        sampler = BinaryClassificationDownSampler(rate=0.6, seed=17)

        def fit(mesh):
            ds = FixedEffectDataset.build("c", game, "f", mesh=mesh)
            coord = FixedEffectCoordinate(
                coordinate_id="c", dataset=ds,
                task=TaskType.LOGISTIC_REGRESSION, config=cfg, lam=0.1,
                downsampler=sampler)
            model, _ = coord.train(np.zeros(n, np.float32), sweep=1)
            return np.asarray(model.model.coefficients.means)

        w1 = fit(None)
        w8 = fit(make_mesh({DATA_AXIS: 8}))
        # f32 psum reduction order differs across the mesh — ~1e-4-level
        # numerics; a kept-set mismatch would diverge at the 1e-1 level
        np.testing.assert_allclose(w1, w8, atol=2e-3, rtol=2e-3)

    def test_compact_path_disabled_in_streaming_mode(self):
        """upload-and-drop (cache_device_buckets=False) bounds peak HBM at
        ~one bucket; the compact-materialize path would pin the dense shard
        image for the dataset's lifetime, so it must stay off there."""
        from photon_ml_tpu.game.data import (
            GameData,
            RandomEffectDataset,
            RandomEffectDatasetConfig,
        )
        from photon_ml_tpu.game.random_effect import RandomEffectSolver
        from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
        from photon_ml_tpu.ops.regularization import L2Regularization
        from photon_ml_tpu.optimize import OptimizerConfig
        from photon_ml_tpu.testing import dense_shard
        from photon_ml_tpu.types import TaskType

        rng = np.random.default_rng(0)
        n = 64
        x = rng.normal(size=(n, 3)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        game = GameData.build(labels=y, shards={"re": dense_shard(x)},
                              id_columns={"e": rng.integers(0, 5, size=n)})
        solver = RandomEffectSolver(
            task=TaskType.LOGISTIC_REGRESSION,
            config=GLMOptimizationConfiguration(
                regularization=L2Regularization,
                optimizer_config=OptimizerConfig(max_iterations=5)))
        cached = RandomEffectDataset.build(
            "c", game, RandomEffectDatasetConfig("e", "re"))
        assert solver._compact_shared(cached) is not None
        streaming = RandomEffectDataset.build(
            "c", game, RandomEffectDatasetConfig(
                "e", "re", cache_device_buckets=False))
        assert solver._compact_shared(streaming) is None
        # and the streaming solve still runs end to end on the host path
        model, scores = solver.train(streaming, np.zeros(n, np.float32), 1.0)
        assert np.isfinite(np.asarray(scores)).all()
        assert np.isfinite(model.coeffs).all()


class TestEvaluatorEdgeCases:
    def test_missing_id_rows_excluded_from_grouped_metric(self):
        from photon_ml_tpu.evaluation import parse_evaluator

        rng = np.random.default_rng(0)
        scores = rng.normal(size=200)
        labels = (rng.uniform(size=200) < 0.5).astype(np.float64)
        groups = np.repeat(np.arange(10), 20)
        ev = parse_evaluator("AUC:g")
        full = ev.evaluate(scores, labels, id_tags={"g": groups})
        # adding missing-id rows must not change the metric
        scores2 = np.concatenate([scores, rng.normal(size=50)])
        labels2 = np.concatenate([labels, np.ones(50)])
        groups2 = np.concatenate([groups, np.full(50, -1)])
        withheld = ev.evaluate(scores2, labels2, id_tags={"g": groups2})
        assert abs(full - withheld) < 1e-12

    def test_precision_at_zero_rejected(self):
        from photon_ml_tpu.evaluation import parse_evaluator

        with pytest.raises(ValueError):
            parse_evaluator("PRECISION@0:queryId")


class TestHistogramBucketing:
    def test_histogram_pad_is_optimal_on_small_cases(self):
        from photon_ml_tpu.game.data import _geom_at_least, _histogram_pad

        rng = np.random.default_rng(0)
        for _trial in range(20):
            sizes = rng.integers(1, 40, size=rng.integers(3, 30))
            k = int(rng.integers(1, 5))
            pad = _histogram_pad(sizes, k)
            # validity: every size padded up, to one of ≤k boundaries
            assert (pad >= sizes).all()
            bounds = np.unique(pad)
            assert len(bounds) <= k
            # optimality vs brute force over all boundary subsets
            uniq = np.unique(sizes)
            best = None
            import itertools
            for r in range(1, min(k, len(uniq)) + 1):
                for combo in itertools.combinations(uniq.tolist(), r):
                    bs = np.array(combo)
                    if bs[-1] < uniq[-1]:
                        continue
                    p = bs[np.searchsorted(bs, sizes, side="left")]
                    cost = int(p.sum())
                    best = cost if best is None else min(best, cost)
            assert int(pad.sum()) == best

    def test_bucket_budget_validated(self):
        with pytest.raises(ValueError):
            RandomEffectDatasetConfig("e", "s", bucket_strategy="histogram",
                                      max_sample_buckets=0)

    def test_histogram_pad_quantized_path(self):
        from photon_ml_tpu.game.data import _HIST_MAX_UNIQUE, _histogram_pad

        rng = np.random.default_rng(1)
        sizes = rng.integers(1, 100_000, size=5000)
        assert len(np.unique(sizes)) > _HIST_MAX_UNIQUE
        pad = _histogram_pad(sizes, 8)
        assert (pad >= sizes).all()
        assert len(np.unique(pad)) <= 8

    def test_histogram_quantization_grid_is_bounded(self):
        """The pre-quantization grid must keep the DP's unique-size count m
        under _HIST_MAX_UNIQUE at ANY size range (a fixed 2% growth spans
        ~1000 grid points over 1..1e9); the growth is derived from the
        observed range to enforce the cap."""
        from photon_ml_tpu.game.data import (
            _HIST_MAX_UNIQUE,
            _geom_at_least,
            _histogram_pad,
        )

        rng = np.random.default_rng(2)
        # log-uniform sizes over 9 decades — the range the fixed grid missed
        sizes = np.exp(rng.uniform(0, np.log(1e9), size=20_000)).astype(
            np.int64)
        # the internal quantization formula keeps the grid under the cap
        lo = max(1, int(sizes.min()))
        growth = max(1.02,
                     (float(sizes.max()) / lo) ** (1.0 / (_HIST_MAX_UNIQUE - 1)))
        xq = _geom_at_least(sizes, growth, 1)
        assert len(np.unique(xq)) <= _HIST_MAX_UNIQUE
        assert (xq >= sizes).all()
        pad = _histogram_pad(sizes, 16)
        assert (pad >= sizes).all()
        assert len(np.unique(pad)) <= 16

    def test_histogram_dataset_matches_geometric_training(self):
        """Same solves, different padding: the trained random-effect models
        must agree (padding is masked; SURVEY.md §7 hard-parts #1)."""
        data, _ = make_mixed_data(n=900, n_entities=23)
        cfg = GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(max_iterations=60),
            regularization=L2Regularization)
        solver = RandomEffectSolver(task=TaskType.LOGISTIC_REGRESSION,
                                    config=cfg)
        offsets = np.zeros(900, np.float32)
        results = {}
        for strategy in ("geometric", "histogram"):
            ds = RandomEffectDataset.build(
                "re", data,
                RandomEffectDatasetConfig("entityId", "re",
                                          bucket_strategy=strategy))
            model, scores = solver.train(ds, offsets, lam=0.5)
            results[strategy] = (model, np.asarray(scores))
        gm, gs = results["geometric"]
        hm, hs = results["histogram"]
        np.testing.assert_array_equal(gm.keys, hm.keys)
        # padding changes fp summation order; agreement is to optimizer
        # convergence tolerance, not bitwise
        np.testing.assert_allclose(hm.coeffs, gm.coeffs, rtol=1e-2, atol=1e-3)
        np.testing.assert_allclose(hs, gs, rtol=1e-2, atol=1e-3)
        # the DP guarantee: per-dimension padded totals are minimal for
        # the shape budget, so with a budget >= geometric's shape count the
        # histogram scheme never pads a dimension more (the E*S*D product
        # is not jointly optimized and is not asserted here)
        geo = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig("entityId", "re"))
        geo_s = sorted({b.x.shape[1] for b in geo.buckets})
        geo_d = sorted({b.x.shape[2] for b in geo.buckets})
        hist = RandomEffectDataset.build(
            "re", data,
            RandomEffectDatasetConfig("entityId", "re",
                                      bucket_strategy="histogram",
                                      max_sample_buckets=len(geo_s),
                                      max_feature_buckets=len(geo_d)))
        pad_samples = lambda ds: sum(
            b.n_entities * b.x.shape[1] for b in ds.buckets)
        pad_features = lambda ds: sum(
            b.n_entities * b.x.shape[2] for b in ds.buckets)
        assert pad_samples(hist) <= pad_samples(geo)
        assert pad_features(hist) <= pad_features(geo)


class TestDevicePassiveScoring:
    def test_device_passive_matches_host_join(self):
        """Active bounds force passive rows; the cached on-device passive
        scoring must agree with the model's host searchsorted join."""
        data, _ = make_mixed_data(n=1200, n_entities=19)
        cfg = GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(max_iterations=40),
            regularization=L2Regularization)
        ds = RandomEffectDataset.build(
            "re", data,
            RandomEffectDatasetConfig("entityId", "re",
                                      active_data_upper_bound=20,
                                      active_data_lower_bound=5))
        assert len(ds.passive_sample_idx) > 0
        coord = RandomEffectCoordinate(
            "re", ds, data, TaskType.LOGISTIC_REGRESSION, cfg, lam=0.5)
        offsets = np.random.default_rng(0).normal(
            size=data.n_samples).astype(np.float32)
        # two sweeps: the second exercises the cached static join structures
        model, scores = coord.train(offsets)
        model2, scores2 = coord.train(offsets, warm_start=model)
        assert model.coeffs_device is not None
        passive = ds.passive_sample_idx
        for m, s in ((model, scores), (model2, scores2)):
            host = m.score(data, sample_idx=passive)
            np.testing.assert_allclose(np.asarray(s)[passive], host,
                                       rtol=1e-4, atol=1e-5)
        # device coefficient mirror must equal the host table
        np.testing.assert_allclose(np.asarray(model.coeffs_device),
                                   model.coeffs, rtol=1e-6)

    def test_device_warm_start_matches_host_gather(self):
        """Sweep-2 solves must be identical whether the warm start comes
        from the device coefficient mirror or the host table gather."""
        import dataclasses as dc

        data, _ = make_mixed_data(n=900, n_entities=17)
        cfg = GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(max_iterations=40),
            regularization=L2Regularization)
        ds = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig("entityId", "re"))
        solver = RandomEffectSolver(task=TaskType.LOGISTIC_REGRESSION,
                                    config=cfg)
        offsets = np.zeros(900, np.float32)
        model1, _ = solver.train(ds, offsets, lam=0.5)
        assert model1.coeffs_device is not None
        m_dev, s_dev = solver.train(ds, offsets, lam=0.5, warm_start=model1)
        host_warm = dc.replace(model1, coeffs_device=None)
        m_host, s_host = solver.train(ds, offsets, lam=0.5,
                                      warm_start=host_warm)
        np.testing.assert_allclose(m_dev.coeffs, m_host.coeffs,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(s_dev), np.asarray(s_host),
                                   rtol=1e-5, atol=1e-6)


class TestOneSweepBody:
    """Resident, streaming and projected datasets run the one sweep body
    (``random_effect._sweep_fused_impl``): all buckets a program, or a
    bucket a program."""

    @staticmethod
    def _solver(variance, **optimizer):
        from photon_ml_tpu.types import VarianceComputationType

        return RandomEffectSolver(
            task=TaskType.LOGISTIC_REGRESSION,
            config=GLMOptimizationConfiguration(
                optimizer_config=OptimizerConfig(
                    **{"max_iterations": 40, **optimizer}),
                regularization=L2Regularization,
                variance_type=VarianceComputationType[variance]))

    @staticmethod
    def _datasets(data):
        resident = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig("entityId", "re"))
        streaming = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig(
                "entityId", "re", cache_device_buckets=False))
        assert resident.config.resident and not streaming.config.resident
        assert len({b.tensor_shape[1:] for b in resident.buckets}) >= 2
        return resident, streaming

    @pytest.mark.parametrize("variance", ["NONE", "SIMPLE"])
    @pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
    def test_streaming_sweep_equals_resident_sweep(self, warm, variance):
        data, _ = make_mixed_data(n=900, n_entities=17)
        resident, streaming = self._datasets(data)
        solver = self._solver(variance)
        offsets = np.random.default_rng(5).normal(size=900).astype(
            np.float32)
        start = None
        if warm:
            start, _ = solver.train(resident, np.zeros(900, np.float32),
                                    lam=0.5, dim=4)
        m_res, s_res = solver.train(resident, offsets, lam=0.5,
                                    warm_start=start, dim=4)
        m_str, s_str = solver.train(streaming, offsets, lam=0.5,
                                    warm_start=start, dim=4)
        # a streaming sweep has pulled its buckets; a resident one has not
        assert isinstance(object.__getattribute__(m_str, "coeffs"),
                          np.ndarray)
        assert callable(object.__getattribute__(m_res, "coeffs"))
        np.testing.assert_array_equal(m_str.keys, m_res.keys)
        np.testing.assert_array_equal(m_str.coeffs, m_res.coeffs)
        np.testing.assert_array_equal(np.asarray(s_str), np.asarray(s_res))
        np.testing.assert_array_equal(np.asarray(m_str.coeffs_device),
                                      np.asarray(m_res.coeffs_device))
        np.testing.assert_array_equal(np.asarray(m_str.coeffs_device),
                                      m_str.coeffs)
        if variance == "NONE":
            assert m_str.variances is None and m_res.variances is None
        else:
            assert (m_res.variances > 0).all()
            np.testing.assert_array_equal(m_str.variances, m_res.variances)

    @pytest.mark.parametrize("resident", [True, False],
                             ids=["resident", "streaming"])
    def test_projected_warm_sweep_starts_from_the_models_lookup(
            self, resident, monkeypatch):
        """The solve is faked to hand its start back, so the sweep's model
        IS what every bucket started from: the warm model's own join, for
        the entities it has (and zero for the others)."""
        import jax.numpy as jnp

        from photon_ml_tpu.game import random_effect
        from photon_ml_tpu.game.model import RandomEffectModel
        from photon_ml_tpu.game.projector import ProjectorType

        def solve(solver, x, labels, offsets, weights, w0, lam):
            zero = jnp.zeros((), jnp.int32)
            return (w0, jnp.zeros((x.shape[0], 0), x.dtype), zero,
                    {"evaluations": zero})

        monkeypatch.setattr(random_effect, "_solve_bucket_jit", solve)
        data, _ = make_mixed_data(n=900, n_entities=17)
        ds = RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig(
                "entityId", "re", projector_type=ProjectorType.RANDOM,
                projected_dim=3, cache_device_buckets=resident))
        assert ds.projector is not None and len(ds.buckets) >= 2
        assert ds.config.resident == resident
        assert not ds.config.reads_shared_image
        entities = np.array([0, 2, 3, 5, 11, 16])
        keys = (entities[:, None] * 3 + np.arange(3)[None, :]).ravel()
        warm = RandomEffectModel(
            "entityId", "re", TaskType.LOGISTIC_REGRESSION, 3, keys,
            np.random.default_rng(1).normal(size=len(keys)).astype(
                np.float32), projector=ds.projector)
        # a configuration of this test's own: the sweep's compiled programs
        # are kept by solver, and no other test may meet the faked one
        solver = self._solver("NONE", max_iterations=39, tolerance=3e-7)
        model, _ = solver.train(ds, np.zeros(900, np.float32), lam=0.5,
                                warm_start=warm)
        started = 0
        for bucket in ds.buckets:
            ent = np.broadcast_to(bucket.entity_ids[:, None],
                                  bucket.feature_index.shape)
            want = warm.lookup(ent, bucket.feature_index)
            np.testing.assert_array_equal(
                model.lookup(ent, bucket.feature_index), want)
            started += np.count_nonzero(want)
        assert started == len(keys)

    @staticmethod
    def _scatter_sweep(solver, dataset, offsets, lam, warm, dim):
        """The plain reference of the sweep's moves, as the sweep body made
        them before it moved rows by their slots: a bucket at a time a
        gather of the offsets over the padded ``(entities, rows)`` index,
        the body's own solve and margins, and a scatter of every padded
        slot's margin into the score vector, a dead slot's at ``n`` and
        dropped."""
        import jax
        import jax.numpy as jnp

        from photon_ml_tpu.game import random_effect

        n = len(offsets)
        coeffs_warm = (solver._zero_coeffs(dataset) if warm is None
                       else warm.coeffs_device)
        inputs = []
        for k, b in enumerate(dataset.buckets):
            x, labels, weights = solver._static_arrays(dataset, k, b)
            inputs.append((
                x, labels, weights,
                jnp.asarray(np.maximum(b.sample_idx, 0)),
                jnp.asarray(np.where(b.sample_idx >= 0, b.sample_idx, n)),
                *solver._warm_ctx(dataset, k, b, warm, dim)))

        @jax.jit
        def sweep(offsets, lam, inputs, coeffs_warm):
            scores = jnp.zeros_like(offsets)
            for x, labels, weights, idx, store, pos, found in inputs:
                boff = jnp.take(offsets, idx.reshape(-1), mode="clip"
                                ).reshape(idx.shape) * (weights > 0)
                w0 = jnp.where(found, jnp.take(
                    coeffs_warm, pos.reshape(-1), mode="clip"
                ).reshape(pos.shape), 0.0).astype(jnp.float32)
                w, *_ = random_effect._solve_bucket_jit(
                    solver, x, labels, boff, weights, w0, lam)
                scores = scores.at[store].set(
                    random_effect._margins_bucket(x, w), mode="drop")
            return scores

        return np.asarray(sweep(jnp.asarray(offsets, jnp.float32),
                                jnp.asarray(lam, jnp.float32), inputs,
                                coeffs_warm))

    @staticmethod
    def _with_a_bucket_of_padding(dataset):
        """``dataset`` and one more bucket that holds entities without a
        row: dead slots only (what a mesh's pad lanes are)."""
        import dataclasses

        from photon_ml_tpu.game.data import REBucket

        e, s, d = 2, 8, dataset.buckets[0].tensor_shape[2]
        top = max(int(b.entity_ids.max()) for b in dataset.buckets)
        empty = REBucket(
            entity_ids=np.arange(top + 1, top + 1 + e, dtype=np.int64),
            x=np.zeros((e, s, d), np.float32),
            labels=np.zeros((e, s), np.float32), offsets_zero=True,
            weights=np.zeros((e, s), np.float32),
            sample_idx=np.full((e, s), -1, np.int64),
            feature_index=np.full((e, d), -1, np.int64))
        return dataclasses.replace(dataset,
                                   buckets=[*dataset.buckets, empty])

    @pytest.mark.parametrize("resident", [True, False],
                             ids=["resident", "streaming"])
    @pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
    def test_rows_moved_by_their_slots_equal_the_padded_moves(self, warm,
                                                              resident):
        """Bit for bit, with rows that no bucket holds (an entity's rows
        past the cap: score 0), dead slots in every bucket and a bucket of
        nothing else."""
        data, _ = make_mixed_data(n=900, n_entities=17)
        ds = self._with_a_bucket_of_padding(RandomEffectDataset.build(
            "re", data, RandomEffectDatasetConfig(
                "entityId", "re", active_data_upper_bound=120,
                cache_device_buckets=resident)))
        assert ds.config.resident == resident and len(ds.buckets) == 4
        held = np.concatenate([b.sample_idx[b.sample_idx >= 0]
                               for b in ds.buckets])
        assert 0 < len(held) == len(np.unique(held)) < 900
        assert all((b.sample_idx < 0).any() for b in ds.buckets)
        solver = self._solver("NONE")
        offsets = np.random.default_rng(5).normal(size=900).astype(
            np.float32)
        start = None
        if warm:
            start, _ = solver.train(ds, np.zeros(900, np.float32), lam=0.5,
                                    dim=4)
        _, scores = solver.train(ds, offsets, lam=0.5, warm_start=start,
                                 dim=4)
        scores = np.asarray(scores)
        np.testing.assert_array_equal(
            scores, self._scatter_sweep(solver, ds, offsets, 0.5, start, 4))
        unheld = np.setdiff1d(np.arange(900), held)
        assert not scores[unheld].any() and scores[held].all()

    def test_the_row_slots_are_built_once_a_dataset(self):
        """A resident dataset keeps one index of its rows' slots for its
        buckets and ``n`` and a second sweep builds none; offsets of another
        length get their own; a streaming dataset keeps none."""
        data, _ = make_mixed_data(n=900, n_entities=17)
        resident, streaming = self._datasets(data)
        solver = self._solver("NONE")
        kept = lambda ds: {k: v for k, v in ds._device_cache.items()
                           if k[0] == "rowslots"}
        zeros = np.zeros(900, np.float32)
        solver.train(resident, zeros, lam=0.5, dim=4)
        (key, first), = kept(resident).items()
        assert key == ("rowslots", (0, 1, 2), 900)
        solver.train(resident, zeros, lam=0.5, dim=4)
        assert set(kept(resident)) == {key} and kept(resident)[key] is first
        # seven rows more, which no bucket holds: their scores are zero
        _, longer = solver.train(resident, np.zeros(907, np.float32),
                                 lam=0.5, dim=4)
        assert set(kept(resident)) == {key, ("rowslots", (0, 1, 2), 907)}
        _, scores = solver.train(resident, zeros, lam=0.5, dim=4)
        np.testing.assert_array_equal(np.asarray(longer)[:900],
                                      np.asarray(scores))
        assert not np.asarray(longer)[900:].any()
        solver.train(streaming, zeros, lam=0.5, dim=4)
        assert not kept(streaming)

    def test_streaming_sweep_records_a_solve_span_a_bucket(self):
        from photon_ml_tpu.telemetry import tracing

        data, _ = make_mixed_data(n=900, n_entities=17)
        solver = self._solver("NONE")
        records = []
        remove = tracing.GLOBAL_TRACER.add_tap(records.append)
        try:
            for ds in self._datasets(data):
                with tracing.span("cd.step", coordinate=ds.coordinate_id,
                                  resident=ds.config.resident):
                    solver.train(ds, np.zeros(900, np.float32), lam=0.5,
                                 dim=4)
            tracing.flush()
        finally:
            remove()
        steps = {r["resident"]: r for r in records if r["name"] == "cd.step"}
        spans = [r for r in records if r["name"] == "game.re.solve"]
        n_buckets = len(ds.buckets)
        assert [s["bucket"] for s in spans] == 2 * list(range(n_buckets))
        per_mode = (spans[:n_buckets], spans[n_buckets:])
        for resident, mine in zip((True, False), per_mode):
            assert steps[resident]["evaluations"] == sum(
                s["evaluations"] for s in mine) > 0
        clock = ("t0", "t1", "ts", "seconds", "span_id", "parent_id")
        strip = lambda r: {k: v for k, v in r.items() if k not in clock}
        assert [strip(s) for s in per_mode[1]] \
            == [strip(s) for s in per_mode[0]]


class TestMidRunResume:
    def test_resume_from_intermediate_checkpoint_matches_uninterrupted(
            self, tmp_path):
        """Kill-and-resume equivalence: restoring from a mid-run coordinate
        boundary (scores from the incrementally-synced host mirror) and
        finishing must produce the same model as an uninterrupted run."""
        import shutil

        from photon_ml_tpu.game.coordinate_descent import CoordinateDescent
        from photon_ml_tpu.io.checkpoint import CheckpointManager

        data, _ = make_mixed_data(n=700, n_entities=13)
        cfg = GLMOptimizationConfiguration(
            optimizer_config=OptimizerConfig(max_iterations=40),
            regularization=L2Regularization)

        def build_coords():
            fe = FixedEffectDataset.build("global", data, "fixed")
            re = RandomEffectDataset.build(
                "re", data, RandomEffectDatasetConfig("entityId", "re"))
            return {
                "global": FixedEffectCoordinate(
                    "global", fe, TaskType.LOGISTIC_REGRESSION, cfg, lam=0.1),
                "re": RandomEffectCoordinate(
                    "re", re, data, TaskType.LOGISTIC_REGRESSION, cfg,
                    lam=1.0),
            }

        cd = CoordinateDescent(update_sequence=["global", "re"],
                               n_iterations=3)
        # uninterrupted run, checkpointing every coordinate boundary
        mgr = CheckpointManager(str(tmp_path / "ckpts"))
        full = cd.run(build_coords(), data, TaskType.LOGISTIC_REGRESSION,
                      checkpoint=mgr, config_fingerprint="t")
        steps = sorted(mgr.steps())
        assert steps  # retention keeps the trailing window of boundaries
        # simulate a crash right after the EARLIEST retained boundary
        # (mid-run: sweeps remain): drop every later checkpoint
        for s in steps[1:]:
            shutil.rmtree(str(tmp_path / "ckpts" / f"step-{s}"))
        assert mgr.latest_step() == steps[0]
        resumed = CoordinateDescent(
            update_sequence=["global", "re"], n_iterations=3).run(
            build_coords(), data, TaskType.LOGISTIC_REGRESSION,
            checkpoint=mgr, resume=True, config_fingerprint="t")
        # checkpoint state rounds through f32 files and the resumed path
        # re-enters warm starts from restored tables, so agreement is to
        # solver-tolerance, not bitwise
        np.testing.assert_allclose(
            np.asarray(resumed.model.coordinates["global"]
                       .model.coefficients.means),
            np.asarray(full.model.coordinates["global"]
                       .model.coefficients.means),
            rtol=5e-3, atol=1e-3)
        np.testing.assert_allclose(resumed.model.coordinates["re"].coeffs,
                                   full.model.coordinates["re"].coeffs,
                                   rtol=5e-3, atol=1e-3)
        for cid in ("global", "re"):
            np.testing.assert_allclose(resumed.scores[cid], full.scores[cid],
                                       rtol=5e-3, atol=1e-3)
