"""Prototype: data-sharded CD score vectors (an earlier roadmap's item 5).

Coordinate descent's score decomposition is device-resident but logically
unsharded: each vector is one ``(n,)`` f32 array. Past ~2-3 B samples/chip
the decomposition itself outgrows HBM (the design, at ≥8x the footprint,
hits the wall first — see ROADMAP — but the cliff needs a guard and the
sharded formulation needs a working prototype).

What this file proves on the 8-device virtual mesh:

- The random-effect sweep accepts a DATA-SHARDED residual-offset vector
  and returns a data-sharded score vector: the fused sweep constrains its
  score vector to the offsets' sharding, and the scatter of the offsets
  into the buckets' slots (entity-grouped slots against the data-sharded
  operand) and the scores' look-up among the margins are compiled by GSPMD
  with the resharding collectives (all-gather of operand / all-to-all)
  inserted automatically — no code changes in the solver, equality with
  the flat path to float tolerance.
- A full manual CD sweep (fixed + random effect) runs end-to-end with
  every score vector carrying ``P("data")`` sharding, equal to the flat
  sweep.
- The memory-cliff guard: ``CoordinateDescent.run`` refuses (loudly, with
  guidance) when the score decomposition's device footprint would exceed
  the configured fraction of device memory.

Measured overhead — a NEGATIVE result, recorded deliberately (8-device
CPU mesh, 1e6 rows, 2000 entities, chained sweeps, min of 3):
flat 1.99 s/sweep vs sharded 18.25 s/sweep = **9.2x slower**. GSPMD
satisfied the entity-grouped bucket gather by all-gathering the sharded
score vector and re-slicing after the scatter, so the sharded layout adds
collectives without removing any memory pressure: per-chip peak still
holds a full score vector transiently. CPU-mesh collective costs
overstate ICI latency, but the structural conclusion stands — sharding
the score vectors buys nothing until the bucket sample-index layout is
reorganized so gathers are shard-local (each entity's rows resident on
the shard owning its bucket lane), which is the real follow-up recorded
in ROADMAP item 5. Until then the flat layout + the memory guard below is
the right trade: the DESIGN (≥8x the bytes) hits HBM first anyway.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from photon_ml_tpu.game.coordinate import (
    FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.game.data import (
    FixedEffectDataset,
    RandomEffectDataset,
    RandomEffectDatasetConfig,
)
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.ops.regularization import L2Regularization
from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh
from photon_ml_tpu.testing import make_mixed_effect
from photon_ml_tpu.types import TaskType


@pytest.fixture(scope="module")
def problem():
    # n divisible by 8 so the flat score vector shards evenly
    game, _ = make_mixed_effect(n=4096, d_fixed=6, d_re=3, n_entities=17,
                                seed=11)
    opt = GLMOptimizationConfiguration(
        regularization=L2Regularization,
        optimizer_config=OptimizerConfig(max_iterations=30))
    return game, opt


def _data_sharded(x, mesh):
    return jax.device_put(jnp.asarray(x, jnp.float32),
                          NamedSharding(mesh, P(DATA_AXIS)))


class TestShardedScoreVectors:
    def test_re_train_accepts_sharded_offsets(self, problem):
        game, opt = problem
        mesh = make_mesh({DATA_AXIS: 8})
        ds = RandomEffectDataset.build(
            "perEntity", game, RandomEffectDatasetConfig("entityId", "re"))
        coord = RandomEffectCoordinate(
            coordinate_id="perEntity", dataset=ds, data=game,
            task=TaskType.LOGISTIC_REGRESSION, config=opt, lam=0.5)
        residual = np.random.default_rng(0).normal(
            size=game.n_samples).astype(np.float32)

        model_flat, scores_flat = coord.train(residual)
        ds.clear_device_cache()  # fresh joins for the sharded run
        model_sh, scores_sh = coord.train(_data_sharded(residual, mesh))

        np.testing.assert_allclose(np.asarray(scores_sh),
                                   np.asarray(scores_flat), atol=1e-5)
        np.testing.assert_allclose(model_sh.coeffs, model_flat.coeffs,
                                   atol=1e-6)
        # the returned score vector must carry the data sharding (inherited
        # through the fused sweep) — not a silent full replication
        spec = scores_sh.sharding.spec
        assert tuple(spec) and spec[0] == DATA_AXIS, spec

    def test_manual_cd_sweep_sharded_equals_flat(self, problem):
        game, opt = problem
        mesh = make_mesh({DATA_AXIS: 8})
        n = game.n_samples
        fe = FixedEffectDataset.build("global", game, "fixed", mesh=mesh)
        re_ds = RandomEffectDataset.build(
            "perEntity", game, RandomEffectDatasetConfig("entityId", "re"))
        fe_coord = FixedEffectCoordinate(
            coordinate_id="global", dataset=fe,
            task=TaskType.LOGISTIC_REGRESSION, config=opt, lam=1e-3)
        re_coord = RandomEffectCoordinate(
            coordinate_id="perEntity", dataset=re_ds, data=game,
            task=TaskType.LOGISTIC_REGRESSION, config=opt, lam=0.5)

        def sweep(make_vec):
            total = make_vec(game.offsets)
            scores = {"global": make_vec(np.zeros(n, np.float32)),
                      "perEntity": make_vec(np.zeros(n, np.float32))}
            models = {}
            for cid, coord in (("global", fe_coord),
                               ("perEntity", re_coord)):
                residual = total - scores[cid]
                model, new_scores = coord.train(residual)
                models[cid] = model
                total = residual + new_scores
                scores[cid] = new_scores
            return models, scores, total

        models_f, scores_f, total_f = sweep(
            lambda x: jnp.asarray(x, jnp.float32))
        re_ds.clear_device_cache()
        models_s, scores_s, total_s = sweep(
            lambda x: _data_sharded(x, mesh))

        np.testing.assert_allclose(np.asarray(total_s),
                                   np.asarray(total_f), atol=1e-4)
        for cid in scores_f:
            np.testing.assert_allclose(np.asarray(scores_s[cid]),
                                       np.asarray(scores_f[cid]), atol=1e-4)
        w_f = np.asarray(
            models_f["global"].model.coefficients.means)
        w_s = np.asarray(
            models_s["global"].model.coefficients.means)
        np.testing.assert_allclose(w_s, w_f, atol=1e-5)


class TestScoreMemoryGuard:
    def test_guard_triggers_above_budget(self, problem):
        from photon_ml_tpu.game.coordinate_descent import CoordinateDescent

        game, opt = problem
        ds = RandomEffectDataset.build(
            "perEntity", game, RandomEffectDatasetConfig("entityId", "re"))
        coord = RandomEffectCoordinate(
            coordinate_id="perEntity", dataset=ds, data=game,
            task=TaskType.LOGISTIC_REGRESSION, config=opt, lam=0.5)
        cd = CoordinateDescent(update_sequence=["perEntity"],
                               n_iterations=1,
                               max_score_memory_bytes=1024)  # absurdly small
        with pytest.raises(ValueError, match="score decomposition"):
            cd.run({"perEntity": coord}, game,
                   TaskType.LOGISTIC_REGRESSION)

    def test_guard_quiet_at_normal_scale(self, problem):
        from photon_ml_tpu.game.coordinate_descent import CoordinateDescent

        game, opt = problem
        ds = RandomEffectDataset.build(
            "perEntity", game, RandomEffectDatasetConfig("entityId", "re"))
        coord = RandomEffectCoordinate(
            coordinate_id="perEntity", dataset=ds, data=game,
            task=TaskType.LOGISTIC_REGRESSION, config=opt, lam=0.5)
        cd = CoordinateDescent(update_sequence=["perEntity"], n_iterations=1)
        result = cd.run({"perEntity": coord}, game,
                        TaskType.LOGISTIC_REGRESSION)
        assert np.isfinite(result.scores["perEntity"]).all()
