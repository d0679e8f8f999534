"""Sharded fixed-effect tests (SURVEY.md §7 stage 4): the shard_map/psum
objective must agree with the single-device objective to float64 precision on
a simulated 8-device CPU mesh — the moral equivalent of the reference's
Spark local[*] integration tests of ``DistributedGLMLossFunction``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

from photon_ml_tpu.glm import GLMOptimizationConfiguration
from photon_ml_tpu.ops.design import CsrDesign, DenseDesign
from photon_ml_tpu.ops.losses import LogisticLoss
from photon_ml_tpu.ops.objective import GLMData, GLMObjective
from photon_ml_tpu.optimize import OptimizerConfig, minimize_lbfgs, minimize_tron
from photon_ml_tpu.parallel import (
    DistributedGLMObjective,
    make_mesh,
    shard_glm_data,
)


def make_data(n=203, d=17, seed=0, sparse=False):
    """n deliberately NOT divisible by 8 to exercise tail padding."""
    rng = np.random.default_rng(seed)
    if sparse:
        m = sp.random(n, d, density=0.3, random_state=int(seed), format="csr")
        design = CsrDesign.from_scipy(m)
        x = m.toarray()
    else:
        x = rng.normal(size=(n, d))
        design = DenseDesign(x=jnp.asarray(x))
    labels = (rng.uniform(size=n) < 0.5).astype(np.float64)
    offsets = rng.normal(size=n) * 0.1
    weights = rng.uniform(0.5, 2.0, size=n)
    return GLMData(design=design, labels=jnp.asarray(labels),
                   offsets=jnp.asarray(offsets), weights=jnp.asarray(weights)), x


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() >= 8, "conftest must provide 8 virtual devices"
    return make_mesh({"data": 8})


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "csr"])
class TestDistributedObjective:
    def test_value_grad_hvp_match_local(self, mesh, sparse):
        data, _ = make_data(sparse=sparse)
        obj = GLMObjective(loss=LogisticLoss)
        dist = DistributedGLMObjective(obj, mesh)
        sharded = shard_glm_data(data, 8, device_put_mesh=mesh)

        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.normal(size=data.dim))
        v = jnp.asarray(rng.normal(size=data.dim))
        l2 = 0.7

        f_local, g_local = obj.value_and_grad(w, data, l2)
        f_dist, g_dist = dist.value_and_grad(w, sharded, l2)
        np.testing.assert_allclose(float(f_dist), float(f_local), rtol=1e-12)
        np.testing.assert_allclose(np.asarray(g_dist), np.asarray(g_local),
                                   rtol=1e-10, atol=1e-12)

        hv_local = obj.hvp(w, v, data, l2)
        hv_dist = dist.hvp(w, v, sharded, l2)
        np.testing.assert_allclose(np.asarray(hv_dist), np.asarray(hv_local),
                                   rtol=1e-10, atol=1e-12)

    def test_reg_mask_counted_once(self, mesh, sparse):
        data, _ = make_data(sparse=sparse)
        mask = jnp.ones(data.dim).at[0].set(0.0)
        obj = GLMObjective(loss=LogisticLoss, reg_mask=mask)
        dist = DistributedGLMObjective(obj, mesh)
        sharded = shard_glm_data(data, 8, device_put_mesh=mesh)
        w = jnp.asarray(np.random.default_rng(2).normal(size=data.dim))
        f_local, g_local = obj.value_and_grad(w, data, 2.0)
        f_dist, g_dist = dist.value_and_grad(w, sharded, 2.0)
        np.testing.assert_allclose(float(f_dist), float(f_local), rtol=1e-12)
        np.testing.assert_allclose(np.asarray(g_dist), np.asarray(g_local),
                                   rtol=1e-10, atol=1e-12)


class TestDistributedSolve:
    def test_lbfgs_solution_matches_single_device(self, mesh):
        data, _ = make_data(seed=3)
        obj = GLMObjective(loss=LogisticLoss)
        dist = DistributedGLMObjective(obj, mesh)
        sharded = shard_glm_data(data, 8, device_put_mesh=mesh)
        cfg = OptimizerConfig(max_iterations=200, tolerance=1e-10)
        w0 = jnp.zeros(data.dim)
        l2 = 0.5

        res_local = jax.jit(lambda w: minimize_lbfgs(
            lambda wv: obj.value_and_grad(wv, data, l2), w, cfg))(w0)
        res_dist = jax.jit(lambda w: minimize_lbfgs(
            lambda wv: dist.value_and_grad(wv, sharded, l2), w, cfg))(w0)
        np.testing.assert_allclose(np.asarray(res_dist.w), np.asarray(res_local.w),
                                   atol=1e-8)

    def test_tron_whole_pod_single_program(self, mesh):
        """TRON's nested TR/CG loops with psum'd Hvp compile into one XLA
        program over the mesh — the reference's per-CG-step treeAggregate
        round-trips collapse into on-device collectives."""
        data, _ = make_data(seed=4)
        obj = GLMObjective(loss=LogisticLoss)
        dist = DistributedGLMObjective(obj, mesh)
        sharded = shard_glm_data(data, 8, device_put_mesh=mesh)
        cfg = OptimizerConfig(max_iterations=100, tolerance=1e-10)
        l2 = 0.5
        res_local = jax.jit(lambda w: minimize_tron(
            lambda wv: obj.value_and_grad(wv, data, l2),
            lambda wv, v: obj.hvp(wv, v, data, l2), w, cfg))(jnp.zeros(data.dim))
        res_dist = jax.jit(lambda w: minimize_tron(
            lambda wv: dist.value_and_grad(wv, sharded, l2),
            lambda wv, v: dist.hvp(wv, v, sharded, l2), w, cfg))(jnp.zeros(data.dim))
        np.testing.assert_allclose(np.asarray(res_dist.w), np.asarray(res_local.w),
                                   atol=1e-8)

    def test_owlqn_elastic_net_matches_single_device(self, mesh):
        """OWL-QN (L1) over the psum'd objective == unsharded: the orthant
        projection happens on the replicated w, so sharding must not change
        the sparsity pattern (BASELINE config 2, distributed)."""
        from photon_ml_tpu.optimize import minimize_owlqn

        data, _ = make_data(seed=9)
        obj = GLMObjective(loss=LogisticLoss)
        dist = DistributedGLMObjective(obj, mesh)
        sharded = shard_glm_data(data, 8, device_put_mesh=mesh)
        cfg = OptimizerConfig(max_iterations=200, tolerance=1e-10)
        l1, l2 = 0.4, 0.2
        res_local = jax.jit(lambda w: minimize_owlqn(
            lambda wv: obj.value_and_grad(wv, data, l2), w, l1, cfg))(
                jnp.zeros(data.dim))
        res_dist = jax.jit(lambda w: minimize_owlqn(
            lambda wv: dist.value_and_grad(wv, sharded, l2), w, l1, cfg))(
                jnp.zeros(data.dim))
        np.testing.assert_allclose(np.asarray(res_dist.w),
                                   np.asarray(res_local.w), atol=1e-6)
        # identical support (L1 zero pattern)
        np.testing.assert_array_equal(np.asarray(res_dist.w) == 0.0,
                                      np.asarray(res_local.w) == 0.0)

    def test_variance_matches_single_device(self, mesh):
        """SIMPLE/FULL variance through the psum'd Hessian contractions."""
        data, _ = make_data(seed=10)
        obj = GLMObjective(loss=LogisticLoss)
        dist = DistributedGLMObjective(obj, mesh)
        sharded = shard_glm_data(data, 8, device_put_mesh=mesh)
        w = jnp.asarray(np.random.default_rng(11).normal(size=data.dim))
        np.testing.assert_allclose(
            np.asarray(dist.hessian_diagonal(w, sharded, 0.3)),
            np.asarray(obj.hessian_diagonal(w, data, 0.3)), rtol=1e-10)
        np.testing.assert_allclose(
            np.asarray(dist.hessian_matrix(w, sharded, 0.3)),
            np.asarray(obj.hessian_matrix(w, data, 0.3)), rtol=1e-10)

    def test_deterministic_across_runs(self, mesh):
        """SURVEY §5.2: the psum reduction is bitwise deterministic —
        repeated evaluation of the same sharded objective produces identical
        bits (the reproducibility property Spark's treeAggregate also has
        for a fixed partitioning)."""
        data, _ = make_data(seed=12)
        obj = GLMObjective(loss=LogisticLoss)
        dist = DistributedGLMObjective(obj, mesh)
        sharded = shard_glm_data(data, 8, device_put_mesh=mesh)
        w = jnp.asarray(np.random.default_rng(13).normal(size=data.dim))
        f1, g1 = dist.value_and_grad(w, sharded, 0.5)
        f2, g2 = dist.value_and_grad(w, sharded, 0.5)
        assert float(f1) == float(f2)
        np.testing.assert_array_equal(np.asarray(g1), np.asarray(g2))

    def test_margins_roundtrip(self, mesh):
        data, x = make_data(seed=5)
        obj = GLMObjective(loss=LogisticLoss)
        dist = DistributedGLMObjective(obj, mesh)
        sharded = shard_glm_data(data, 8, device_put_mesh=mesh)
        w = jnp.asarray(np.random.default_rng(6).normal(size=data.dim))
        m = np.asarray(dist.margins(w, sharded)).reshape(-1)[:data.n_samples]
        np.testing.assert_allclose(m, np.asarray(obj.margins(w, data)), rtol=1e-10)


@pytest.fixture(scope="module")
def feature_mesh():
    from photon_ml_tpu.parallel import FEATURE_AXIS, make_mesh

    assert jax.device_count() >= 8
    return make_mesh({FEATURE_AXIS: 8})


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "csr"])
class TestFeatureShardedObjective:
    """TP sharding of the coefficient dim (SURVEY.md §2.10 TP row): every
    quantity must match the unsharded objective. d=17 over 8 devices
    exercises feature-dim padding (d_pad=24, 7 dead columns)."""

    def test_value_grad_hvp_match_local(self, feature_mesh, sparse):
        from photon_ml_tpu.parallel import (
            FeatureShardedGLMObjective,
            shard_glm_data_features,
        )

        data, _ = make_data(sparse=sparse)
        obj = GLMObjective(loss=LogisticLoss)
        tp = FeatureShardedGLMObjective(obj, feature_mesh)
        sharded, d_pad = shard_glm_data_features(
            data, 8, device_put_mesh=feature_mesh)
        assert d_pad == 24

        rng = np.random.default_rng(7)
        w = jnp.asarray(np.concatenate(
            [rng.normal(size=data.dim), np.zeros(d_pad - data.dim)]))
        v = jnp.asarray(np.concatenate(
            [rng.normal(size=data.dim), np.zeros(d_pad - data.dim)]))
        l2 = 0.7

        f_local, g_local = obj.value_and_grad(w[:data.dim], data, l2)
        f_tp, g_tp = tp.value_and_grad(w, sharded, l2)
        np.testing.assert_allclose(float(f_tp), float(f_local), rtol=1e-12)
        np.testing.assert_allclose(np.asarray(g_tp)[:data.dim],
                                   np.asarray(g_local), rtol=1e-10, atol=1e-12)
        # padded columns: zero data, zero w → gradient exactly 0
        np.testing.assert_array_equal(np.asarray(g_tp)[data.dim:], 0.0)

        hv_local = obj.hvp(w[:data.dim], v[:data.dim], data, l2)
        hv_tp = tp.hvp(w, v, sharded, l2)
        np.testing.assert_allclose(np.asarray(hv_tp)[:data.dim],
                                   np.asarray(hv_local), rtol=1e-10, atol=1e-12)

        m_tp = np.asarray(tp.margins(w, sharded))
        np.testing.assert_allclose(m_tp, np.asarray(obj.margins(w[:data.dim], data)),
                                   rtol=1e-10)

    def test_tron_solve_matches_single_device(self, feature_mesh, sparse):
        """TRON's TR/CG loops over the feature-sharded objective: the
        closed-form block Hvp must drive the same solution as unsharded."""
        from photon_ml_tpu.parallel import (
            FeatureShardedGLMObjective,
            shard_glm_data_features,
        )

        data, _ = make_data(seed=21, sparse=sparse)
        obj = GLMObjective(loss=LogisticLoss)
        tp = FeatureShardedGLMObjective(obj, feature_mesh)
        sharded, d_pad = shard_glm_data_features(
            data, 8, device_put_mesh=feature_mesh)
        cfg = OptimizerConfig(max_iterations=100, tolerance=1e-10)
        l2 = 0.5
        res_local = jax.jit(lambda w: minimize_tron(
            lambda wv: obj.value_and_grad(wv, data, l2),
            lambda wv, v: obj.hvp(wv, v, data, l2), w, cfg))(
                jnp.zeros(data.dim))
        res_tp = jax.jit(lambda w: minimize_tron(
            lambda wv: tp.value_and_grad(wv, sharded, l2),
            lambda wv, v: tp.hvp(wv, v, sharded, l2), w, cfg))(
                jnp.zeros(d_pad))
        np.testing.assert_allclose(np.asarray(res_tp.w)[:data.dim],
                                   np.asarray(res_local.w), atol=1e-6)

    def test_lbfgs_solve_matches_single_device(self, feature_mesh, sparse):
        from photon_ml_tpu.parallel import (
            FeatureShardedGLMObjective,
            shard_glm_data_features,
        )

        data, _ = make_data(seed=8, sparse=sparse)
        obj = GLMObjective(loss=LogisticLoss)
        tp = FeatureShardedGLMObjective(obj, feature_mesh)
        sharded, d_pad = shard_glm_data_features(
            data, 8, device_put_mesh=feature_mesh)
        cfg = OptimizerConfig(max_iterations=200, tolerance=1e-10)
        l2 = 0.5
        res_local = jax.jit(lambda w: minimize_lbfgs(
            lambda wv: obj.value_and_grad(wv, data, l2), w, cfg))(
                jnp.zeros(data.dim))
        res_tp = jax.jit(lambda w: minimize_lbfgs(
            lambda wv: tp.value_and_grad(wv, sharded, l2), w, cfg))(
                jnp.zeros(d_pad))
        # both runs stop at the shared optimum, but stall termination may
        # trigger an iteration apart — compare at solver, not fp, precision
        np.testing.assert_allclose(np.asarray(res_tp.w)[:data.dim],
                                   np.asarray(res_local.w), atol=1e-6)
        np.testing.assert_array_equal(np.asarray(res_tp.w)[data.dim:], 0.0)


def test_fused_kernel_under_shard_map_interpret():
    """The fused Pallas value+grad kernel must run inside a shard_map body
    (its out_shapes carry the block's vma) and match the closed form — the
    dp fixed-effect path now enables it on TPU."""
    import jax
    import jax.numpy as jnp

    from photon_ml_tpu.ops.design import DenseDesign
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.objective import GLMData, GLMObjective
    from photon_ml_tpu.parallel.distributed import (
        DistributedGLMObjective,
        shard_glm_data,
    )
    from photon_ml_tpu.parallel.mesh import DATA_AXIS, make_mesh

    rng = np.random.default_rng(0)
    n, d = 128, 16
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    host = GLMData(design=DenseDesign(x=jnp.asarray(x)),
                   labels=jnp.asarray(y),
                   offsets=jnp.zeros(n, jnp.float32),
                   weights=jnp.ones(n, jnp.float32))
    mesh = make_mesh({DATA_AXIS: 8})
    sharded = shard_glm_data(host, 8, device_put_mesh=mesh)
    w = jnp.asarray(rng.normal(size=d), jnp.float32)

    ref = DistributedGLMObjective(
        objective=GLMObjective(LogisticLoss), mesh=mesh)
    v0, g0 = ref.value_and_grad(w, sharded, 0.3)

    # The Pallas HLO *interpreter* can't propagate vma through its internal
    # dynamic_slices (the real Mosaic lowering on TPU can — validated
    # on-chip through a mesh), so the interpret-mode check wraps its own
    # shard_map with check_vma=False around the fused objective.
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    fused_obj = GLMObjective(LogisticLoss, fused=True, fused_interpret=True)

    def body(wv, blk):
        data = jax.tree.map(lambda a: a[0], blk)
        val, grad = fused_obj.value_and_grad(wv, data, 0.0)
        return (jax.lax.psum(val, DATA_AXIS) + 0.5 * 0.3 * jnp.vdot(wv, wv),
                jax.lax.psum(grad, DATA_AXIS) + 0.3 * wv)

    v1, g1 = shard_map(body, mesh=mesh, in_specs=(P(), P(DATA_AXIS)),
                       out_specs=(P(), P()), check_vma=False)(w, sharded)
    np.testing.assert_allclose(float(v1), float(v0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g0),
                               rtol=1e-4, atol=1e-5)
