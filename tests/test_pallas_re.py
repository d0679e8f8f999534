"""Fused Pallas random-effect sweep kernel (ops/pallas_re.py) tests.

CPU runs the kernel through the Pallas interpreter (``fused_interpret`` —
the same opt-in the pallas_glm tests use); the TPU speedup claim lives in
the ``-m slow`` lane. The load-bearing contracts:

- **kernel correctness**: single-pass (values, grads) match the closed
  form per entity, f32 and bf16 designs, ragged weight-0 padding included,
  and no lane's depend on its neighbours in the block;
- **engagement**: ``RandomEffectSolver(fused=True, fused_interpret=True)``
  trains through the kernel (the flat loop's lanes-last evaluation) and lands
  within tolerance of the XLA ``_solve_bucket`` path — and with
  ``fused=True`` but NO interpreter on CPU the gate is inert, producing
  BIT-identical output to ``fused=False`` (the default-flip safety net);
- **determinism**: the fused f32 path is bit-identical run to run;
- **flat recompiles**: a second fused sweep adds zero
  ``game.re.sweep_fused`` compiles;
- **solver pre-pad**: entity counts that don't divide the block plan
  solve correctly (the padded lanes are weight-0 and sliced off).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.game.data import (
    GameData,
    RandomEffectDataset,
    RandomEffectDatasetConfig,
)
from photon_ml_tpu.game.random_effect import RandomEffectSolver
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.ops import pallas_re
from photon_ml_tpu.ops.losses import LogisticLoss
from photon_ml_tpu.ops.regularization import L2Regularization
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.testing import dense_shard
from photon_ml_tpu.types import TaskType


def _ref_value_and_grad(x, w, y, off, wt):
    """NumPy single-entity logistic closed form (f64)."""
    m = x.astype(np.float64) @ w.astype(np.float64) + off
    lvec = np.logaddexp(0.0, m) - y * m
    p = 1.0 / (1.0 + np.exp(-m))
    dl = wt * (p - y)
    return (wt * lvec).sum(), dl @ x.astype(np.float64)


def _batch(e, s, d, seed=0, dtype=np.float32, dead_frac=0.3):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(e, s, d)).astype(dtype)
    w = rng.normal(size=(e, d)).astype(np.float32)
    y = (rng.uniform(size=(e, s)) < 0.5).astype(np.float32)
    off = rng.normal(size=(e, s)).astype(np.float32)
    wt = (rng.uniform(size=(e, s)) > dead_frac).astype(np.float32)
    # weight-0 rows must also carry zero data for the ref to agree exactly
    x = x * wt[:, :, None].astype(dtype)
    off = off * wt
    return x, w, y, off, wt


def _kernel(x, w, y, off, wt):
    return pallas_re.fused_entity_value_and_grad(
        LogisticLoss, jnp.asarray(x), jnp.asarray(w), jnp.asarray(y),
        jnp.asarray(off), jnp.asarray(wt), interpret=True)


class TestKernel:
    # entity counts under one 128-lane block, and over it by no multiple
    @pytest.mark.parametrize("e,s,d", [(13, 11, 5), (8, 16, 4), (40, 7, 3),
                                       (1, 5, 2), (130, 9, 3), (300, 8, 8)])
    def test_matches_closed_form_f32(self, e, s, d):
        x, w, y, off, wt = _batch(e, s, d, seed=e)
        vals, grads = _kernel(x, w, y, off, wt)
        assert vals.shape == (e,) and grads.shape == (e, d)
        for i in range(e):
            rv, rg = _ref_value_and_grad(x[i], w[i], y[i], off[i], wt[i])
            np.testing.assert_allclose(float(vals[i]), rv, rtol=1e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(grads[i]), rg, rtol=1e-4,
                                       atol=1e-5)

    @pytest.mark.parametrize("e,s,d", [(10, 9, 6), (131, 33, 4)])
    def test_bf16_design_accumulates_f32(self, e, s, d):
        xf, w, y, off, wt = _batch(e, s, d, seed=3)
        vals, grads = _kernel(jnp.asarray(xf, jnp.bfloat16), w, y, off, wt)
        assert vals.dtype == jnp.float32 and grads.dtype == jnp.float32
        x16 = np.asarray(jnp.asarray(xf, jnp.bfloat16).astype(jnp.float32))
        for i in range(e):
            # reference on the ROUNDED design: only the storage is bf16
            rv, rg = _ref_value_and_grad(x16[i], w[i], y[i], off[i], wt[i])
            np.testing.assert_allclose(float(vals[i]), rv, rtol=1e-3,
                                       atol=1e-3)
            np.testing.assert_allclose(np.asarray(grads[i]), rg, rtol=1e-2,
                                       atol=1e-3)

    def test_all_dead_entity_is_zero(self):
        x, w, y, off, wt = _batch(6, 5, 3, seed=9)
        wt[2] = 0.0
        x[2] = 0.0
        vals, grads = _kernel(x, w, y, off, wt)
        assert float(vals[2]) == 0.0
        assert not np.asarray(grads[2]).any()

    def test_a_lane_of_one_real_row(self):
        x, w, y, off, wt = _batch(6, 19, 3, seed=4, dead_frac=0.0)
        wt[3, 1:] = 0.0
        x[3, 1:] = 0.0
        off[3, 1:] = 0.0
        vals, grads = _kernel(x, w, y, off, wt)
        rv, rg = _ref_value_and_grad(x[3, :1], w[3], y[3, :1], off[3, :1],
                                     wt[3, :1])
        np.testing.assert_allclose(float(vals[3]), rv, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(grads[3]), rg, rtol=1e-5,
                                   atol=1e-7)

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_a_lane_does_not_depend_on_its_neighbours(self, dtype):
        """Bit for bit: a lane in a batch of 300 (its third block of 128
        lanes, among lanes of other data) against the lane alone (lane 0 of
        a block whose other lanes are padding). The entities are the
        kernel's last axis and every operation on it is elementwise."""
        x, w, y, off, wt = _batch(300, 21, 5, seed=2)
        x = np.asarray(jnp.asarray(x, dtype))
        vals, grads = _kernel(x, w, y, off, wt)
        for i in (0, 127, 128, 299):
            one = slice(i, i + 1)
            v1, g1 = _kernel(x[one], w[one], y[one], off[one], wt[one])
            assert np.array_equal(np.asarray(v1), np.asarray(vals[one]))
            assert np.array_equal(np.asarray(g1), np.asarray(grads[one]))

    def test_operands_not_laid_out_are_refused(self):
        """The lanes-last entry takes ``entity_layout``'s operands, whose
        width is the block plan's: it copies nothing, so it pads nothing."""
        x, w, y, off, wt = _batch(13, 11, 5)
        laid = pallas_re.entity_layout(*(jnp.asarray(a)
                                         for a in (x, y, off, wt)))
        assert laid[0].shape == (5, 16, 128) and laid[1].shape == (16, 128)
        with pytest.raises(ValueError, match="not entity_layout's"):
            pallas_re.entity_value_and_grad_lanes(
                LogisticLoss, *(a[..., :100] for a in laid),
                jnp.zeros((5, 100)), interpret=True)
        # coefficients for the bucket's 13 lanes alone are taken as they are
        vals, grads = pallas_re.entity_value_and_grad_lanes(
            LogisticLoss, *laid, jnp.asarray(w).T, interpret=True)
        assert vals.shape == (13,) and grads.shape == (5, 13)


class TestPlan:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("e,s,d", [(13, 11, 5), (1000, 64, 8), (7, 3, 1),
                                       (8, 200, 40), (168301, 56, 8),
                                       (56309, 141, 8)])
    def test_plan_idempotent_on_its_own_padding(self, e, s, d, dtype):
        plan = pallas_re.entity_plan(e, s, d, dtype)
        assert plan is not None
        be, e_pad = plan
        assert be % pallas_re.ENTITY_TILE == 0 and pallas_re.ENTITY_TILE == 128
        assert e_pad % be == 0 and e <= e_pad < e + be
        assert pallas_re.entity_plan(e_pad, s, d, dtype) == plan
        assert be * pallas_re._entity_bytes(s, d, dtype) \
            + pallas_re._body_bytes(d, dtype) \
            <= pallas_re.VMEM_BUDGET_BYTES < 16 << 20

    def test_oversized_lane_is_ineligible(self):
        # 128 entities' padded slabs alone exceed the block budget
        assert pallas_re.entity_plan(100, 2048, 256, jnp.float32) is None
        assert not pallas_re.lane_fits_vmem(2048, 256, jnp.float32)
        assert pallas_re.entity_pad(100, 2048, 256, jnp.float32) == 0

    @pytest.mark.parametrize("s,fits", [(56, True), (141, True),
                                        (1096, True), (1104, False),
                                        (2151, False)])
    def test_the_gate_divides_lanes_of_8_columns_by_their_rows(self, s, fits):
        """At a block of 128 lanes the longest float32 lane of 8 columns is
        1,096 rows: the benchmark cell's buckets of 56 and 141 rows run the
        kernel, those of 1,104 and longer keep the closed form, as before
        the entities went last (PERF.md, PR 31)."""
        assert pallas_re.lane_fits_vmem(s, 8, jnp.float32) == fits

    def test_pad_matches_plan(self):
        for (e, s, d) in [(13, 11, 5), (64, 16, 4)]:
            pad = pallas_re.entity_pad(e, s, d, jnp.float32)
            _, e_pad = pallas_re.entity_plan(e, s, d, jnp.float32)
            assert e + pad == e_pad


class TestCustomVmap:
    def test_all_batched_vmap_dispatches_kernel(self):
        e, s, d = 12, 10, 4
        x, w, y, off, wt = _batch(e, s, d, seed=5)
        vag = pallas_re.vmappable_entity_value_and_grad(LogisticLoss, True)
        vals_v, grads_v = jax.vmap(vag)(
            jnp.asarray(x), jnp.asarray(w), jnp.asarray(y),
            jnp.asarray(off), jnp.asarray(wt))
        vals_k, grads_k = _kernel(x, w, y, off, wt)
        assert np.array_equal(np.asarray(vals_v), np.asarray(vals_k))
        assert np.array_equal(np.asarray(grads_v), np.asarray(grads_k))

    def test_unbatched_call_is_closed_form(self):
        x, w, y, off, wt = _batch(1, 9, 3, seed=7)
        vag = pallas_re.vmappable_entity_value_and_grad(LogisticLoss, True)
        val, grad = vag(jnp.asarray(x[0]), jnp.asarray(w[0]),
                        jnp.asarray(y[0]), jnp.asarray(off[0]),
                        jnp.asarray(wt[0]))
        rv, rg = _ref_value_and_grad(x[0], w[0], y[0], off[0], wt[0])
        np.testing.assert_allclose(float(val), rv, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(grad), rg, rtol=1e-4,
                                   atol=1e-5)


def _re_problem(n=3000, n_ent=41, d=4, seed=3):
    """41 entities: deliberately NOT a multiple of the 128-entity tile, so
    the solver's pre-pad path is always exercised."""
    rng = np.random.default_rng(seed)
    xr = rng.normal(size=(n, d)).astype(np.float32)
    ent = rng.integers(0, n_ent, size=n).astype(np.int64)
    u = rng.normal(size=(n_ent, d)).astype(np.float32)
    m = np.einsum("nd,nd->n", xr, u[ent])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-m))).astype(np.float32)
    data = GameData.build(labels=y, shards={"re": dense_shard(xr)},
                          id_columns={"entityId": ent})
    return data


def _solver(**kw):
    return RandomEffectSolver(
        task=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=20,
                                             tolerance=1e-6,
                                             track_states=False)), **kw)


def _dataset(data):
    return RandomEffectDataset.build(
        "perEntity", data, RandomEffectDatasetConfig("entityId", "re"))


def _coeffs(model):
    c = model.coeffs() if callable(model.coeffs) else model.coeffs
    return np.asarray(c[0] if isinstance(c, tuple) else c)


class TestSolverEngagement:
    def test_fused_train_matches_xla_path(self):
        data = _re_problem()
        off = np.zeros(data.n_samples, np.float32)
        mf, sf = _solver(fused_interpret=True).train(_dataset(data), off, 1.0)
        mx, sx = _solver(fused=False).train(_dataset(data), off, 1.0)
        cf, cx = _coeffs(mf), _coeffs(mx)
        assert cf.shape == cx.shape
        # different single-pass reduction order steers the line search
        # microscopically differently per iteration; the optimum agrees
        np.testing.assert_allclose(cf, cx, atol=2e-3)
        np.testing.assert_allclose(np.asarray(sf), np.asarray(sx),
                                   atol=5e-3)

    def test_inert_gate_is_bit_identical_on_cpu(self):
        """fused=True (the DEFAULT) without the interpreter on CPU must
        change nothing, bit for bit — the production fallback contract
        (non-TPU backends keep XLA)."""
        data = _re_problem()
        off = np.zeros(data.n_samples, np.float32)
        ma, sa = _solver().train(_dataset(data), off, 1.0)
        mb, sb = _solver(fused=False).train(_dataset(data), off, 1.0)
        assert np.array_equal(_coeffs(ma), _coeffs(mb))
        assert np.array_equal(np.asarray(sa), np.asarray(sb))

    def test_fused_f32_is_deterministic_bit_identical(self):
        data = _re_problem()
        off = np.zeros(data.n_samples, np.float32)
        solver = _solver(fused_interpret=True)
        dataset = _dataset(data)
        m1, s1 = solver.train(dataset, off, 1.0)
        m2, s2 = solver.train(dataset, off, 1.0)
        assert np.array_equal(_coeffs(m1), _coeffs(m2))
        assert np.array_equal(np.asarray(s1), np.asarray(s2))

    def test_fused_sweep_zero_recompiles_past_first(self):
        from photon_ml_tpu.telemetry.metrics import default_registry

        data = _re_problem(seed=11)
        off = np.zeros(data.n_samples, np.float32)
        solver = _solver(fused_interpret=True)
        dataset = _dataset(data)
        solver.train(dataset, off, 1.0)
        fam = default_registry().get("photon_compiles_total")
        before = (fam.labels(fn="game.re.sweep_fused").value
                  if fam is not None else 0)
        solver.train(dataset, off, 1.0)
        fam = default_registry().get("photon_compiles_total")
        after = (fam.labels(fn="game.re.sweep_fused").value
                 if fam is not None else 0)
        assert after == before

    def test_bf16_design_through_fused_kernel(self):
        data = _re_problem()
        off = np.zeros(data.n_samples, np.float32)
        mb, _sb = _solver(fused_interpret=True,
                          design_dtype="bfloat16").train(
                              _dataset(data), off, 1.0)
        mx, _sx = _solver(fused=False).train(_dataset(data), off, 1.0)
        np.testing.assert_allclose(_coeffs(mb), _coeffs(mx), atol=5e-2)

    def test_entity_mesh_fused_matches_unsharded(self):
        from photon_ml_tpu.parallel.mesh import make_mesh

        data = _re_problem()
        off = np.zeros(data.n_samples, np.float32)
        mesh = make_mesh({"entity": 4})
        mm, _ = _solver(fused_interpret=True, mesh=mesh).train(
            _dataset(data), off, 1.0)
        mx, _ = _solver(fused=False).train(_dataset(data), off, 1.0)
        np.testing.assert_allclose(_coeffs(mm), _coeffs(mx), atol=2e-3)


@pytest.mark.slow
@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="kernel speedup is a TPU property")
def test_fused_sweep_beats_xla_on_tpu():
    """The acceptance gate: the single-pass kernel measurably beats the
    XLA two-pass _solve_bucket path on a Mosaic-lowered run."""
    import time

    rng = np.random.default_rng(0)
    n, n_ent, d = 1_500_000, 25_000, 8
    xr = rng.normal(size=(n, d)).astype(np.float32)
    probs = 1.0 / np.arange(1, n_ent + 1)
    probs /= probs.sum()
    ent = rng.choice(n_ent, size=n, p=probs).astype(np.int64)
    y = (rng.uniform(size=n) < 0.5).astype(np.float32)
    data = GameData.build(labels=y, shards={"re": dense_shard(xr)},
                          id_columns={"entityId": ent})
    off = np.zeros(n, np.float32)

    def wall(solver):
        dataset = _dataset(data)
        _m, s = solver.train(dataset, off, 1.0)  # compile + warm
        float(np.asarray(s[:1])[0])
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            _m, s = solver.train(dataset, off, 1.0)
            float(np.asarray(s[:1])[0])
            best = min(best, time.perf_counter() - t0)
        return best

    fused_s = wall(_solver())
    xla_s = wall(_solver(fused=False))
    assert fused_s < xla_s, (fused_s, xla_s)
