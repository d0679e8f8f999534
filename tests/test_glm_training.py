"""End-to-end single-chip GLM training tests (SURVEY.md §7 stage 3).

Parity targets mirror BASELINE configs 1–3: logistic L-BFGS+L2 vs sklearn,
elastic-net via OWLQN (sparsity + loss sanity), TRON vs L-BFGS solution
agreement, warm-start sweep semantics, variance computation closed forms.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from sklearn.linear_model import LogisticRegression, PoissonRegressor, Ridge

from photon_ml_tpu.glm import (
    GLMOptimizationConfiguration,
    OptimizationProblem,
    train_glm_sweep,
    validate_and_select,
)
from photon_ml_tpu.glm import training
from photon_ml_tpu.models import Coefficients, GeneralizedLinearModel
from photon_ml_tpu.ops.design import DenseDesign
from photon_ml_tpu.ops.losses import loss_for_task
from photon_ml_tpu.ops.normalization import NormalizationContext, NoNormalization
from photon_ml_tpu.ops.objective import GLMData, GLMObjective
from photon_ml_tpu.ops.regularization import (
    L2Regularization,
    elastic_net,
)
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.evaluation import parse_evaluators
from photon_ml_tpu.telemetry import metrics, tracing
from photon_ml_tpu.types import OptimizerType, TaskType, VarianceComputationType


def make_classification(n=400, d=8, seed=0, intercept=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    w_true = rng.normal(size=d)
    logits = x @ w_true - 0.3
    labels = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))).astype(np.float64)
    if intercept:
        x = np.hstack([x, np.ones((n, 1))])
    data = GLMData(
        design=DenseDesign(x=jnp.asarray(x)),
        labels=jnp.asarray(labels),
        offsets=jnp.zeros(n), weights=jnp.ones(n))
    return data, x, labels


TIGHT = OptimizerConfig(max_iterations=300, tolerance=1e-10)


class TestLogisticParity:
    def test_matches_sklearn_l2(self):
        """BASELINE config 1: logistic + L-BFGS + L2 (a1a-shaped problem)."""
        data, x, labels = make_classification()
        lam = 2.0
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerType.LBFGS, regularization=L2Regularization,
            optimizer_config=TIGHT)
        # Exclude the intercept column from L2, like sklearn.
        mask = jnp.ones(x.shape[1]).at[-1].set(0.0)
        models = train_glm_sweep(TaskType.LOGISTIC_REGRESSION, data, [lam], cfg,
                                 reg_mask=mask)
        w = np.asarray(models[0].model.coefficients.means)

        sk = LogisticRegression(C=1.0 / lam, fit_intercept=True, tol=1e-12,
                                max_iter=10000)
        sk.fit(x[:, :-1], labels)
        np.testing.assert_allclose(w[:-1], sk.coef_[0], atol=2e-5)
        np.testing.assert_allclose(w[-1], sk.intercept_[0], atol=2e-5)

    def test_tron_matches_lbfgs(self):
        """BASELINE config 3: TRON reaches the same optimum as L-BFGS."""
        data, x, labels = make_classification(seed=1)
        for opt in (OptimizerType.LBFGS, OptimizerType.TRON):
            cfg = GLMOptimizationConfiguration(
                optimizer=opt, regularization=L2Regularization,
                optimizer_config=TIGHT)
            models = train_glm_sweep(TaskType.LOGISTIC_REGRESSION, data, [1.0], cfg)
            if opt == OptimizerType.LBFGS:
                w_lbfgs = np.asarray(models[0].model.coefficients.means)
            else:
                w_tron = np.asarray(models[0].model.coefficients.means)
        np.testing.assert_allclose(w_tron, w_lbfgs, atol=1e-6)


class TestLinearAndPoisson:
    def test_ridge_closed_form(self):
        rng = np.random.default_rng(2)
        n, d = 200, 6
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d) + 0.1 * rng.normal(size=n)
        lam = 3.0
        data = GLMData(design=DenseDesign(x=jnp.asarray(x)), labels=jnp.asarray(y),
                       offsets=jnp.zeros(n), weights=jnp.ones(n))
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization, optimizer_config=TIGHT)
        models = train_glm_sweep(TaskType.LINEAR_REGRESSION, data, [lam], cfg)
        w = np.asarray(models[0].model.coefficients.means)
        w_exact = np.linalg.solve(x.T @ x + lam * np.eye(d), x.T @ y)
        np.testing.assert_allclose(w, w_exact, atol=1e-7)

    def test_poisson_matches_sklearn(self):
        rng = np.random.default_rng(3)
        n, d = 300, 5
        x = rng.normal(size=(n, d)) * 0.5
        y = rng.poisson(np.exp(x @ rng.normal(size=d) * 0.5)).astype(np.float64)
        data = GLMData(design=DenseDesign(x=jnp.asarray(x)), labels=jnp.asarray(y),
                       offsets=jnp.zeros(n), weights=jnp.ones(n))
        lam = 1.0
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization, optimizer_config=TIGHT)
        models = train_glm_sweep(TaskType.POISSON_REGRESSION, data, [lam], cfg)
        w = np.asarray(models[0].model.coefficients.means)
        # sklearn PoissonRegressor minimizes mean loss + alpha/2 ||w||^2
        # (and 2*deviance scaling); alpha = lam / n matches our sum-form.
        sk = PoissonRegressor(alpha=lam / n, fit_intercept=False, tol=1e-12,
                              max_iter=10000)
        sk.fit(x, y)
        np.testing.assert_allclose(w, sk.coef_, atol=1e-4)


class TestElasticNet:
    def test_owlqn_produces_sparsity(self):
        """BASELINE config 2: elastic-net via OWLQN zeroes out coefficients."""
        rng = np.random.default_rng(4)
        n, d = 300, 20
        x = rng.normal(size=(n, d))
        w_true = np.zeros(d)
        w_true[:3] = [2.0, -1.5, 1.0]  # only 3 informative features
        logits = x @ w_true
        labels = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))).astype(float)
        data = GLMData(design=DenseDesign(x=jnp.asarray(x)), labels=jnp.asarray(labels),
                       offsets=jnp.zeros(n), weights=jnp.ones(n))
        cfg = GLMOptimizationConfiguration(
            regularization=elastic_net(alpha=0.9), optimizer_config=TIGHT)
        models = train_glm_sweep(TaskType.LOGISTIC_REGRESSION, data, [20.0], cfg)
        w = np.asarray(models[0].model.coefficients.means)
        assert np.sum(np.abs(w) > 1e-8) <= 8, "L1 should zero most noise features"
        assert np.all(np.abs(w[:3]) > 0.05), "informative features survive"


class TestSweep:
    def test_descending_order_and_warm_start(self):
        data, _, _ = make_classification(seed=5)
        cfg = GLMOptimizationConfiguration(regularization=L2Regularization)
        models = train_glm_sweep(
            TaskType.LOGISTIC_REGRESSION, data, [0.1, 10.0, 1.0], cfg)
        assert [m.regularization_weight for m in models] == [10.0, 1.0, 0.1]
        # Stronger regularization => smaller coefficient norm.
        norms = [float(jnp.linalg.norm(m.model.coefficients.means)) for m in models]
        assert norms[0] < norms[1] < norms[2]

    def test_batched_sweep_matches_sequential(self):
        """The vmapped all-lambda sweep must reach the same optima the
        warm-started sequential sweep reaches (convex problems, tight
        tolerance — paths differ, fixed points don't)."""
        from photon_ml_tpu.glm.training import train_glm_sweep_batched

        data, _, _ = make_classification(seed=8)
        cfg = GLMOptimizationConfiguration(regularization=L2Regularization,
                                           optimizer_config=TIGHT)
        lams = [10.0, 1.0, 0.1]
        seq = train_glm_sweep(TaskType.LOGISTIC_REGRESSION, data, lams, cfg)
        bat = train_glm_sweep_batched(
            TaskType.LOGISTIC_REGRESSION, data, lams, cfg)
        assert ([m.regularization_weight for m in bat]
                == [m.regularization_weight for m in seq])
        for s, b in zip(seq, bat):
            # both solvers stop within working-precision of the optimum
            # (stall-terminated at TIGHT tolerance); the fixed points agree
            assert float(b.result.grad_norm) < 1e-4
            assert float(s.result.grad_norm) < 1e-4
            np.testing.assert_allclose(
                np.asarray(b.model.coefficients.means),
                np.asarray(s.model.coefficients.means),
                atol=1e-4, rtol=1e-3,
                err_msg=f"lambda={s.regularization_weight}")

    def test_validate_and_select(self):
        data, x, labels = make_classification(seed=6)
        val, _, _ = make_classification(seed=7)
        cfg = GLMOptimizationConfiguration(regularization=L2Regularization,
                                           optimizer_config=TIGHT)
        models = train_glm_sweep(
            TaskType.LOGISTIC_REGRESSION, data, [1000.0, 1.0], cfg)
        best, evaluated = validate_and_select(
            models, parse_evaluators(["AUC", "LOGISTIC_LOSS"]), val)
        # Sane lambda should beat absurd over-regularization on validation.
        assert evaluated[best].regularization_weight == 1.0
        assert evaluated[0].evaluation is not None


class TestVariance:
    def test_full_variance_linear_closed_form(self):
        rng = np.random.default_rng(8)
        n, d = 150, 4
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d)
        lam = 0.5
        data = GLMData(design=DenseDesign(x=jnp.asarray(x)), labels=jnp.asarray(y),
                       offsets=jnp.zeros(n), weights=jnp.ones(n))
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization, optimizer_config=TIGHT,
            variance_type=VarianceComputationType.FULL)
        models = train_glm_sweep(TaskType.LINEAR_REGRESSION, data, [lam], cfg)
        v = np.asarray(models[0].model.coefficients.variances)
        expect = np.diag(np.linalg.inv(x.T @ x + lam * np.eye(d)))
        np.testing.assert_allclose(v, expect, rtol=1e-6)

    def test_simple_variance_is_inverse_diagonal(self):
        data, x, labels = make_classification(seed=9)
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization, optimizer_config=TIGHT,
            variance_type=VarianceComputationType.SIMPLE)
        models = train_glm_sweep(TaskType.LOGISTIC_REGRESSION, data, [1.0], cfg)
        w = np.asarray(models[0].model.coefficients.means)
        v = np.asarray(models[0].model.coefficients.variances)
        p = 1.0 / (1.0 + np.exp(-(x @ w)))
        diag = np.einsum("nd,n->d", x**2, p * (1 - p)) + 1.0
        np.testing.assert_allclose(v, 1.0 / diag, rtol=1e-6)


class TestModelScoring:
    def test_predict_mean_per_task(self):
        x = jnp.asarray(np.array([[1.0, 2.0], [0.0, -1.0]]))
        design = DenseDesign(x=x)
        coeffs = Coefficients(means=jnp.asarray([0.5, -0.5]))
        margins = np.asarray(design.matvec(coeffs.means))
        m_log = GeneralizedLinearModel(coeffs, TaskType.LOGISTIC_REGRESSION)
        np.testing.assert_allclose(
            np.asarray(m_log.predict_mean(design)), 1 / (1 + np.exp(-margins)))
        m_poi = GeneralizedLinearModel(coeffs, TaskType.POISSON_REGRESSION)
        np.testing.assert_allclose(
            np.asarray(m_poi.predict_mean(design)), np.exp(margins))


class TestSmoothedHingeSVM:
    def test_trains_and_separates(self):
        """BASELINE task 4: SMOOTHED_HINGE_LOSS_LINEAR_SVM end-to-end —
        the smoothed-hinge margin objective must learn a separator on
        separable data and achieve high accuracy."""
        rng = np.random.default_rng(11)
        n, d = 600, 8
        w_true = rng.normal(size=d)
        x = rng.normal(size=(n, d))
        margin = x @ w_true
        labels = (margin > 0).astype(np.float64)
        data = GLMData(design=DenseDesign(x=jnp.asarray(x)),
                       labels=jnp.asarray(labels),
                       offsets=jnp.zeros(n), weights=jnp.ones(n))
        # smoothed hinge is only piecewise-twice-differentiable — gradient
        # norms plateau above L-BFGS's tight tolerance, so assert on the
        # solution quality, not the convergence flag
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=300,
                                             tolerance=1e-6))
        models = train_glm_sweep(
            TaskType.SMOOTHED_HINGE_LOSS_LINEAR_SVM, data, [0.1], cfg)
        w = np.asarray(models[0].model.coefficients.means)
        pred = (x @ w > 0)
        accuracy = float((pred == labels.astype(bool)).mean())
        assert accuracy > 0.97, accuracy
        # direction agrees with the generating hyperplane
        cos = (w @ w_true) / (np.linalg.norm(w) * np.linalg.norm(w_true))
        assert cos > 0.95, cos

    def test_iteration_trace_recorded(self):
        """OptimizerResult carries the reference's OptimizationStatesTracker
        table; log_optimizer_trace renders it without error."""
        import logging

        from photon_ml_tpu.logging_util import log_optimizer_trace

        rng = np.random.default_rng(0)
        n, d = 200, 4
        x = rng.normal(size=(n, d))
        labels = (rng.uniform(size=n) < 0.5).astype(np.float64)
        data = GLMData(design=DenseDesign(x=jnp.asarray(x)),
                       labels=jnp.asarray(labels),
                       offsets=jnp.zeros(n), weights=jnp.ones(n))
        cfg = GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=30,
                                             tolerance=1e-8))
        tm = train_glm_sweep(TaskType.LOGISTIC_REGRESSION, data, [1.0], cfg)[0]
        values = np.asarray(tm.result.values)
        n_it = int(tm.result.iterations)
        assert values.shape[0] == 31  # max_iterations + 1
        assert np.isfinite(values[:n_it + 1]).all()
        # monotone nonincreasing objective for the recorded iterations
        assert (np.diff(values[:n_it + 1]) <= 1e-8).all()
        log_optimizer_trace(tm.result, "test")  # must not raise


class TestA1aShapedAucParity:
    def test_auc_parity_to_1e4(self):
        """BASELINE config 1's acceptance criterion — validation AUC parity
        to 1e-4 vs an independent solver — on an a1a-SHAPED problem: 1605
        train / 123 binary features (~14 active per row, the LIBSVM a1a
        layout; the real dataset needs egress, SURVEY Appendix A). Both
        solvers get the same L2 objective; parity must hold at the METRIC
        level, not just coefficients."""
        from sklearn.metrics import roc_auc_score

        rng = np.random.default_rng(11)
        n_train, n_val, d = 1605, 3000, 123
        w_true = rng.normal(size=d) * (rng.uniform(size=d) < 0.4)

        def make(n, seed):
            r = np.random.default_rng(seed)
            x = (r.uniform(size=(n, d)) < 14.0 / d).astype(np.float64)
            margin = x @ w_true - 0.5
            y = (r.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(
                np.float64)
            return x, y

        xt, yt = make(n_train, 1)
        xv, yv = make(n_val, 2)
        # intercept column appended, exempt from L2 (sklearn semantics)
        xt_i = np.concatenate([xt, np.ones((n_train, 1))], axis=1)
        lam = 1.0
        data = GLMData(design=DenseDesign(x=jnp.asarray(xt_i)),
                       labels=jnp.asarray(yt),
                       offsets=jnp.zeros(n_train), weights=jnp.ones(n_train))
        mask = jnp.ones(d + 1).at[-1].set(0.0)
        cfg = GLMOptimizationConfiguration(
            optimizer=OptimizerType.LBFGS, regularization=L2Regularization,
            optimizer_config=TIGHT)
        models = train_glm_sweep(TaskType.LOGISTIC_REGRESSION, data, [lam],
                                 cfg, reg_mask=mask)
        w = np.asarray(models[0].model.coefficients.means)

        sk = LogisticRegression(C=1.0 / lam, fit_intercept=True, tol=1e-12,
                                max_iter=10000)
        sk.fit(xt, yt)
        auc_ours = roc_auc_score(yv, xv @ w[:-1] + w[-1])
        auc_sk = roc_auc_score(yv, xv @ sk.coef_[0] + sk.intercept_[0])
        assert abs(auc_ours - auc_sk) < 1e-4, (auc_ours, auc_sk)
        assert auc_ours > 0.7, auc_ours  # the model actually learned


# --- the compiled solve outlives the call -----------------------------------

LOGISTIC = TaskType.LOGISTIC_REGRESSION
HELD = GLMOptimizationConfiguration(
    optimizer=OptimizerType.LBFGS, regularization=L2Regularization,
    optimizer_config=OptimizerConfig(max_iterations=40, tolerance=1e-9))
D = 9  # make_classification's eight features and the intercept


def _compiles(fn="glm.sweep_solve"):
    """``photon_compiles_total{fn}`` as the process stands."""
    family = metrics.default_registry().get("photon_compiles_total")
    return 0.0 if family is None else family.labels(fn=fn).value


def _scaling(seed, shifts=False):
    rng = np.random.default_rng(seed)
    factors = jnp.asarray(rng.uniform(0.5, 2.0, size=D)).at[-1].set(1.0)
    if not shifts:
        return NormalizationContext(factors=factors)
    return NormalizationContext(
        factors=factors,
        shifts=jnp.asarray(rng.normal(size=D) * 0.1).at[-1].set(0.0),
        intercept_index=D - 1)


def _mask(*free):
    """A 0/1 selector that leaves the coefficients ``free`` unregularized."""
    return jnp.ones(D).at[jnp.asarray(free)].set(0.0)


def _means(trained):
    return [np.asarray(t.model.coefficients.means) for t in trained]


def _fresh_means(data, weights, normalization, reg_mask):
    """The sweep by a fresh ``jax.jit`` of ``build_problem(...).run`` that
    closes over its own normalization and mask: what the held program must
    give bit for bit."""
    problem = training.build_problem(LOGISTIC, HELD, normalization, reg_mask)
    run = jax.jit(problem.run)
    w, out = jnp.zeros((D,)), []
    for lam in sorted(weights, reverse=True):
        w = run(data, w, jnp.asarray(lam, w.dtype)).w
        out.append(np.asarray(training.to_original_space(
            Coefficients(means=w), normalization).means))
    return out


@pytest.fixture
def cold():
    """No solve held: the test's first call is its signature's first."""
    training._sweep_solve_fn.cache_clear()


class TestHeldSolve:
    def test_second_call_compiles_nothing(self, cold):
        """(a) two calls on one shape: one compile, and the second call
        emits no ``jit.compile`` span."""
        data, _, _ = make_classification(seed=11)
        before = _compiles()
        first = train_glm_sweep(LOGISTIC, data, [1.0, 0.1], HELD)
        assert _compiles() - before == 1
        seen = []
        remove = tracing.GLOBAL_TRACER.add_tap(seen.append)
        try:
            second = train_glm_sweep(LOGISTIC, data, [1.0, 0.1], HELD)
            tracing.flush()
        finally:
            remove()
        assert _compiles() - before == 1
        names = [r["name"] for r in seen]
        assert names.count("glm.sweep") == 1 and names.count("glm.solve") == 2
        assert "jit.compile" not in names
        for a, b in zip(_means(first), _means(second)):
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("varied", ["factors", "shifts", "reg_mask"])
    def test_values_are_arguments_not_constants(self, cold, varied):
        """(b) two calls that differ in the VALUES of the normalization or
        of the mask share one program, and each answers for its own: a
        closed-over constant gone stale would give the second call the
        first's."""
        data, _, _ = make_classification(seed=12)
        if varied == "factors":
            cases = [(_scaling(1), None), (_scaling(2), None)]
        elif varied == "shifts":
            cases = [(_scaling(1, shifts=True), None),
                     (_scaling(2, shifts=True), None)]
        else:
            cases = [(NoNormalization, _mask(D - 1)),
                     (NoNormalization, _mask(0, 3))]
        before = _compiles()
        got = [_means(train_glm_sweep(LOGISTIC, data, [2.0, 0.5], HELD,
                                      normalization=n, reg_mask=m))
               for n, m in cases]
        assert _compiles() - before == 1
        assert not np.array_equal(got[0][-1], got[1][-1])
        # constant shifts let XLA fold factors * shifts ahead of time in
        # the fresh program: the last digit there, every bit elsewhere
        atol = 1e-7 if varied == "shifts" else 0.0
        for (n, m), means in zip(cases, got):
            for a, b in zip(means, _fresh_means(data, [2.0, 0.5], n, m)):
                np.testing.assert_allclose(a, b, rtol=0.0, atol=atol)

    def test_one_executable_per_structure_under_one_wrapper(self, cold):
        """(c) identity, scaling, scaling with shifts, each with a mask and
        without: six executables, one wrapper, chosen by the input."""
        data, _, _ = make_classification(seed=13)
        run = training._sweep_solve_fn(LOGISTIC, HELD, None, False)
        structures = [(n, m)
                      for n in (NoNormalization, _scaling(3),
                                _scaling(3, shifts=True))
                      for m in (None, _mask(D - 1))]
        for k, (n, m) in enumerate(structures, start=1):
            for _ in range(2):  # the second call of a structure adds none
                train_glm_sweep(LOGISTIC, data, [1.0], HELD,
                                normalization=n, reg_mask=m)
                assert run.compiles == k
        assert training._sweep_solve_fn(LOGISTIC, HELD, None, False) is run
        assert training._sweep_solve_fn.cache_info().currsize == 1

    @pytest.mark.parametrize("batched", [False, True])
    def test_the_solve_lowers_as_jit_run(self, batched):
        """(d) the benchmark reads the solve's device time from the programs
        named ``jit_run``."""
        data, _, _ = make_classification(seed=14)
        run = training._sweep_solve_fn(LOGISTIC, HELD, None, batched)
        lam = jnp.ones((2,)) if batched else jnp.asarray(1.0)
        text = run._jitted.lower(data, jnp.zeros((D,)), lam,
                                 NoNormalization, None).as_text()
        assert "module @jit_run " in text

    @pytest.mark.parametrize("sweep", ["sequential", "batched"])
    def test_a_mask_that_is_not_zero_one_still_raises(self, sweep):
        """(e) the traced mask inside the program cannot be checked; the
        concrete one is, at the call's entry."""
        data, _, _ = make_classification(seed=15)
        fn = train_glm_sweep if sweep == "sequential" \
            else training.train_glm_sweep_batched
        with pytest.raises(ValueError, match="0/1 selector"):
            fn(LOGISTIC, data, [1.0], HELD,
               reg_mask=jnp.ones(D).at[-1].set(0.5))

    def test_batched_twice_compiles_once(self, cold):
        """(f)"""
        data, _, _ = make_classification(seed=16)
        before = _compiles("glm.sweep_solve_batched")
        first = training.train_glm_sweep_batched(
            LOGISTIC, data, [1.0, 0.1], HELD, normalization=_scaling(4))
        second = training.train_glm_sweep_batched(
            LOGISTIC, data, [1.0, 0.1], HELD, normalization=_scaling(5))
        assert _compiles("glm.sweep_solve_batched") - before == 1
        assert not np.array_equal(_means(first)[-1], _means(second)[-1])
        sequential = train_glm_sweep(
            LOGISTIC, data, [1.0, 0.1], HELD, normalization=_scaling(5),
            warm_start=False)
        for a, b in zip(_means(second), _means(sequential)):
            np.testing.assert_allclose(a, b, atol=1e-6)

    @pytest.mark.parametrize("shifts", [False, True])
    def test_mesh_path_holds_its_solve_too(self, cold, shifts):
        """(g) under a mesh the ``shard_map`` bodies close over the outer
        trace's normalization and mask: two calls with different factors
        compile once (cold and warm starts under one placement), and agree
        with one device."""
        from photon_ml_tpu.parallel import make_mesh, shard_glm_data

        assert jax.device_count() >= 8
        mesh = make_mesh({"data": 8})
        data, _, _ = make_classification(n=203, seed=17)
        sharded = shard_glm_data(data, 8, device_put_mesh=mesh)
        mask = _mask(D - 1)
        tight = GLMOptimizationConfiguration(
            optimizer=OptimizerType.LBFGS, regularization=L2Regularization,
            optimizer_config=OptimizerConfig(max_iterations=200,
                                             tolerance=1e-10))
        for seed in (6, 7):
            n = _scaling(seed, shifts=shifts)
            dist = train_glm_sweep(LOGISTIC, sharded, [2.0, 0.5], tight,
                                   normalization=n, reg_mask=mask,
                                   mesh=mesh, dim=D)
            local = train_glm_sweep(LOGISTIC, data, [2.0, 0.5], tight,
                                    normalization=n, reg_mask=mask)
            for a, b in zip(_means(dist), _means(local)):
                np.testing.assert_allclose(a, b, atol=1e-8)
        assert training._sweep_solve_fn(LOGISTIC, tight, mesh, False).compiles == 1
        assert training._sweep_solve_fn(LOGISTIC, tight, None, False).compiles == 1
