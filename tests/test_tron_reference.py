"""TRON held to the benchmark's plain reference, and its count of
Hessian-vector products held exact.

``benchmark/reference/tron.py`` is LIBLINEAR's method written out on the host
(nothing of the program imported); ``optimize/tron.py`` is the same method as
two nested ``while_loop``s. On a seeded logistic problem the two walk one
path: the same outer iterations, the same products, the same iterates. The
problem's columns are near one scale: conjugate gradients multiply a rounding
from one inner step to the next, so on columns spread over decades the paths
of two float32 implementations part after a few outer iterations (PERF.md,
section 4, ``glm_tron_1024``), and the benchmark holds those one-sided.

``OptimizerResult.hvps`` is the conjugate gradients' trip count summed over
the outer iterations: one an iteration where the Hessian is the identity, a
Python-side count of the test's own ``hvp`` on a logistic problem, zero from
every minimizer that makes no product. And ``fused_hvp``, the kernel each
product is on a TPU, against the two plain contractions through the Pallas
interpreter.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import tron as reference
from photon_ml_tpu.glm.problem import (
    GLMOptimizationConfiguration,
    OptimizationProblem,
)
from photon_ml_tpu.ops.design import DenseDesign
from photon_ml_tpu.ops.losses import LogisticLoss
from photon_ml_tpu.ops.objective import GLMData, GLMObjective
from photon_ml_tpu.ops.pallas_glm import auto_block_rows, fused_hvp
from photon_ml_tpu.ops.regularization import L2Regularization
from photon_ml_tpu.optimize import (
    OptimizerConfig,
    minimize_lbfgs,
    minimize_owlqn,
    minimize_tron,
)
from photon_ml_tpu.optimize.lbfgs import (
    minimize_lbfgs_lanes,
    vmapped_evaluation,
)
from photon_ml_tpu.types import OptimizerType

ROWS, DIM, CHUNK = 3000, 12, 1000
#: the benchmark cell's settings (upstream TRON.scala's defaults)
SETTINGS = dict(max_iterations=15, tolerance=1e-5, cg_max_iterations=20)


def _logistic(dtype):
    rng = np.random.default_rng(17)
    scale = 10.0 ** rng.uniform(-0.3, 0.3, DIM)
    x = rng.normal(size=(ROWS, DIM)) * scale
    planted = rng.normal(size=DIM) / scale
    y = rng.random(ROWS) < 1.0 / (1.0 + np.exp(-(x @ planted)))
    return jnp.asarray(x, dtype), jnp.asarray(y, dtype)


@pytest.mark.parametrize("dtype, rtol, tolerance", [
    ("float64", 1e-9, 1e-5), ("float32", 2e-4, 1e-4)])
@pytest.mark.parametrize("lam", [10.0, 0.1])
def test_program_walks_the_references_path(dtype, rtol, tolerance, lam):
    """``minimize_tron`` through ``OptimizationProblem.run`` against the
    reference: the iterate, both counts, the verdict and the value after
    every outer iteration. In float32 the whole process computes in float32,
    as on the chip (the suite's 64-bit mode off for the test), and the
    gradient test is one that float32's rounding lets a solve meet."""
    settings = dict(SETTINGS, tolerance=tolerance)
    with jax.enable_x64(dtype == "float64"):
        x, y = _logistic(dtype)
        data = GLMData(design=DenseDesign(x=x), labels=y,
                       offsets=jnp.zeros_like(y), weights=jnp.ones_like(y))
        problem = OptimizationProblem(
            GLMObjective(LogisticLoss),
            GLMOptimizationConfiguration(
                optimizer=OptimizerType.TRON, regularization=L2Regularization,
                optimizer_config=OptimizerConfig(**settings)))
        got = jax.jit(problem.run)(data, jnp.zeros((DIM,), dtype),
                                   jnp.asarray(lam, dtype))
        plain = reference.Problem(x, y, lam, chunk=CHUNK)
        want = reference.tron(plain.fun, plain.hessian_at,
                              np.zeros(DIM, dtype), **settings)
        assert got.w.dtype == jnp.dtype(dtype) and got.hvps.dtype == jnp.int32
        k = int(got.iterations)
        assert k == want["iterations"] and 3 <= k <= 15
        assert int(got.hvps) == want["hvps"] and k < want["hvps"] <= 20 * k
        assert bool(got.converged) is want["converged"] is True
        assert int(got.evaluations) == k + 1
        np.testing.assert_allclose(got.w, want["w"], rtol=rtol,
                                   atol=rtol * np.abs(want["w"]).max())
        # the program's trace is float32 whatever the solve's type
        np.testing.assert_allclose(got.values[:k + 1], want["values"],
                                   rtol=max(rtol, 2e-7))
        np.testing.assert_allclose(got.grad_norms[:k + 1],
                                   want["grad_norms"], rtol=max(rtol, 1e-6),
                                   atol=1e-5 * want["grad_norms"][0])
        assert not np.any(np.isfinite(np.asarray(got.values[k + 1:])))


def test_reference_imports_nothing_of_the_program():
    import ast
    import inspect

    tree = ast.parse(inspect.getsource(reference))
    modules = [n.module if isinstance(n, ast.ImportFrom) else a.name
               for n in ast.walk(tree)
               if isinstance(n, (ast.Import, ast.ImportFrom))
               for a in n.names]
    assert modules and not [m for m in modules if "photon" in m]


# --- the count of Hessian-vector products ------------------------------------
def test_one_product_an_iteration_where_the_hessian_is_the_identity():
    """``f = 0.5 |w - c|^2``: the first conjugate-gradient step is the Newton
    step, cut to the trust region's boundary while the radius is short, so
    every outer iteration makes exactly one product."""
    c = jnp.asarray([30.0, -40.0, 0.5, 7.0])
    fun = lambda w: (0.5 * jnp.vdot(w - c, w - c), w - c)
    result = minimize_tron(fun, lambda w, v: v, jnp.zeros(4),
                           OptimizerConfig(max_iterations=30, tolerance=1e-9))
    assert bool(result.converged)
    assert int(result.hvps) == int(result.iterations) >= 1
    np.testing.assert_allclose(result.w, c, rtol=1e-9)


def test_products_equal_a_python_side_count():
    x, y = _logistic("float64")
    calls = []

    def fun(w):
        m = x @ w
        return (jnp.sum(jnp.logaddexp(0.0, m) - y * m) + 0.5 * jnp.vdot(w, w),
                (jax.nn.sigmoid(m) - y) @ x + w)

    def hvp(w, v):
        jax.debug.callback(lambda _: calls.append(1), v)
        s = jax.nn.sigmoid(x @ w)
        return (s * (1.0 - s) * (x @ v)) @ x + v

    result = jax.jit(lambda w0: minimize_tron(
        fun, hvp, w0, OptimizerConfig(**SETTINGS)))(jnp.zeros(DIM))
    jax.effects_barrier()
    assert result.hvps.dtype == jnp.int32
    assert int(result.hvps) == len(calls) > int(result.iterations) > 1


@pytest.mark.parametrize("name", ["lbfgs", "owlqn", "lbfgs_lanes"])
def test_minimizers_without_products_count_none(name):
    x, y = _logistic("float64")

    def evaluation(xe, ye):
        def fun(w):
            m = xe @ w
            return (jnp.sum(jnp.logaddexp(0.0, m) - ye * m)
                    + 0.5 * jnp.vdot(w, w),
                    (jax.nn.sigmoid(m) - ye) @ xe + w)
        return fun

    config = OptimizerConfig(max_iterations=10, track_states=False)
    if name == "lbfgs":
        result = minimize_lbfgs(evaluation(x, y), jnp.zeros(DIM), config)
    elif name == "owlqn":
        result = minimize_owlqn(evaluation(x, y), jnp.zeros(DIM), 0.05,
                                config)
    else:
        lanes = (x.reshape(3, ROWS // 3, DIM), y.reshape(3, ROWS // 3))
        result, _ = minimize_lbfgs_lanes(
            lambda ls: vmapped_evaluation(
                lambda lane, w: evaluation(*lane)(w), ls),
            lanes, jnp.zeros((3, DIM)), config)
    assert result.hvps.dtype == jnp.int32
    assert result.hvps.shape == result.iterations.shape
    assert int(jnp.sum(result.iterations)) > 0
    assert not np.any(np.asarray(result.hvps))


# --- the kernel a product is on the chip -------------------------------------
@pytest.mark.parametrize("dtype, rtol", [(jnp.float32, 1e-5),
                                         (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("rows", [2000, 2003], ids=["dividing", "padded"])
def test_fused_hvp_is_the_two_plain_contractions(dtype, rtol, rows):
    """``X'(d2 * (X v))`` through the Pallas interpreter against the plain
    contractions of the same (rounded) design in float64, in the automatic
    mode the objective runs: at a row count that a block divides (400 rows:
    the design streams in place) and at one that none does (the kernel pads
    the tail); rows of weight zero contribute nothing, whatever they hold."""
    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.normal(size=(rows, 256)), dtype)
    v = jnp.asarray(rng.normal(size=256), jnp.float32)
    d2 = rng.random(rows).astype(np.float32) * 0.25
    d2[::7] = 0.0
    block = auto_block_rows(rows, dtype)
    assert block == (400 if rows == 2000 else None)
    block = None  # the automatic mode
    got = fused_hvp(x, v, jnp.asarray(d2), block_rows=block, interpret=True)
    xf = np.asarray(x.astype(jnp.float32), np.float64)
    vf = np.asarray(v, np.float64)
    if dtype == jnp.bfloat16:  # the kernel rounds what it multiplies
        vf = np.asarray(v.astype(dtype).astype(jnp.float32), np.float64)
    want = (d2 * (xf @ vf)) @ xf
    assert got.dtype == jnp.float32 and got.shape == (256,)
    np.testing.assert_allclose(got, want, rtol=rtol,
                               atol=rtol * np.abs(want).max())
    # the rows of weight zero: anything there, the product unmoved
    noisy = jnp.where((jnp.asarray(d2) == 0)[:, None],
                      jnp.asarray(1e3, dtype), x)
    again = fused_hvp(noisy, v, jnp.asarray(d2), block_rows=block,
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(got))
