"""Finished lanes leave the shared loops of a vmapped solve.

Under ``jax.vmap`` a solver's outer ``while_loop`` runs every lane while any
lane's condition holds, and so does the loop inside an iteration (the Armijo
halving of L-BFGS and OWL-QN, TRON's conjugate gradients). A lane whose solve
has ended (converged, failed, or padded) must add nothing to the trip count
that the batch shares, and its result must be what the lane gives alone.

The objective below counts its own BATCHED calls (one tick for a call that
serves every lane), which is what a bucket of the random-effect solver pays
for: one pass over the bucket's design.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.optimize import (
    OptimizerConfig,
    minimize_lbfgs,
    minimize_owlqn,
    minimize_tron,
)

E, S, D = 6, 40, 4
CONFIG = OptimizerConfig(max_iterations=25, tolerance=1e-9,
                         track_states=False)


class Counter:
    """Ticks once per call of the objective, however many lanes it serves."""

    def __init__(self):
        self.calls = 0

    def tick(self, f):
        self.calls += 1
        return np.zeros(np.shape(f), np.float64)

    def fold_into(self, f):
        return f + jax.pure_callback(
            self.tick, jax.ShapeDtypeStruct((), jnp.float64), f,
            vmap_method="broadcast_all")


def _lanes():
    """Six logistic lanes of uneven difficulty (column scales 0.3 to 10):
    four real ones, one whose gradient points uphill (its first line search
    finds no decrease in 25 halvings: ``failed`` at iteration 1), and one
    padded lane (no data, weight 0: converged at iteration 0)."""
    rng = np.random.default_rng(5)
    x = rng.normal(size=(E, S, D)) * np.array([1.0, 3.0, 0.3, 10.0])
    planted = rng.normal(size=(E, D))
    p = 1.0 / (1.0 + np.exp(-np.einsum("esd,ed->es", x, planted)))
    y = (rng.random((E, S)) < p).astype(np.float64)
    weights = np.ones((E, S))
    sign = np.ones(E)
    sign[4] = -1.0
    x[5], weights[5] = 0.0, 0.0
    return tuple(jnp.asarray(a) for a in (x, y, weights, sign))


def _objective(counter, xe, ye, we, sign):
    def fun(w):
        m = xe @ w
        f = jnp.sum(we * (jnp.logaddexp(0.0, m) - ye * m)) \
            + 0.5 * jnp.vdot(w, w)
        g = (we * (jax.nn.sigmoid(m) - ye)) @ xe + w
        return counter.fold_into(f), sign * g

    def hvp(w, v):
        s = jax.nn.sigmoid(xe @ w)
        return (we * s * (1.0 - s) * (xe @ v)) @ xe + v

    return fun, hvp


def _solver(name, config, counter):
    def solve(xe, ye, we, sign, w0):
        fun, hvp = _objective(counter, xe, ye, we, sign)
        if name == "lbfgs":
            return minimize_lbfgs(fun, w0, config)
        if name == "owlqn":
            return minimize_owlqn(fun, w0, 0.05, config)
        return minimize_tron(fun, hvp, w0, config)
    return solve


@pytest.mark.parametrize("name", ["lbfgs", "owlqn", "tron"])
def test_vmapped_lanes_equal_the_lanes_solved_one_by_one(name):
    lanes = _lanes()
    w0 = jnp.zeros((E, D))
    solve = _solver(name, CONFIG, Counter())
    batched = jax.jit(jax.vmap(solve))(*lanes, w0)
    single = jax.jit(solve)
    for e in range(E):
        alone = single(*(a[e] for a in lanes), w0[e])
        # the counts are exact; the iterate agrees to float64 rounding (a
        # batched contraction sums in another order than a lane's own)
        assert int(batched.iterations[e]) == int(alone.iterations), e
        assert int(batched.evaluations[e]) == int(alone.evaluations), e
        assert bool(batched.converged[e]) == bool(alone.converged), e
        np.testing.assert_allclose(batched.w[e], alone.w, rtol=1e-9,
                                   atol=1e-12)
    assert int(batched.iterations[5]) == 0 and bool(batched.converged[5])
    if name != "tron":  # TRON has no line search to fail
        assert int(batched.iterations[4]) == 1
        assert int(batched.evaluations[4]) == 2 + CONFIG.max_line_search
        assert not bool(batched.converged[4])
    # the lanes end at different iterations: the mask has something to do
    assert len({int(i) for i in batched.iterations}) >= 4


@pytest.mark.parametrize("name", ["lbfgs", "owlqn"])
def test_batched_passes_are_the_active_lanes_largest_trial_counts(name):
    """The objective is called, for all lanes at once, 1 + the sum over outer
    iterations of the largest number of trial points among the lanes still
    ACTIVE in that iteration: not 1 + ``max_line_search`` an iteration from
    the moment one lane is done. A lane's trials in iteration ``k`` are its
    ``evaluations`` under a cap of ``k`` iterations less those under
    ``k - 1`` (0 once it has ended)."""
    lanes = _lanes()
    w0 = jnp.zeros((E, D))

    def run(cap):
        counter = Counter()
        config = dataclasses.replace(CONFIG, max_iterations=cap)
        result = jax.jit(jax.vmap(_solver(name, config, counter)))(*lanes, w0)
        jax.block_until_ready(result)
        return np.asarray(result.evaluations), counter.calls

    evaluations = np.stack(
        [run(cap)[0] for cap in range(1, CONFIG.max_iterations + 1)])
    trials = np.diff(evaluations, axis=0, prepend=np.ones((1, E), int))
    _, calls = run(CONFIG.max_iterations)
    assert calls == 1 + int(trials.max(axis=1).sum())
    # and the failed lane's 26 trials are paid once, in iteration 1 alone
    assert trials[0].max() == 1 + CONFIG.max_line_search
    assert trials[1:].max() < 1 + CONFIG.max_line_search


@pytest.mark.parametrize("name", ["lbfgs", "owlqn", "tron"])
def test_a_finished_lane_adds_no_pass_to_its_batch(name):
    """A lane that starts at its optimum (gradient tiny, not zero: converged
    at iteration 0) beside one real lane: the batch makes exactly the calls
    the real lane makes alone. For TRON the count is of Hessian-vector
    products, the conjugate-gradient loop's passes."""
    x, y, weights, sign = (a[:1] for a in _lanes())
    config = dataclasses.replace(CONFIG, tolerance=1e-6)
    products = Counter()

    def solver(counter):
        inner = _solver(name, config, counter)

        def solve(xe, ye, we, s, w0):
            if name != "tron":
                return inner(xe, ye, we, s, w0)
            fun, hvp = _objective(counter, xe, ye, we, s)
            counted = lambda w, v: hvp(w, v) + jax.pure_callback(
                products.tick, jax.ShapeDtypeStruct((), jnp.float64),
                jnp.vdot(v, v), vmap_method="broadcast_all")
            return minimize_tron(fun, counted, w0, config)
        return solve

    alone_counter = Counter()
    alone = jax.jit(solver(alone_counter))(x[0], y[0], weights[0], sign[0],
                                           jnp.zeros(D))
    jax.block_until_ready(alone)
    alone_calls, alone_products = alone_counter.calls, products.calls
    assert bool(alone.converged) and int(alone.iterations) >= 3

    # lane 1 starts at the optimum, found to a tolerance far under the
    # batch's: its first gradient already meets the batch's test
    optimum = _solver(name, dataclasses.replace(
        CONFIG, max_iterations=80, tolerance=1e-12), Counter())(
            x[0], y[0], weights[0], sign[0], jnp.zeros(D)).w
    two = lambda a: jnp.concatenate([a, a])
    w0 = jnp.stack([jnp.zeros(D), optimum])
    pair_counter = Counter()
    products.calls = 0
    pair = jax.jit(jax.vmap(solver(pair_counter)))(
        two(x), two(y), two(weights), two(sign), w0)
    jax.block_until_ready(pair)
    assert int(pair.iterations[1]) == 0 and bool(pair.converged[1])
    assert float(pair.grad_norm[1]) > 0.0
    assert int(pair.evaluations[0]) == int(alone.evaluations)
    assert pair_counter.calls == alone_calls
    assert products.calls == alone_products


def test_unbatched_lbfgs_is_bit_for_bit_the_parents():
    """A single solve never has an inactive lane: its arithmetic is what it
    was before the mask. The numbers below are the parent commit's (af750a2)
    on this problem, float64 on the CPU, printed with ``float.hex``."""
    rng = np.random.default_rng(3)
    n, d = 200, 8
    x = rng.normal(size=(n, d)) * np.logspace(-1, 1, d)
    y = (rng.random(n) < 1 / (1 + np.exp(-(x @ rng.normal(size=d))))
         ).astype(np.float64)
    x, y = jnp.asarray(x), jnp.asarray(y)

    def fun(w):
        m = x @ w
        return (jnp.sum(jnp.logaddexp(0.0, m) - y * m) + 0.05 * jnp.vdot(w, w),
                (jax.nn.sigmoid(m) - y) @ x + 0.1 * w)

    r = jax.jit(lambda w0: minimize_lbfgs(
        fun, w0, OptimizerConfig(max_iterations=20, tolerance=1e-7)))(
            jnp.zeros(d))
    k = int(r.iterations) + 1
    assert (int(r.iterations), int(r.evaluations), bool(r.converged)) \
        == PARENT["counts"]
    assert [float(v).hex() for v in np.asarray(r.w)] == PARENT["w"]
    assert [float(v).hex() for v in np.asarray(r.values[:k])] \
        == PARENT["values"]


PARENT = {'counts': (20, 22, False),
 'values': ['0x1.1542460000000p+7', '0x1.ae22760000000p+6',
            '0x1.1e0e180000000p+6', '0x1.0df64c0000000p+6',
            '0x1.08f7e00000000p+6', '0x1.e91b5e0000000p+5',
            '0x1.d9b4860000000p+5', '0x1.cd107e0000000p+5',
            '0x1.c91e880000000p+5', '0x1.c5f52e0000000p+5',
            '0x1.c1d3960000000p+5', '0x1.bdf95a0000000p+5',
            '0x1.b7ff760000000p+5', '0x1.b461e20000000p+5',
            '0x1.b35b0a0000000p+5', '0x1.b27f540000000p+5',
            '0x1.b236de0000000p+5', '0x1.b1e2d60000000p+5',
            '0x1.b116380000000p+5', '0x1.b0aa900000000p+5',
            '0x1.b0587c0000000p+5'],
 'w': ['0x1.6d28d81ca311ap-1', '-0x1.59053f07cc72ep-3',
       '-0x1.f77810ccecf9ap+0', '0x1.46c4bc67e4d00p-3',
       '-0x1.6fbbcbd1ed500p-1', '0x1.d1fe5fc8af5c1p-2',
       '-0x1.722d1885184edp-1', '-0x1.66ffaf6afc7a8p-3']}
