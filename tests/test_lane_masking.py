"""Finished lanes leave the shared loops of a vmapped solve.

Under ``jax.vmap`` a solver's outer ``while_loop`` runs every lane while any
lane's condition holds, and so does the loop inside an iteration (the Armijo
halving of L-BFGS and OWL-QN, TRON's conjugate gradients). A lane whose solve
has ended (converged, failed, or padded) must add nothing to the trip count
that the batch shares, and its result must be what the lane gives alone.

The objective below counts its own BATCHED calls (one tick for a call that
serves every lane), which is what a bucket of the random-effect solver pays
for: one pass over the bucket's design.

The nested solvers still share the line search's trips among the lanes that
are running. ``minimize_lbfgs_lanes`` (the second half of this file) is the
flat loop a random-effect bucket runs: one evaluation a lane a trip, so the
batch's passes are its slowest lane's evaluations.
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
from photon_ml_tpu.optimize.lbfgs import (
    minimize_lbfgs_lanes,
    vmapped_evaluation,
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
        assert int(batched.hvps[e]) == int(alone.hvps), e
        assert bool(batched.converged[e]) == bool(alone.converged), e
        np.testing.assert_allclose(batched.w[e], alone.w, rtol=1e-9,
                                   atol=1e-12)
    assert int(batched.iterations[5]) == 0 and bool(batched.converged[5])
    assert int(batched.hvps[5]) == 0
    assert (int(jnp.sum(batched.hvps)) > 0) == (name == "tron")
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
    # and the lanes' own counts: the real lane's as alone (for TRON the
    # products the batch made), the finished lane's none
    assert int(pair.hvps[0]) == int(alone.hvps) == alone_products
    assert int(pair.hvps[1]) == 0


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


# --- the flat loop of a batch: minimize_lbfgs_lanes -------------------------

E2 = E + 2
ONE_ROW, HARD = E, E + 1
DTYPES = ["float32", "float64"]


def _floor_lanes(dtype):
    """``_lanes()`` and two more, in ``dtype``: a lane of one small row (its
    first gradient under 1, so the tolerance is absolute, as for most
    entities of a GAME bucket), and a hard one (column scales 0.03 to 100).
    In float32 at tolerance 1e-6 the one-row lane reaches the floor of an
    Armijo search on function values (a decrease under the value's last bit
    is refused): searches of over ten halvings, then two flat steps end it;
    the hard lane is stopped by the cap of 25 iterations."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(2, S, D)) * np.array(
        [[0.1, 0.3, 0.03, 1.0], [1.0, 30.0, 0.03, 100.0]])[:, None, :]
    planted = rng.normal(size=(2, D))
    p = 1.0 / (1.0 + np.exp(-np.einsum("esd,ed->es", x, planted)))
    y = (rng.random((2, S)) < p).astype(np.float64)
    weights = np.ones((2, S))
    weights[0, 1:] = 0.0
    x[0] *= weights[0][:, None]
    extra = (x, y, weights, np.ones(2))
    return tuple(jnp.asarray(np.concatenate([np.asarray(a), b]), dtype)
                 for a, b in zip(_lanes(), extra))


def _floor_config(dtype):
    return dataclasses.replace(
        CONFIG, tolerance=1e-6 if dtype == "float32" else 1e-9)


def _lane_fun(counter):
    """``fun(lane, w)`` of ``vmapped_evaluation`` over ``_objective``, the
    value kept in the lanes' dtype (the counter's zero is a float64)."""
    def fun(lane, w):
        f, g = _objective(counter, *lane)[0](w)
        return f.astype(w.dtype), g
    return fun


def _zeros(lanes):
    return jnp.zeros(lanes[0].shape[:1] + (D,), lanes[0].dtype)


def _flat(lanes, config, counter=None):
    """``(result, passes)`` of the flat loop from zero."""
    fun = _lane_fun(counter or Counter())
    result, loop = jax.jit(lambda l, w: minimize_lbfgs_lanes(
        lambda ls: vmapped_evaluation(fun, ls), l, w, config))(
            lanes, _zeros(lanes))
    return jax.block_until_ready((result, loop.passes))


def _nested(lanes, config, counter=None):
    """``vmap(minimize_lbfgs)`` on the same lanes: each lane's own solve,
    the line search's trips shared."""
    fun = _lane_fun(counter or Counter())
    solve = lambda lane, w: minimize_lbfgs(lambda v: fun(lane, v), w, config)
    return jax.block_until_ready(
        jax.jit(jax.vmap(solve))(lanes, _zeros(lanes)))


FIELDS = ("w", "value", "grad_norm", "iterations", "evaluations", "converged")


def _assert_same_bits(a, b, lanes_of_a=slice(None), lanes_of_b=slice(None)):
    for name in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, name))[lanes_of_a],
            np.asarray(getattr(b, name))[lanes_of_b], err_msg=name)


@pytest.mark.parametrize("dtype", DTYPES)
def test_flat_loop_gives_a_lane_what_it_gives_the_lane_alone(dtype):
    """Bit for bit, every field: a lane's result in the batch is its result
    in a batch in which it alone has data, so no lane moves another's
    iterates, and the batch's passes are then that lane's evaluations. (The
    lanes of the flat loop meet in no contraction: they are its arrays' last
    axis, and every operation on them is elementwise.)"""
    lanes = _floor_lanes(dtype)
    config = _floor_config(dtype)
    flat, _ = _flat(lanes, config)
    assert flat.w.dtype == jnp.dtype(dtype)
    # the lanes end in every way a lane can end
    its, evs = np.asarray(flat.iterations), np.asarray(flat.evaluations)
    conv = np.asarray(flat.converged)
    assert its[5] == 0 and evs[5] == 1 and conv[5]  # padded
    assert its[HARD] == config.max_iterations and not conv[HARD]  # the cap
    if dtype == "float64":
        assert its[4] == 1 and evs[4] == 2 + config.max_line_search  # failed
        assert conv[[1, 2, 3, ONE_ROW]].all()
    else:
        # the floor: two flat steps after searches of many halvings
        assert not conv[ONE_ROW] and its[ONE_ROW] < config.max_iterations
        assert evs[ONE_ROW] - 1 - its[ONE_ROW] >= 20
        assert not conv[4] and its[4] == 2
        assert evs[4] >= 2 + 2 * (config.max_line_search - 1)
        assert conv[[0, 1, 3]].all()
    assert len({int(i) for i in its}) >= 5

    for e in range(E2):
        keep = jnp.arange(E2) == e
        alone = tuple(a if i == 3 else a * keep.reshape(
            (-1,) + (1,) * (a.ndim - 1)).astype(a.dtype)
            for i, a in enumerate(lanes))
        one, passes = _flat(alone, config)
        _assert_same_bits(one, flat, slice(e, e + 1), slice(e, e + 1))
        assert int(passes) == int(flat.evaluations[e])
        others = np.arange(E2) != e
        assert (np.asarray(one.evaluations)[others] == 1).all()


@pytest.mark.parametrize("dtype", DTYPES)
def test_flat_loop_solves_each_lane_as_minimize_lbfgs_does(dtype):
    """Against ``minimize_lbfgs`` on each lane (under ``vmap``, and lane by
    lane unbatched). The rules and the order of a lane's operations are the
    same; a dot product over ``d`` is summed in another order (the module's
    comment says why), so last bits differ, as they do between the nested
    form under ``vmap`` and unbatched. So: the lanes that end before any
    curvature pair is used (padded, uphill, the one small row) agree bit for
    bit; every lane ends the same way; the lanes that converge agree to the
    tolerance of the solve, and take as many iterations give or take the few
    that a last bit near the end can move."""
    lanes = _floor_lanes(dtype)
    config = _floor_config(dtype)
    flat, _ = _flat(lanes, config)
    nested = _nested(lanes, config)
    early = np.array([4, 5, ONE_ROW])
    _assert_same_bits(flat, nested, early, early)
    np.testing.assert_array_equal(flat.converged, nested.converged)
    np.testing.assert_array_equal(
        np.asarray(flat.iterations) == config.max_iterations,
        np.asarray(nested.iterations) == config.max_iterations)
    rtol = 1e-6 if dtype == "float64" else 2e-3
    fun = _lane_fun(Counter())
    single = jax.jit(lambda lane, w: minimize_lbfgs(
        lambda v: fun(lane, v), w, config))
    for e in np.flatnonzero(np.asarray(flat.converged)):
        np.testing.assert_allclose(flat.w[e], nested.w[e], rtol=rtol,
                                   atol=rtol * 1e-2)
        np.testing.assert_allclose(flat.value[e], nested.value[e], rtol=rtol)
        assert abs(int(flat.iterations[e]) - int(nested.iterations[e])) <= 3
        alone = single(tuple(a[e] for a in lanes),
                       jnp.zeros(D, lanes[0].dtype))
        assert bool(alone.converged), e
        np.testing.assert_allclose(flat.w[e], alone.w, rtol=rtol,
                                   atol=rtol * 1e-2)


@pytest.mark.parametrize("dtype", DTYPES)
def test_flat_loops_passes_are_its_slowest_lanes_evaluations(dtype):
    """The objective's batched calls == the most evaluations any lane made
    == ``passes`` (the loop's own count), strictly under what the nested
    form runs on the same lanes: 1 + the sum of every iteration's longest
    search."""
    lanes = _floor_lanes(dtype)
    config = _floor_config(dtype)
    flat_counter, nested_counter = Counter(), Counter()
    flat, passes = _flat(lanes, config, flat_counter)
    nested = _nested(lanes, config, nested_counter)
    slowest = int(np.asarray(flat.evaluations).max())
    assert flat_counter.calls == slowest == int(passes)
    # the nested form: more than ITS slowest lane's, and than the flat loop's
    assert nested_counter.calls > int(np.asarray(nested.evaluations).max())
    assert nested_counter.calls > slowest
    if dtype == "float32":  # at the floor the nested form runs twice as many
        assert nested_counter.calls >= 2 * slowest


@pytest.mark.parametrize("dtype", DTYPES)
def test_a_failed_lanes_trial_points_are_its_own_trips(dtype):
    """The uphill lane's long searches (float64: one failed search of 26
    trial points; float32: 25 halvings to a flat step, twice) are trips of
    that lane alone. An easy lane beside it ends after its own evaluations
    with the result it has without the failing lane, the batch ends when
    the failing lane does, and the nested form pays the search on top of
    the easy lane's iterations."""
    full = _floor_lanes(dtype)
    config = _floor_config(dtype)
    pick = lambda idx: tuple(a[jnp.asarray(idx)] for a in full)
    # the same shape of batch, a padded lane in the failing lane's place
    without, passes_without = _flat(pick([3, 5, 5]), config)
    counter = Counter()
    with_failed, passes = _flat(pick([3, 5, 4]), config, counter)
    failed_evals = int(with_failed.evaluations[2])
    assert failed_evals >= 2 + config.max_line_search
    assert not bool(with_failed.converged[2])
    _assert_same_bits(with_failed, without, slice(0, 2), slice(0, 2))
    easy_evals = int(without.evaluations[0])
    assert int(passes_without) == easy_evals < failed_evals
    assert int(passes) == counter.calls == failed_evals
    nested_counter = Counter()
    _nested(pick([3, 5, 4]), config, nested_counter)
    assert nested_counter.calls >= failed_evals + easy_evals - 3


def test_a_padded_lane_makes_one_evaluation():
    """Weight 0, no data: converged at ``w0``. Beside real lanes it makes
    the evaluation at ``w0`` and no other; a batch of padded lanes never
    enters the loop."""
    lanes = _floor_lanes("float32")
    config = _floor_config("float32")
    flat, passes = _flat(lanes, config)
    assert int(flat.evaluations[5]) == 1 and int(flat.iterations[5]) == 0
    assert bool(flat.converged[5]) and int(passes) > 1
    np.testing.assert_array_equal(np.asarray(flat.w[5]), np.zeros(D))
    counter = Counter()
    padded = tuple(a[jnp.asarray([5, 5, 5])] for a in lanes)
    only, passes = _flat(padded, config, counter)
    assert int(passes) == counter.calls == 1
    assert np.asarray(only.evaluations).tolist() == [1, 1, 1]
    assert np.asarray(only.converged).all()


def test_flat_loop_keeps_the_trace_of_minimize_lbfgs():
    """``track_states``: a lane's recorded values and gradient norms are
    those of its own solve, written when its search ends: finite up to its
    last iteration, non-increasing, ending in its result, and the first
    iterations those of ``minimize_lbfgs``."""
    lanes = _floor_lanes("float64")
    config = dataclasses.replace(_floor_config("float64"), track_states=True)
    flat, _ = _flat(lanes, config)
    nested = _nested(lanes, config)
    assert flat.values.shape == nested.values.shape == (E2, 26)
    for e in range(E2):
        k = int(flat.iterations[e])
        values, norms = np.asarray(flat.values[e]), np.asarray(
            flat.grad_norms[e])
        assert np.isfinite(values[:k + 1]).all()
        assert np.isinf(values[k + 1:]).all() and np.isinf(norms[k + 1:]).all()
        assert (np.diff(values[:k + 1]) <= 0).all()  # accepted iterates only
        assert values[k] == np.float32(flat.value[e])
        assert norms[k] == np.float32(flat.grad_norm[e])
        shared = min(k, int(nested.iterations[e]), 5) + 1
        np.testing.assert_allclose(values[:shared],
                                   nested.values[e, :shared], rtol=1e-5)
