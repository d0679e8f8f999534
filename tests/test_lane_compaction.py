"""The flat L-BFGS loop of a batch runs in stages that drop its ended lanes.

``optimize/lbfgs.py::minimize_lbfgs_lanes`` runs a bucket's lanes one
evaluation a lane a trip, in stages of the widths it is given; a bucket's
are its own and an eighth of it (``stage_widths``). When no more lanes run
than the next stage holds, the running lanes are gathered to its front with
their data, so a lane that has ended stops riding along. A lane's trip reads
its own column alone, so where it runs must not change it: every lane's
result is the single-stage loop's, bit for bit, in two stages or many, and
only ``lane_passes`` (the lanes the loop's evaluations were over) falls.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.glm import problem as problem_module
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.ops.design import DenseDesign
from photon_ml_tpu.ops.objective import GLMData
from photon_ml_tpu.ops.pallas_re import entity_pad
from photon_ml_tpu.ops.regularization import L2Regularization
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.optimize.lbfgs import (
    LANE_ROW,
    minimize_lbfgs_lanes,
    stage_widths,
    vmapped_evaluation,
)
from photon_ml_tpu.types import TaskType

FIELDS = ("w", "value", "grad_norm", "iterations", "evaluations", "converged")
ROUTES = {"closed_form": dict(fused=False),
          "pallas_interpreter": dict(fused_interpret=True)}


def _bucket(lanes, s=16, d=4, seed=0):
    """A float32 logistic bucket whose lanes need very different numbers of
    evaluations: column scales from 0.08 to 12, 1 to ``s`` real rows, at
    tolerance 1e-6 (some lanes converge in a few iterations, some reach
    float32's floor after long searches, some the cap)."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(lanes, s, d)) * np.exp(
        rng.uniform(-2.5, 2.5, size=(lanes, 1, d)))
    rows = rng.integers(1, s + 1, size=lanes)
    weights = (np.arange(s)[None, :] < rows[:, None]).astype(np.float32)
    x = x * weights[:, :, None]
    y = (rng.random((lanes, s)) < 0.5).astype(np.float32)
    offsets = rng.normal(size=(lanes, s)) * weights
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return GLMData(design=DenseDesign(x=f32(x)), labels=f32(y),
                   offsets=f32(offsets), weights=f32(weights))


def _solver(mesh=None, **route):
    from photon_ml_tpu.game.random_effect import RandomEffectSolver

    return RandomEffectSolver(
        task=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(
                max_iterations=25, tolerance=1e-6, track_states=False)),
        mesh=mesh, **route)


LAM = jnp.float32(1.0)


def _zeros(data):
    return jnp.zeros((data.labels.shape[0], data.dim), jnp.float32)


def test_stage_widths_narrow_once_to_an_eighth():
    assert stage_widths(1) == (1,)
    assert stage_widths(700) == (700,)
    assert stage_widths(8 * LANE_ROW - 1) == (8 * LANE_ROW - 1,)
    assert stage_widths(8 * LANE_ROW) == (8 * LANE_ROW, LANE_ROW)
    assert stage_widths(8209) == (8209, 1152)
    # the entity kernel's block plan at the cell's user bucket: the second
    # stage a width the plan takes as it is (blocks of 2,304 lanes)
    fit = lambda n: n + entity_pad(n, 56, 8, jnp.float32)
    assert stage_widths(fit(168_167), fit) == (168_192, 23_040)
    with pytest.raises(ValueError):
        minimize_lbfgs_lanes(lambda lanes: None, None,
                             jnp.zeros((300, 2)), widths=(300, 300))


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_compacted_lanes_are_the_single_stage_loops_bit_for_bit(
        route, monkeypatch):
    """640 lanes (five rows of 128) through ``run_lanes``, on the closed
    form under ``vmap`` and on the entity kernel: stages of 640 and 128, and
    of 640, 384, 256 and 128, against one stage of 640 (what ``run_lanes``
    runs for a batch under eight rows), every field of every lane equal to
    the bit. The closed form runs under ``vmap`` at each stage's width and
    the kernel is laid out again for each stage: neither moves a lane's
    sums."""
    data = _bucket(640)
    problem_ = _solver(**ROUTES[route])._problem()
    kernel = problem_.objective.entity_kernel_fit(data)
    assert (kernel is not None) == (route == "pallas_interpreter")
    assert stage_widths(640, kernel) == (640,)
    single, one = jax.jit(problem_.run_lanes)(data, _zeros(data), LAM)
    runs = {}
    for widths in ((640, 128), (640, 384, 256, 128)):
        with monkeypatch.context() as patch:
            patch.setattr(problem_module, "stage_widths",
                          lambda n, fit=None, widths=widths: widths)
            runs[len(widths)] = jax.jit(problem_.run_lanes)(
                data, _zeros(data), LAM)
    (staged, loop), (many, four) = runs[2], runs[4]
    for other in (single, many):
        for name in FIELDS:
            np.testing.assert_array_equal(getattr(staged, name),
                                          getattr(other, name), err_msg=name)
    evaluations = np.asarray(staged.evaluations)
    assert evaluations.max() >= 3 * np.median(evaluations)
    assert int(loop.passes) == int(one.passes) == int(four.passes) \
        == evaluations.max()
    assert (int(one.compactions), int(loop.compactions),
            int(four.compactions)) == (0, 1, 3)
    assert int(one.lane_passes) == 640 * int(one.passes)
    assert evaluations.sum() <= int(four.lane_passes) \
        < int(loop.lane_passes) < 0.6 * int(one.lane_passes)


def test_lane_passes_are_each_stages_width_times_its_trips():
    """Every call of the evaluation says how many lanes it is over: the
    first ``E`` (the evaluation at ``w0``), then each stage's width once a
    trip, stage after stage (700, 384, 256 and 128 lanes here).
    ``lane_passes`` is their sum, ``passes`` their number, ``compactions``
    the boundaries crossed."""
    data = _bucket(700, seed=3)
    seen = []

    def tick(f):
        seen.append(int(f.shape[0]))
        return np.zeros((), np.float32)

    def evaluation_of(lanes):
        evaluate = vmapped_evaluation(
            lambda lane, w: problem_.objective.value_and_grad(w, lane, LAM),
            lanes)

        def counted(w):
            f, g = evaluate(w)
            return f + jax.pure_callback(
                tick, jax.ShapeDtypeStruct((), jnp.float32), f), g
        return counted

    problem_ = _solver(fused=False)._problem()
    widths = (700, 384, 256, 128)
    result, loop = jax.jit(lambda d, w: minimize_lbfgs_lanes(
        evaluation_of, d, w, problem_.config.optimizer_config, widths))(
            data, _zeros(data))
    jax.block_until_ready(result)
    assert seen[0] == 700 and len(seen) == int(loop.passes)
    assert sum(seen) == int(loop.lane_passes)
    trips = [seen[1:].count(w) for w in widths]
    assert sum(trips) == int(loop.passes) - 1
    assert [w for w in seen[1:]] == [w for w, n in zip(widths, trips)
                                     for _ in range(n)]
    assert int(loop.lane_passes) == 700 + sum(
        w * n for w, n in zip(widths, trips))
    assert int(loop.compactions) == len(widths) - 1
    assert trips[-1] > 0 and trips[0] > 0
    assert int(loop.passes) == int(np.asarray(result.evaluations).max())


@pytest.mark.parametrize("lanes", [1, 8 * LANE_ROW - 1])
def test_a_batch_under_eight_rows_is_one_stage(lanes):
    data = _bucket(lanes, seed=5)
    problem_ = _solver(fused=False)._problem()
    result, loop = jax.jit(problem_.run_lanes)(data, _zeros(data), LAM)
    assert int(loop.compactions) == 0
    assert int(loop.lane_passes) == lanes * int(loop.passes)
    assert int(loop.passes) == int(np.asarray(result.evaluations).max())


def test_each_shard_of_an_entity_mesh_compacts_its_own_lanes():
    """A bucket of 2,048 lanes on two devices under ``shard_map``: each
    device runs stages of 1,024 and 128 over its half, with no word to the
    other. Every lane's result is the one-device solve's (stages of 2,048
    and 256) to the bit; the bucket's ``lane_passes`` are the two halves' own,
    summed, its ``passes`` the longer half's."""
    from photon_ml_tpu.game.random_effect import _solve_bucket_impl
    from photon_ml_tpu.parallel.mesh import ENTITY_AXIS, make_mesh

    data = _bucket(2048, seed=7)
    args = (data.design.x, data.labels, data.offsets, data.weights)
    solve = jax.jit(_solve_bucket_impl, static_argnames="solver")

    def run(solver, lanes=slice(None)):
        return solve(solver, *(a[lanes] for a in args),
                     _zeros(data)[lanes], LAM)

    mesh = make_mesh({ENTITY_AXIS: 2}, devices=jax.devices()[:2])
    w, _, converged, counts = run(_solver(mesh=mesh, fused=False))
    w1, _, converged1, counts1 = run(_solver(fused=False))
    np.testing.assert_array_equal(w, w1)
    np.testing.assert_array_equal(converged, converged1)
    halves = [run(_solver(fused=False), slice(k * 1024, (k + 1) * 1024))[3]
              for k in range(2)]
    assert int(counts["lane_passes"]) == sum(
        int(h["lane_passes"]) for h in halves)
    assert int(counts["passes"]) == max(int(h["passes"]) for h in halves) \
        == int(counts1["passes"])
    assert int(counts["compactions"]) == max(
        int(h["compactions"]) for h in halves) == 1
    assert int(counts1["compactions"]) == 1
    for name in ("evaluations", "iterations", "converged", "lanes"):
        assert int(counts[name]) == int(counts1[name]) == sum(
            int(h[name]) for h in halves), name
