"""What the TPU's compiler makes of the random-effect bucket solve, compiled
here for a described v5e chip (nothing runs; no chip is needed).

The flat L-BFGS loop of a bucket (``optimize/lbfgs.py::minimize_lbfgs_lanes``)
pays its algebra at every trip, so its arrays must be dense on the chip: the
lanes in the 128-lane dimension. With the lanes first the compiler puts ``d``
(8 for a random effect) there, 16 to 25 times the bytes, and a trip's algebra
then costs eight times the bucket's kernel (PERF.md, PR 29). Both tests are
in this one file and describe the topology inside a fixture: one process
loads the TPU's library.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.ops.regularization import L2Regularization
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.types import TaskType

E, S, D, M = 4096, 16, 8, 10


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its library is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def bucket_program(one_chip):
    """The compiled text of one kernel bucket's solve: the Pallas entity
    kernel's ``(E, d)`` operands are where the lanes-first layout comes
    from. The objective's gate asks for the backend's name, which is the
    CPU's here: the test answers for the chip it compiles for."""
    from photon_ml_tpu.game.random_effect import (
        RandomEffectSolver,
        _solve_bucket_impl,
    )

    solver = RandomEffectSolver(
        task=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(
                max_iterations=25, tolerance=1e-6, history=M,
                track_states=False)))
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    # float32 as on the chip: the suite's 64-bit mode is not Mosaic's
    with pytest.MonkeyPatch.context() as patch, \
            jax.enable_x64(False):
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(
            _solve_bucket_impl, static_argnames="solver").lower(
                solver, sds(E, S, D), sds(E, S), sds(E, S), sds(E, S),
                sds(E, D), sds()).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


LANES = r"40\d\d|41\d\d"  # E, or E padded to the kernel's block plan


def _layouts(text, shape):
    """The layouts (minor to major) given to float32 arrays of ``shape``
    (a pattern: the lane count is ``LANES``)."""
    return set(re.findall(rf"f32\[{shape}\]{{([0-9,]+):", text))


def test_the_buckets_histories_are_dense_on_the_chip(bucket_program):
    assert _layouts(bucket_program, f"{M},{D},(?:{LANES})") == {"2,1,0"}
    assert not _layouts(bucket_program, f"(?:{LANES}),{M},{D}")
    # the iterate, the gradient and the direction: lanes minor too
    assert _layouts(bucket_program, f"{D},(?:{LANES})") == {"1,0"}


def test_the_bucket_is_one_loop(bucket_program):
    """No loop inside the loop: one evaluation a trip."""
    assert len(re.findall(r" while\(", bucket_program)) == 1
