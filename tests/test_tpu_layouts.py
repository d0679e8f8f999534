"""What the TPU's compiler makes of the random-effect bucket solve, compiled
here for a described v5e chip (nothing runs; no chip is needed).

The flat L-BFGS loop of a bucket (``optimize/lbfgs.py::minimize_lbfgs_lanes``)
pays its algebra at every trip, so its arrays must be dense on the chip: the
lanes in the 128-lane dimension. With the lanes first the compiler puts ``d``
(8 for a random effect) there, 16 to 25 times the bytes, and a trip's algebra
then costs eight times the bucket's kernel (PERF.md, PR 29). The bucket's
entity kernel (``ops/pallas_re.py``) takes its operands entities-last for the
same reason, laid out once before the loop (PERF.md, PR 31). The tests are in
this one file and describe the topology inside a fixture: one process loads
the TPU's library.
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

E, S, D, M = 4000, 16, 8, 10  # E: the kernel's block plan pads it to 4096


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
    """The compiled text of one kernel bucket's solve. The objective's gate
    asks for the backend's name, which is the CPU's here: the test answers
    for the chip it compiles for."""
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


LANES = r"40\d\d"  # E, or E padded to the kernel's block plan


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


def _loop_body(text):
    """The text of the ``while`` loop's body and of every computation it
    calls (its fusions, the kernel's call), from the compiled module."""
    computations = dict(re.findall(
        r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S))
    (body,) = re.findall(r" while\(.*body=%?([\w.\-]+)", text)
    seen, todo = {}, [body]
    while todo:
        name = todo.pop()
        if name not in seen:
            seen[name] = computations[name]
            todo += re.findall(
                r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                seen[name])
    return "\n".join(seen.values())


def test_the_kernel_takes_the_bucket_entities_last(bucket_program):
    """The design reaches the kernel as ``(D, S, E)``, the entities in the
    128-lane dimension, dense; and the loop's body neither reads nor writes
    an array that holds ``D`` there (the bucket's ``(E, S, D)`` statics are
    laid out once, before the loop), nor stacks the columns of ``w`` or of
    the gradient: the kernel takes the loop's ``(d, E)`` iterate as it is."""
    kernel = re.findall(r"[^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*",
                        bucket_program)
    assert len(kernel) == 2  # the evaluation at w0, and the loop's
    for call in kernel:
        assert re.search(rf"operand_layout_constraints={{f32\[{D},{S},"
                         rf"(?:{LANES})\]{{2,1,0}}", call)
    assert _layouts(bucket_program, f"{D},{S},(?:{LANES})") == {"2,1,0"}
    body = _loop_body(bucket_program)
    assert "tpu_custom_call" in body
    assert not re.search(rf"f32\[(?:{LANES}),{S},{D}\]", body)
    assert not re.search(rf"f32\[(?:{LANES}),{D}\]", body)
    assert not re.search(rf"f32\[{D},(?:{LANES})\][^ ]* concatenate\(", body)
    assert not re.search(rf"f32\[{D},{S},(?:{LANES})\][^ ]* (?:copy|transpose)\(",
                         body)
