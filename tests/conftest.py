"""Test fixture: force JAX onto CPU with 8 virtual devices.

The moral equivalent of the reference's ``SparkTestUtils.sparkTest`` local[*]
fixture (``photon-test-utils/.../test/SparkTestUtils.scala``), provided by
the PUBLIC :mod:`photon_ml_tpu.testing` module (this repo eats its own
test-utils dog food): the *same* pjit/shard_map code paths used on a real
TPU slice run here on a simulated 8-device host mesh.

Must run before any backend resolves, hence at conftest import time.
``virtual_devices`` pins CPU through ``jax.config.update("jax_platforms",
"cpu")``, which holds whatever ``JAX_PLATFORMS`` said when jax was first
imported.
"""

from photon_ml_tpu.testing import virtual_devices

virtual_devices(8, force_cpu=True)

import jax  # noqa: E402

# x64 on the CPU test backend so finite-difference numeric checks are sharp;
# production code paths stay f32/bf16 on TPU.
jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Drop compiled-executable caches between test modules.

    The full suite compiles hundreds of distinct XLA CPU programs in one
    process; observed on this box (2026-07-31, jax 0.9.0): after ~25 min /
    a few hundred compilations the NEXT compile segfaults inside
    ``backend_compile_and_load`` — reproducibly, at whatever test happens
    to sit at that point in the ordering (three runs, three different
    victims, all mid-compile). Bounding per-process compile-cache state by
    clearing between modules keeps each module's peak well below the
    crash threshold; the cost is re-compiling shared helpers per module
    (~seconds each on CPU).
    """
    yield
    jax.clear_caches()
