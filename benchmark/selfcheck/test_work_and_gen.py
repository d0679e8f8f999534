"""The work functions against a hand count, and the generators against
themselves: on the CPU, at a small shape."""

import numpy as np
import pytest

from benchmark import manifest
from benchmark.gen import glm_dense, glm_dense_stacked
from benchmark.work.common import least_seconds
from benchmark.work import glm as glm_work

GLM_WL = dict(rows_per_chip=600, row_chunk=200, nnz_per_row=64,
              column_scale_decades=[-2.0, 1.0])
GLM_CFG = dict(dim=1024)


def test_glm_pass_work_hand_count():
    # 10 rows x 4 columns of float32: X w is 10*4 multiply-adds, X' r as
    # many, 2 operations each: 160, plus 8 a row for the pointwise loss; the
    # design is read once (160 B), three per-row vectors (120 B), w read and
    # the gradient written (32 B)
    assert glm_work.pass_work(10, 4) == (160.0 + 80.0, 160.0 + 120.0 + 32.0)
    assert glm_work.solve_passes(80) == 81
    peaks = manifest.peaks("TPU v5 lite")
    least, bound = least_seconds(
        *glm_work.pass_work(1_500_000, 1024), peaks)
    assert bound == "bandwidth"
    assert least == pytest.approx(6.162e9 / 819e9, rel=1e-3)


def test_glm_generator_repeats_from_a_seed_and_differs_across_seeds():
    big = 2**31 + 12345  # the driver's seeds pass 32 signed bits
    a = glm_dense.generate(big, GLM_WL, GLM_CFG)
    b = glm_dense.generate(big, GLM_WL, GLM_CFG)
    c = glm_dense.generate(big + 1, GLM_WL, GLM_CFG)
    d = glm_dense.generate(12345, GLM_WL, GLM_CFG)
    assert a["x"].shape == (600, 1024) and a["y"].shape == (600,)
    assert np.array_equal(a["x"], b["x"]) and np.array_equal(a["y"], b["y"])
    assert not np.array_equal(a["x"], c["x"])
    assert not np.array_equal(a["x"], d["x"])  # the high bits count
    # the seed draws the rows, the column scales and the planted model
    assert not np.array_equal(np.sort(np.abs(np.asarray(a["x"])).max(axis=0)),
                              np.sort(np.abs(np.asarray(c["x"])).max(axis=0)))
    nnz = float(np.mean(np.sum(np.asarray(a["x"]) != 0, axis=1)))
    assert 56 < nnz < 72
    assert 0.2 < float(np.mean(a["y"])) < 0.8


FIXED_WL = dict(GLM_WL, problem_seed=77)


def test_a_fixed_problem_leaves_the_seed_only_the_column_signs():
    """With a ``problem_seed`` every seed holds the same problem mirrored in
    some columns: the same labels, the same sizes of every entry, and an
    objective that reads bit for bit the same at the mirrored point, so that
    every seed's solve takes the same steps and the same number of them."""
    import jax.numpy as jnp

    from benchmark.reference import glm as reference

    big = 2**31 + 12345
    a = glm_dense.generate(big, FIXED_WL, GLM_CFG)
    b = glm_dense.generate(big, FIXED_WL, GLM_CFG)
    c = glm_dense.generate(7, FIXED_WL, GLM_CFG)
    xa, xc = np.asarray(a["x"]), np.asarray(c["x"])
    assert np.array_equal(xa, b["x"]) and np.array_equal(a["y"], b["y"])
    assert np.array_equal(a["y"], c["y"])
    assert np.array_equal(np.abs(xa), np.abs(xc))
    used = np.abs(xa).sum(axis=0) > 0
    flipped = np.array([np.array_equal(xa[:, j], -xc[:, j])
                        for j in range(1024)])
    kept = np.array([np.array_equal(xa[:, j], xc[:, j]) for j in range(1024)])
    assert np.all((flipped ^ kept)[used])
    assert 0.3 < flipped[used].mean() < 0.7
    # another problem_seed is another problem
    other = glm_dense.generate(big, dict(FIXED_WL, problem_seed=78), GLM_CFG)
    assert not np.array_equal(np.abs(xa), np.abs(np.asarray(other["x"])))
    # the objective at w on one seed and at the mirrored w on the other
    s = np.where(flipped, -1.0, 1.0).astype(np.float32)
    w = np.random.default_rng(0).normal(size=1024).astype(np.float32) * 0.1
    fa, ga = reference.value_and_grad(a["x"], a["y"], jnp.asarray(w),
                                      jnp.float32(1.0), chunk=200)
    fc, gc = reference.value_and_grad(c["x"], c["y"], jnp.asarray(w * s),
                                      jnp.float32(1.0), chunk=200)
    assert float(fa) == float(fc)
    assert np.array_equal(np.asarray(ga), np.asarray(gc) * s)


def test_stacked_generator_draws_every_chip_its_own_rows():
    """The stacked layout: block ``i`` on device ``i``, the column scales
    shared, no two chips the same rows, the whole from the seed."""
    import jax
    from jax.sharding import Mesh

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    big = 2**31 + 12345
    a = glm_dense_stacked.generate(big, GLM_WL, GLM_CFG, mesh)
    b = glm_dense_stacked.generate(big, GLM_WL, GLM_CFG, mesh)
    c = glm_dense_stacked.generate(big + 1, GLM_WL, GLM_CFG, mesh)
    assert a["x"].shape == (4, 600, 1024) and a["y"].shape == (4, 600)
    for arr in (a["x"], a["y"]):
        assert [s.index[0] for s in arr.addressable_shards] \
            == [slice(i, i + 1) for i in range(4)]
        assert [s.device for s in arr.addressable_shards] \
            == list(mesh.devices.flat)
    x = np.asarray(a["x"])
    assert np.array_equal(x, b["x"]) and np.array_equal(a["y"], b["y"])
    assert not np.array_equal(x, c["x"])
    for i in range(4):
        for j in range(i):
            assert not np.array_equal(x[i], x[j])
    # one set of column scales over three decades: a column's largest entry
    # (of about 37 non-zeros a chip) is large or small on every chip alike
    top = np.log(np.abs(x).max(axis=1))
    for i in range(1, 4):
        assert np.corrcoef(top[0], top[i])[0, 1] > 0.98
    nnz = float(np.mean(np.sum(x != 0, axis=-1)))
    assert 56 < nnz < 72
    assert 0.2 < float(np.mean(a["y"])) < 0.8
    # a fixed problem: every chip mirrors the same columns, labels unmoved
    d = glm_dense_stacked.generate(big, FIXED_WL, GLM_CFG, mesh)
    e = glm_dense_stacked.generate(7, FIXED_WL, GLM_CFG, mesh)
    xd, xe = np.asarray(d["x"]), np.asarray(e["x"])
    assert np.array_equal(d["y"], e["y"]) and not np.array_equal(xd, xe)
    assert np.array_equal(np.abs(xd), np.abs(xe))
    sign = np.sign((xd * xe).sum(axis=1))  # per chip, per column
    for i in range(1, 4):
        both = (sign[0] != 0) & (sign[i] != 0)
        assert np.array_equal(sign[0][both], sign[i][both])
