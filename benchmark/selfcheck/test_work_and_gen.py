"""The work functions against a hand count, and the generators against
themselves: on the CPU, at a small shape."""

import numpy as np
import pytest

from benchmark import manifest
from benchmark.gen import glm_dense
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
