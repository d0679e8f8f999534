"""The wide sparse GLM path at a small size: the one build of
``ChunkedSparseDesign`` against ``CsrDesign``, the whole solve against the
benchmark's plain sparse reference, and ``_to_glm_data`` reaching that build.

The tests run on the CPU with x64 on (``conftest.py``): the program then
carries float64 coefficients over float32 entries while the reference is
float32 throughout, so every tolerance below is a float32 one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import glm_sparse as reference
from benchmark.reference.lbfgs import lbfgs
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.glm.training import train_glm_sweep
from photon_ml_tpu.ops.design import (
    BUILD_SPAN,
    ChunkedSparseDesign,
    CsrDesign,
    design_kind,
)
from photon_ml_tpu.ops.objective import GLMData
from photon_ml_tpu.ops.regularization import L2Regularization
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.telemetry import tracing
from photon_ml_tpu.types import OptimizerType, TaskType

ROWS, DIM, WIDTH = 20_000, 5_003, 39


def _records(fn):
    """``fn()`` and the span records completed meanwhile."""
    records = []
    untap = tracing.GLOBAL_TRACER.add_tap(records.append)
    try:
        out = fn()
        tracing.flush()
    finally:
        untap()
    return out, records


@pytest.fixture(scope="module")
def problem():
    """The cell's shape: one active bin a field, Zipf ids hashed into ``DIM``
    bins (over ``DENSE_MAX_DIM``), every tenth row's first two fields forced
    into one bin, and one bin that holds a third of the rows."""
    rng = np.random.default_rng(7)
    sizes = np.r_[[64] * 13, [1460, 583, 10131227, 2202608, 305, 24, 12517,
                              633, 3, 93145, 5683, 8351593, 3194, 27, 14992,
                              5461306, 10, 5652, 2173, 4, 7046547, 18, 15,
                              286181, 105, 142572]]
    rank = np.floor(sizes ** rng.random((ROWS, WIDTH))).astype(np.int64)
    cols = ((rank * 2654435761 + np.arange(WIDTH) * 40503) % DIM
            ).astype(np.int32)
    cols[::10, 1] = cols[::10, 0]
    cols[::3, 2] = 11
    sign = np.where(rng.random(DIM) < 0.5, 1.0, -1.0).astype(np.float32)
    vals = sign[cols]
    w_true = (0.3 * rng.normal(size=DIM) * sign).astype(np.float32)
    m = (vals * w_true[cols]).sum(axis=1) - 1.0
    y = (rng.random(ROWS) < 1.0 / (1.0 + np.exp(-m))).astype(np.float32)
    assert (np.bincount(cols.reshape(-1), minlength=DIM)[11]
            >= ROWS // 3)
    return cols, vals, y


@pytest.fixture(scope="module")
def solved(problem):
    cols, vals, y = problem
    rows = np.repeat(np.arange(ROWS, dtype=np.int32), WIDTH)
    design = ChunkedSparseDesign.from_coo(
        rows, cols.reshape(-1), vals.reshape(-1), ROWS, DIM)
    data = GLMData(design=design, labels=jnp.asarray(y),
                   offsets=jnp.zeros(ROWS, jnp.float32),
                   weights=jnp.ones(ROWS, jnp.float32))
    config = GLMOptimizationConfiguration(
        optimizer=OptimizerType.LBFGS, regularization=L2Regularization,
        optimizer_config=OptimizerConfig(max_iterations=80, tolerance=1e-6,
                                         history=10, max_line_search=25))
    trained, records = _records(lambda: train_glm_sweep(
        TaskType.LOGISTIC_REGRESSION, data, [1.0], config))
    result = trained[0].result
    fun = reference.objective(jnp.asarray(cols), jnp.asarray(vals),
                              jnp.asarray(y), 1.0, block=5_000)
    ref = lbfgs(fun, np.zeros(DIM), max_iterations=80, tolerance=1e-6,
                history=10, max_line_search=25)
    return result, ref, fun, records


class TestSolveAgainstThePlainReference:
    def test_first_gradient(self, solved):
        result, ref, _, _ = solved
        # one float32 pass over 780,000 entries, the hot bin's 6,667 among
        # them, against the same sum in float64: 1e-6 of the norm
        assert float(result.grad_norms[0]) == pytest.approx(
            ref["grad_norms"][0], rel=1e-5)

    def test_losses_after_the_first_iterations(self, solved):
        result, ref, _, _ = solved
        # the same steps from the same start: a float32 loss of 20,000 rows
        for k in (1, 2, 3):
            assert float(result.values[k]) == pytest.approx(
                ref["values"][k], rel=2e-5)

    def test_final_loss(self, solved):
        result, ref, _, _ = solved
        assert int(result.iterations) > 3
        # both paths stop near the optimum, where the loss is flat: their
        # last steps differ by rounding, the loss by far less than 1e-4
        assert float(result.value) == pytest.approx(ref["values"][-1],
                                                    rel=1e-4)

    def test_kkt_at_the_programs_answer(self, solved):
        result, ref, fun, _ = solved
        f, g = fun(jnp.asarray(result.w, jnp.float32))
        g0 = ref["grad_norms"][0]
        # the reference's own value and gradient at the program's w: the
        # loss to float32, the gradient norm to 1e-5 of the first gradient's
        assert float(result.value) == pytest.approx(float(f), rel=2e-5)
        assert abs(float(result.grad_norm)
                   - float(jnp.linalg.norm(g))) <= 1e-5 * g0

    def test_sweep_span_names_the_design(self, solved):
        _, _, _, records = solved
        sweep = [r for r in records if r["name"] == "glm.sweep"]
        assert [r["design"] for r in sweep] == ["chunked_sparse"]


def _case(name):
    rng = np.random.default_rng(3)
    n, d = 61, 47
    mask = rng.random((n, d)) < 0.15
    r, c = np.nonzero(mask)
    v = rng.normal(size=len(r)).astype(np.float32)
    if name == "duplicates":
        r, c = np.r_[r, r[:40]], np.r_[c, c[:40]]
        v = np.r_[v, rng.normal(size=40).astype(np.float32)]
    elif name == "empty_rows":
        keep = ~np.isin(r, (0, 5, n - 1))
        r, c, v = r[keep], c[keep], v[keep]
    elif name == "empty_columns":
        keep = ~np.isin(c, (0, 7, d - 1))
        r, c, v = r[keep], c[keep], v[keep]
    elif name == "explicit_zeros":
        v = v.copy()
        v[::4] = 0.0
    elif name == "unordered":
        order = rng.permutation(len(r))
        r, c, v = r[order], c[order], v[order]
    elif name == "hot_column":
        r = np.r_[r, np.arange(n)]
        c = np.r_[c, np.full(n, 3)]
        v = np.r_[v, np.ones(n, np.float32)]
    return r.astype(np.int32), c.astype(np.int32), v, n, d


@pytest.mark.parametrize("chunks", [(None, None), (8, 8), (16, 24), (128, 8)])
@pytest.mark.parametrize("case", ["plain", "duplicates", "empty_rows",
                                  "empty_columns", "explicit_zeros",
                                  "unordered", "hot_column"])
def test_the_build_agrees_with_csr(case, chunks):
    r, c, v, n, d = _case(case)
    built = ChunkedSparseDesign.from_coo(r, c, v, n, d, *chunks)
    csr = CsrDesign(rows=jnp.asarray(r), cols=jnp.asarray(c),
                    values=jnp.asarray(v), n_rows=n, n_cols=d)
    squared = CsrDesign(rows=csr.rows, cols=csr.cols,
                        values=jnp.square(csr.values), n_rows=n, n_cols=d)
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=d), jnp.float32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    close = lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    close(built.matvec(w), csr.matvec(w))
    close(built.rmatvec(g), csr.rmatvec(g))
    close(built.rmatvec_squared(g), squared.rmatvec(g))
    # int32 and float32 throughout; padding holds value 0; a dropped zero
    # takes no slot
    assert (built.rcols.dtype == built.fcols.dtype == built.crows.dtype
            == jnp.int32)
    assert (built.rvals.dtype == built.fvals.dtype == built.cvals.dtype
            == jnp.float32)
    live = int(np.count_nonzero(v))
    assert _row_entries(built) == live
    assert int(jnp.count_nonzero(built.cvals)) == live
    if chunks[0] is not None:  # a caller's widths: the row side's both
        assert built.fvals.shape[-1] == built.fold * chunks[0]
        assert built.rvals.shape[-1] == chunks[0]
        assert built.cvals.shape[-1] == chunks[1]


def _row_entries(built):
    return int(jnp.count_nonzero(built.fvals)
               + jnp.count_nonzero(built.rvals))


def _one_hot_case(name):
    """Skewed one-hot entries: a bin's entries all carry the bin's sign."""
    rng = np.random.default_rng(7)
    n, d, e = 97, 300, 2000
    r = rng.integers(0, n, e)
    c = np.minimum((d ** rng.random(e)).astype(int) - 1, d - 1)
    v = np.where(rng.random(d) < 0.5, 1.0, -1.0)[c]
    if name == "duplicates":  # a row's second entry in a busy bin
        r, c, v = np.r_[r, r[:400]], np.r_[c, c[:400]], np.r_[v, v[:400]]
    elif name == "explicit_zeros":
        v = v.copy()
        v[::5] = 0.0
    elif name == "mixed_values":  # the busiest bin's entries differ: no plane
        v = v.copy()
        v[c == 0] = rng.normal(size=int((c == 0).sum()))
    return (r.astype(np.int32), c.astype(np.int32), v.astype(np.float32),
            n, d)


@pytest.mark.parametrize("hot", [1, 40, 256, 300])
@pytest.mark.parametrize("case", ["plain", "duplicates", "explicit_zeros",
                                  "mixed_values"])
def test_busy_bins_as_planes_agree_with_csr(case, hot):
    r, c, v, n, d = _one_hot_case(case)
    built, records = _records(
        lambda: ChunkedSparseDesign.from_coo(r, c, v, n, d, hot_columns=hot))
    plain = ChunkedSparseDesign.from_coo(r, c, v, n, d, hot_columns=0)
    csr = CsrDesign(rows=jnp.asarray(r), cols=jnp.asarray(c),
                    values=jnp.asarray(v), n_rows=n, n_cols=d)
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.normal(size=d), jnp.float32)
    g = jnp.asarray(rng.normal(size=n), jnp.float32)
    close = lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-4)
    close(built.matvec(w), csr.matvec(w))
    close(built.rmatvec(g), csr.rmatvec(g))
    close(built.rmatvec_squared(g), plain.rmatvec_squared(g))
    # every entry once: in a plane or in the chunks of either side
    (record,) = [x for x in records if x["name"] == BUILD_SPAN]
    live = int(np.count_nonzero(v))
    assert record["entries"] == live and record["hot_columns"] % 256 == 0
    in_planes = sum(int(jax.lax.population_count(p).sum())
                    for p in (built.hot_by_row, built.hot_by_bin))
    assert in_planes == 2 * record["hot_entries"] > 0
    assert _row_entries(built) == live - record["hot_entries"]
    assert int(jnp.count_nonzero(built.cvals)) == live - record["hot_entries"]
    # a plane's bin has one value, and a (row, bin) pair one bit
    busy = np.asarray(built.hot_cols)[np.asarray(built.hot_vals) != 0]
    assert len(set(busy)) == len(busy) <= hot
    pairs = {(a, b) for a, b, x in zip(r, c, v) if x != 0 and b in set(busy)}
    assert record["hot_entries"] == len(pairs)
    if case == "mixed_values":
        assert 0 not in set(busy)
    # the margins differentiate and batch like any other contraction
    close(jax.grad(lambda w: jnp.vdot(built.matvec(w), g))(w), csr.rmatvec(g))
    close(jax.vmap(built.matvec)(jnp.stack([w, -w]))[1], -csr.matvec(w))


def test_a_small_design_keeps_every_bin_in_the_chunks():
    r, c, v, n, d = _one_hot_case("plain")
    built = ChunkedSparseDesign.from_coo(r, c, v, n, d)
    assert built.hot_cols is None and built.hot_by_row is None
    assert _row_entries(built) == int(np.count_nonzero(v))


def test_build_record_carries_the_sizes():
    r, c, v, n, d = _case("explicit_zeros")
    built, records = _records(
        lambda: ChunkedSparseDesign.from_coo(r, c, v, n, d, 8, 16))
    (record,) = [x for x in records if x["name"] == BUILD_SPAN]
    assert record["rows"] == n and record["dim"] == d
    assert record["entries"] == int(np.count_nonzero(v))
    assert (record["row_chunk"], record["col_chunk"]) == (8, 16)
    assert record["row_overflow_chunk"] == 8
    # every stored slot of the row side: first chunks and overflow chunks
    assert record["row_slots"] == built.fvals.size + built.rvals.size
    assert record["row_slots"] == n * 8 + 8 * int(np.sum(np.maximum(
        -(-np.bincount(r[v != 0], minlength=n) // 8) - 1, 0)))
    assert record["col_slots"] == built.cvals.size
    assert record["seconds"] > 0
    assert design_kind(built) == "chunked_sparse"


def test_to_glm_data_reaches_the_one_build():
    """A shard wider than ``DENSE_MAX_DIM`` goes through ``from_coo``: its
    ``design.build`` record says so."""
    from photon_ml_tpu.cli.train_glm import DENSE_MAX_DIM, _to_glm_data
    from photon_ml_tpu.game.data import FeatureShard, GameData

    n, d = 50, DENSE_MAX_DIM + 5
    rng = np.random.default_rng(5)
    rows = np.repeat(np.arange(n), 4)
    cols = rng.integers(0, d, size=len(rows))
    vals = rng.normal(size=len(rows)).astype(np.float32)
    data = GameData.build(
        labels=(rng.random(n) < 0.5).astype(np.float32),
        shards={"wide": FeatureShard.from_coo(rows, cols, vals, n, d)})
    glm, records = _records(lambda: _to_glm_data(data, "wide"))
    assert isinstance(glm.design, ChunkedSparseDesign)
    (record,) = [x for x in records if x["name"] == BUILD_SPAN]
    assert (record["rows"], record["dim"]) == (n, d)
    assert record["entries"] == int(np.count_nonzero(vals))
    dense = np.zeros((n, d), np.float32)
    np.add.at(dense, (rows, cols), vals)
    w = rng.normal(size=d).astype(np.float32)
    np.testing.assert_allclose(np.asarray(glm.design.matvec(jnp.asarray(w))),
                               dense @ w, rtol=1e-4, atol=1e-5)


def test_a_rows_first_chunk_takes_no_segment_sum():
    r, c, v, n, d = _case("empty_rows")
    per_row = np.bincount(r, minlength=n)
    fits = ChunkedSparseDesign.from_coo(r, c, v, n, d, 16, 8)
    assert int(per_row.max()) <= 16
    assert fits.rows_first and fits.fvals.shape == (n, 16)
    assert fits.rvals.shape == (0, 16) and fits.rrow.shape == (0,)
    text = jax.jit(fits.matvec.__func__).lower(
        fits, jnp.zeros(d, jnp.float32)).as_text()
    assert "scatter" not in text
    # a row wider than the chunk: its first chunk still at its own place,
    # the others, and only those, summed by row
    split = ChunkedSparseDesign.from_coo(r, c, v, n, d, 8, 8)
    assert split.rows_first and int(per_row.max()) > 8
    beyond = np.maximum(np.ceil(per_row / 8).astype(int) - 1, 0)
    assert split.fvals.shape == (n, 8) and split.fold == 1
    assert split.rvals.shape == (beyond.sum(), 8)
    assert np.array_equal(np.asarray(split.rrow),
                          np.repeat(np.arange(n), beyond))
    w = jnp.asarray(np.random.default_rng(2).normal(size=d), jnp.float32)
    np.testing.assert_allclose(np.asarray(fits.matvec(w)),
                               np.asarray(split.matvec(w)), rtol=1e-5,
                               atol=1e-5)
    # a caller that stacks blocks gets the chunks of non-empty rows alone
    lay = ChunkedSparseDesign.layout_numpy(r, c, v, n, d, row_chunk=8,
                                           col_chunk=8)
    assert not lay["rows_first"] and "fvals" not in lay
    assert lay["rvals"].shape[0] == int(np.ceil(per_row / 8).sum())


def _runs_case(c, n, duplicates, hot):
    """Rows of 0, 1, C, C + 1 and 3C + 2 entries in turn, distinct bins a
    row but for ``duplicates`` (a row's entries again, other values), and
    with ``hot`` a bin that every other row holds at value 1; explicit zeros
    up to 4,096 entries in 300 bins, so that every case shares the build's
    compiled programs."""
    rng = np.random.default_rng(c * 100 + n)
    d, e = 300, 4096
    sizes = np.resize([0, 1, c, c + 1, 3 * c + 2], n)
    r = np.repeat(np.arange(n), sizes)
    cols = np.concatenate([rng.choice(np.arange(1, d), k, replace=False)
                           for k in sizes])
    v = rng.normal(size=len(r))
    if duplicates:
        twice = rng.random(len(r)) < 0.3
        r, cols = np.r_[r, r[twice]], np.r_[cols, cols[twice]]
        v = np.r_[v, rng.normal(size=int(twice.sum()))]
    if hot:
        r = np.r_[r, np.arange(0, n, 2)]
        cols = np.r_[cols, np.zeros(len(range(0, n, 2)), int)]
        v = np.r_[v, np.ones(len(range(0, n, 2)))]
    pad = e - len(r)
    r = np.r_[r, rng.integers(0, n, pad)]
    cols, v = np.r_[cols, rng.integers(0, d, pad)], np.r_[v, np.zeros(pad)]
    order = rng.permutation(e)
    return (r[order].astype(np.int32), cols[order].astype(np.int32),
            v[order].astype(np.float32), n, d)


@pytest.mark.parametrize("n", [101, 96])
@pytest.mark.parametrize("case", ["plain", "duplicates", "planes",
                                  "duplicates_planes"])
@pytest.mark.parametrize("widths", [(11, 4), (12, 12), (10, 2), (16, 16),
                                    (3, 5), (1, 1)], ids=str)
def test_the_row_layout_equals_a_dense_design(widths, case, n, monkeypatch):
    """Every row's first chunk C wide, ``fold`` rows a lane column so that
    ``fold x C`` is a multiple of 8, the rest in overflow chunks O wide:
    the three contractions equal a dense design's, at an ``n`` the fold
    divides and at one it does not."""
    c, o = widths
    monkeypatch.setattr(ChunkedSparseDesign, "row_widths",
                        staticmethod(lambda counts: (c, o)))
    r, cols, v, n, d = _runs_case(c, n, "duplicates" in case,
                                  "planes" in case)
    built, records = _records(lambda: ChunkedSparseDesign.from_coo(
        r, cols, v, n, d, hot_columns=8 if "planes" in case else 0))
    dense = np.zeros((n, d))
    squared = np.zeros((n, d))
    np.add.at(dense, (r, cols), v.astype(np.float64))
    np.add.at(squared, (r, cols), np.square(v.astype(np.float64)))
    rng = np.random.default_rng(1)
    w = rng.normal(size=d)
    g = rng.normal(size=n)
    close = lambda a, b: np.testing.assert_allclose(
        np.asarray(a), b, rtol=1e-5, atol=1e-5)
    three = jax.jit(lambda x, w, g: (x.matvec(w), x.rmatvec(g),
                                     x.rmatvec_squared(g)))(
        built, jnp.asarray(w, jnp.float32), jnp.asarray(g, jnp.float32))
    for got, want in zip(three, (dense @ w, dense.T @ g, squared.T @ g)):
        close(got, want)
    fold = 8 // np.gcd(c, 8)
    assert built.fold == fold and built.fvals.shape == (-(-n // fold),
                                                        fold * c)
    assert built.rvals.shape[-1] == o
    (record,) = [x for x in records if x["name"] == BUILD_SPAN]
    assert (record["row_chunk"], record["row_overflow_chunk"]) == (c, o)
    if "planes" in case:
        assert record["hot_entries"] > 0
    live = v != 0
    assert _row_entries(built) == int(live.sum()) - record["hot_entries"]
    if "planes" not in case:
        over = np.maximum(np.bincount(r[live], minlength=n) - c, 0)
        assert built.rvals.shape[0] == int(np.sum(-(-over // o)))


#: remaining entries a row of the sparse cell's 5,000,000 after the planes,
#: per million, 0 to 17 of them (ISSUE 36, redrawn from the generator's law)
CELL_COUNTS = [2, 31, 255, 1612, 7753, 25809, 64470, 122175, 179096, 203457,
               178403, 121776, 62424, 24340, 6806, 1413, 167, 11]
#: what the width rule makes of them at the chip's rates (PERF.md, section 6)
CELL_WIDTHS = (10, 2)


@pytest.mark.parametrize("counts, widths", [
    (np.repeat(np.arange(18), CELL_COUNTS), CELL_WIDTHS),
    (np.full(1000, 16), (16, 16)),  # flat: the median's width, as before
    (np.full(1000, 8), (8, 8)),
    (np.r_[np.full(999, 8), 9], (8, 1)),  # one entry over: an O of 1
    (np.zeros(10, int), (1, 1)),
], ids=["cell", "flat16", "flat8", "one_over", "empty"])
def test_the_width_rule(counts, widths):
    assert ChunkedSparseDesign.row_widths(counts) == widths
    if widths[0] == widths[1] and counts.any() and (counts == counts[0]).all():
        assert widths[0] == ChunkedSparseDesign.default_chunk(counts)


@pytest.mark.parametrize("row_chunk", [12, 5, 16])
def test_an_explicit_row_chunk_is_honoured(row_chunk):
    r, c, v, n, d = _case("duplicates")
    built, records = _records(lambda: ChunkedSparseDesign.from_coo(
        r, c, v, n, d, row_chunk=row_chunk))
    fold = 8 // np.gcd(row_chunk, 8)
    assert built.fold == fold
    assert built.fvals.shape == (-(-n // fold), fold * row_chunk)
    assert built.rvals.shape[-1] == row_chunk
    (record,) = [x for x in records if x["name"] == BUILD_SPAN]
    assert record["row_chunk"] == record["row_overflow_chunk"] == row_chunk


def _stacked_side(keys, other, vals, n_keys, chunk):
    """The stacked layout's side, in numpy: entries of distinct (key, other)
    pairs ordered by them, each key's run cut into chunks of ``chunk``."""
    live = vals != 0
    keys, other, vals = keys[live], other[live], vals[live]
    order = np.lexsort((other, keys))
    keys, other, vals = keys[order], other[order], vals[order]
    out_v, out_o, out_k = [], [], []
    for k in range(n_keys):
        at = np.flatnonzero(keys == k)
        for s in range(0, len(at), chunk):
            part = at[s:s + chunk]
            out_v.append(np.pad(vals[part], (0, chunk - len(part))))
            out_o.append(np.pad(other[part], (0, chunk - len(part))))
            out_k.append(k)
    return (np.array(out_v, np.float32).reshape(-1, chunk),
            np.array(out_o, np.int32).reshape(-1, chunk),
            np.array(out_k, np.int32))


@pytest.mark.parametrize("case", ["plain", "unordered", "empty_rows",
                                  "explicit_zeros"])
def test_the_stacked_layout_is_as_before(case):
    """``rows_first=False`` (``layout_numpy``, what
    ``parallel/distributed.py`` stacks): the same arrays, keys and widths
    as ever, to the byte."""
    r, c, v, n, d = _case(case)
    lay = ChunkedSparseDesign.layout_numpy(r, c, v, n, d)
    assert set(lay) == {"rvals", "rcols", "rrow", "cvals", "crows", "ccol",
                        "row_chunk", "col_chunk", "rows_first", "entries",
                        "hot_entries"}
    live = v != 0
    widths = (ChunkedSparseDesign.default_chunk(np.bincount(
        r[live], minlength=n)), ChunkedSparseDesign.default_chunk(
            np.bincount(c[live], minlength=d)))
    assert (lay["row_chunk"], lay["col_chunk"]) == widths
    for got, want in zip(
            ("rvals", "rcols", "rrow", "cvals", "crows", "ccol"),
            _stacked_side(r, c, v, n, widths[0])
            + _stacked_side(c, r, v, d, widths[1])):
        assert lay[got].dtype == want.dtype
        np.testing.assert_array_equal(lay[got], want)
    assert not lay["rows_first"] and lay["hot_entries"] == 0
    assert lay["entries"] == int(live.sum())


def test_lookup_in_blocks(monkeypatch):
    """The table read as rows of 128 lanes, the indices in blocks with a
    remainder: the same values as plain indexing, under vmap too."""
    from photon_ml_tpu.ops import design

    monkeypatch.setattr(design, "_LOOKUP_ROWS", 2048)
    rng = np.random.default_rng(4)
    table = jnp.asarray(rng.normal(size=1000), jnp.float32)  # 1000 % 128 != 0
    idx = jnp.asarray(rng.integers(0, 1000, size=(2, 2500)), jnp.int32)
    np.testing.assert_array_equal(np.asarray(design.lookup(table, idx)),
                                  np.asarray(table)[np.asarray(idx)])
    few = idx[:, :300]  # under one block
    np.testing.assert_array_equal(np.asarray(design.lookup(table, few)),
                                  np.asarray(table)[np.asarray(few)])
    stacked = jnp.stack([idx, idx[::-1]])
    np.testing.assert_array_equal(np.asarray(design.lookup(table, stacked)),
                                  np.asarray(table)[np.asarray(stacked)])
    tables = jnp.stack([table, 2 * table])
    got = jax.vmap(design.lookup, in_axes=(0, None))(tables, idx)
    np.testing.assert_array_equal(np.asarray(got[1]),
                                  2 * np.asarray(table)[np.asarray(idx)])
