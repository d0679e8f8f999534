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
the TPU's library. The loop runs in stages, each a ``while`` of its own over
the lanes still running; what moves the lanes between stages runs between
the loops.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.ops.pallas_re import entity_pad, lane_fits_vmem
from photon_ml_tpu.ops.regularization import L2Regularization
from photon_ml_tpu.optimize import OptimizerConfig
from photon_ml_tpu.optimize.lbfgs import stage_widths
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


def _solver():
    """The cells' random-effect solver: L-BFGS, history ``M``, L2."""
    from photon_ml_tpu.game.random_effect import RandomEffectSolver

    return RandomEffectSolver(
        task=TaskType.LOGISTIC_REGRESSION,
        config=GLMOptimizationConfiguration(
            regularization=L2Regularization,
            optimizer_config=OptimizerConfig(
                max_iterations=25, tolerance=1e-6, history=M,
                track_states=False)))


@pytest.fixture(scope="module")
def bucket_program(one_chip):
    """The compiled text of one kernel bucket's solve. The objective's gate
    asks for the backend's name, which is the CPU's here: the test answers
    for the chip it compiles for."""
    from photon_ml_tpu.game.random_effect import _solve_bucket_impl

    solver = _solver()
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


def _fit(n):
    """The lanes the kernel's block plan takes for ``n`` of the bucket's."""
    return n + entity_pad(n, S, D, jnp.float32)


#: the flat loop's stages: E padded to the block plan (4096), then an eighth
WIDTHS = stage_widths(_fit(E), _fit)
#: E, or a stage's width
LANES = "|".join(str(w) for w in (E,) + WIDTHS)


def _layouts(text, shape):
    """The layouts (minor to major) given to float32 arrays of ``shape``
    (a pattern: the lane count is ``LANES``)."""
    return set(re.findall(rf"f32\[{shape}\]{{([0-9,]+):", text))


def _loop_bodies(text):
    """The text of each ``while`` loop's body and of every computation it
    calls (its fusions, the kernel's call), from the compiled module."""
    computations = dict(re.findall(
        r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S))
    bodies = []
    for body in re.findall(r" while\(.*body=%?([\w.\-]+)", text):
        seen, todo = {}, [body]
        while todo:
            name = todo.pop()
            if name not in seen:
                seen[name] = computations[name]
                todo += re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                    seen[name])
        bodies.append("\n".join(seen.values()))
    return bodies


def test_the_buckets_histories_are_dense_on_the_chip(bucket_program):
    """Inside every stage's loop. Between the loops, where the running
    lanes are gathered to the next stage's front once a stage, the compiler
    may lay a history lanes-major for the gather: no trip pays that."""
    bodies = _loop_bodies(bucket_program)
    for body in bodies:
        assert _layouts(body, f"{M},{D},(?:{LANES})") == {"2,1,0"}
        assert not _layouts(body, f"(?:{LANES}),{M},{D}")
        # the iterate, the gradient and the direction: lanes minor too
        assert _layouts(body, f"{D},(?:{LANES})") == {"1,0"}
    # every stage's width is in some loop's arrays
    for w in WIDTHS:
        assert any(_layouts(body, f"{M},{D},{w}") for body in bodies), w


def test_the_bucket_is_one_loop(bucket_program):
    """One flat loop, one evaluation a trip, run as a ``while`` a stage: no
    loop inside a loop."""
    bodies = _loop_bodies(bucket_program)
    assert len(bodies) == len(WIDTHS) == 2
    for body in bodies:
        assert not re.search(r" while\(", body)


def test_the_kernel_takes_the_bucket_entities_last(bucket_program):
    """The design reaches the kernel as ``(D, S, E)``, the entities in the
    128-lane dimension, dense; and no stage's loop body reads or writes an
    array that holds ``D`` there (the bucket's ``(E, S, D)`` statics are
    laid out once a stage, before its loop), nor stacks the columns of ``w``
    or of the gradient: the kernel takes the loop's ``(d, E)`` iterate as it
    is."""
    kernel = re.findall(r"[^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*",
                        bucket_program)
    # the evaluation at w0, and each stage's loop
    assert len(kernel) == 1 + len(WIDTHS)
    for call in kernel:
        assert re.search(rf"operand_layout_constraints={{f32\[{D},{S},"
                         rf"(?:{LANES})\]{{2,1,0}}", call)
    # one dense layout of a stage's design, made before the stage's loop
    for w in WIDTHS:
        made = re.findall(rf"= f32\[{D},{S},{w}\]{{2,1,0:[^}}]*}} "
                          rf"(?:copy|transpose|fusion)\(", bucket_program)
        assert len(made) == 1, w
    for body in _loop_bodies(bucket_program):
        assert _layouts(body, f"{D},{S},(?:{LANES})") <= {"2,1,0"}
        assert "tpu_custom_call" in body
        assert not re.search(rf"f32\[(?:{LANES}),{S},{D}\]", body)
        assert not re.search(rf"f32\[(?:{LANES}),{D}\]", body)
        assert not re.search(rf"f32\[{D},(?:{LANES})\][^ ]* concatenate\(",
                             body)
        assert not re.search(
            rf"f32\[{D},{S},(?:{LANES})\][^ ]* (?:copy|transpose)\(", body)


# --- the random-effect sweep's moves (PERF.md, PR 33) ------------------------
SWEEP_ROWS = 3_000_000
#: (entities, rows) of a kernel bucket and of two closed-form ones; the
#: scores' look-up runs a whole block and a rest
#: (``ops/design.py::_LOOKUP_ROWS``)
SWEEP_BUCKETS = [(50_000, 56), (300, 9_000), (20, 140_000)]


@pytest.fixture(scope="module")
def sweep_program(one_chip):
    """The compiled text of a resident coordinate's sweep, the body's own
    inputs as ``RandomEffectSolver._sweep_inputs`` lays them."""
    from photon_ml_tpu.game.random_effect import _sweep_fused_impl

    solver = _solver()
    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    statics = tuple((sds((e, s, D)), sds((e, s)), sds((e, s)))
                    for e, s in SWEEP_BUCKETS)
    warm_ctxs = tuple((sds((e, D), jnp.int32), sds((e, D), jnp.bool_))
                      for e, _ in SWEEP_BUCKETS)
    cidxs = tuple(sds((e * D,), jnp.int32) for e, _ in SWEEP_BUCKETS)
    with pytest.MonkeyPatch.context() as patch, \
            jax.enable_x64(False):
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(
            _sweep_fused_impl,
            static_argnames=("solver", "e_reals", "out_sharding")).lower(
                solver, sds((SWEEP_ROWS,)), sds(()), statics, warm_ctxs,
                sds((sum(e for e, _ in SWEEP_BUCKETS) * D,)), cidxs,
                tuple(e for e, _ in SWEEP_BUCKETS),
                sds((1, SWEEP_ROWS), jnp.int32)).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


def _stage_widths(e, s):
    """The flat loop's stage widths in an ``(e, s)`` bucket of the sweep."""
    if not lane_fits_vmem(s, D, jnp.float32):
        return stage_widths(e)
    fit = lambda n: n + entity_pad(n, s, D, jnp.float32)
    return stage_widths(fit(e), fit)


#: the lanes a stage of some bucket's loop holds: between its stages the
#: loop moves lanes (entities), each a bucket's width or a stage's
STAGE_LANES = {w for e, s in SWEEP_BUCKETS for w in _stage_widths(e, s)}


def test_the_sweep_moves_rows_and_not_padded_slots(sweep_program):
    """What the sweep indexes, it indexes by the row: one scatter (the
    offsets into their slots) and its sort, each over the ``n`` rows, none
    over a bucket's padded slots; and every gather (the scores' look-up,
    the warm start's and the coefficient mirror's) fetches rows of 128
    lanes: a gather of scalars runs an element at a time on the chip. The
    flat loops' stages move their lanes apart from these: every other
    sort, scatter and gather is over a stage's lanes."""
    moved = re.findall(r"= \(?[fs]32\[(\d+)\][^\n]* (sort|scatter)\(",
                       sweep_program)
    moved = [(size, kind) for size, kind in moved
             if int(size) not in STAGE_LANES]
    assert {kind for _, kind in moved} == {"sort", "scatter"}
    assert len(moved) == 2
    slots = sum(e * s for e, s in SWEEP_BUCKETS)
    assert sorted(int(size) for size, _ in moved) == [SWEEP_ROWS, slots]
    assert re.search(rf"s32\[{SWEEP_ROWS}\][^\n]* sort\(", sweep_program)
    gathers = re.findall(
        r"= f32\[([0-9,]+)\]\S* gather\([^\n]*slice_sizes={([0-9,]+)}",
        sweep_program)
    rows = [(shape, fetched) for shape, fetched in gathers
            if fetched == "1,128"]
    assert len(rows) >= 2 + 2 * len(SWEEP_BUCKETS)
    assert all(shape.endswith(",128") for shape, _ in rows), rows
    lanes = [shape for shape, fetched in gathers if fetched != "1,128"]
    assert all(any(int(n) in STAGE_LANES for n in shape.split(","))
               for shape in lanes), lanes


def test_the_sweep_holds_one_index_of_the_rows(sweep_program):
    """The rows' slots come in as ``(1, n)``, the long axis last, and no
    index of a bucket's ``(entities, rows)`` shape is left in the program
    (``(50000, 56)`` with 56 in the 128-lane dimension is 2.3 times its
    bytes)."""
    assert re.search(rf"s32\[1,{SWEEP_ROWS}\]\S* parameter\(", sweep_program)
    assert not re.search(rf"s32\[{SWEEP_ROWS},1\]{{1,0", sweep_program)
    for e, s in SWEEP_BUCKETS:
        assert not re.search(rf"s32\[{e},{s}\]\S* parameter\(", sweep_program)


# --- the wide sparse design's evaluation (PERF.md, PR 32) --------------------
SPARSE_ROWS, SPARSE_DIM, SPARSE_CHUNKS = 400_000, 1_000_000, 1_100_000
SPARSE_OVER = 50_000  # chunks beyond the rows' first
#: (row side, column chunk): the row side ``(C, O)`` with every row's first
#: chunk C wide at its place and overflow chunks O wide, or ``(None, C)``
#: for chunks of non-empty rows alone, summed by row. The click-through
#: shape first (rows of 40 slots, column chunks of 16: the one timed on the
#: chip in PR 32), then the other widths ``default_chunk`` yields, first
#: chunks that take a fold (12, 10: PR 36) and row sides that take their
#: whole segment-sum
SPARSE_WIDTHS = [((40, 40), 16), ((40, 40), 8), ((40, 40), 32),
                 ((40, 40), 128), ((12, 12), 16), ((10, 2), 16),
                 ((None, 8), 16), ((None, 64), 64)]


def _sparse_design(sds, n, d, rows, col_chunk, mc, over, **hot):
    """A ``ChunkedSparseDesign`` of described arrays: ``rows`` as in
    ``SPARSE_WIDTHS``; ``over`` chunks beyond the rows' first."""
    import math

    from photon_ml_tpu.ops.design import ChunkedSparseDesign

    first, width = rows
    side = {}
    if first is not None:
        fold = 8 // math.gcd(first, 8)
        shape = (-(-n // fold), fold * first)
        side = dict(fvals=sds(shape), fcols=sds(shape, jnp.int32), fold=fold)
    mr = over if first is not None else mc
    return ChunkedSparseDesign(
        rvals=sds((mr, width)), rcols=sds((mr, width), jnp.int32),
        rrow=sds((mr,), jnp.int32), cvals=sds((mc, col_chunk)),
        crows=sds((mc, col_chunk), jnp.int32), ccol=sds((mc,), jnp.int32),
        n_rows=n, n_cols=d, **side, **hot)


def _evaluation(sds, design, n, d):
    """One value-and-gradient evaluation over ``design``, compiled for the
    chip: its text and its temporaries."""
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.objective import GLMData, GLMObjective

    data = GLMData(design=design, labels=sds((n,)), offsets=sds((n,)),
                   weights=sds((n,)))
    objective = GLMObjective(loss=LogisticLoss, fused=True)
    with jax.enable_x64(False):
        program = jax.jit(lambda w, data: objective.value_and_grad(
            w, data, 1.0)).lower(sds((d,)), data).compile()
    return program.as_text(), program.memory_analysis()


def _sds(one_chip):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def sparse_evaluation(one_chip):
    """The evaluation of the given widths at ``SPARSE_ROWS``."""
    sds = _sds(one_chip)
    built = {}

    def compiled(rows, col_chunk):
        if (rows, col_chunk) not in built:
            design = _sparse_design(sds, SPARSE_ROWS, SPARSE_DIM, rows,
                                    col_chunk, SPARSE_CHUNKS, SPARSE_OVER)
            built[rows, col_chunk] = _evaluation(sds, design, SPARSE_ROWS,
                                                 SPARSE_DIM)
        return built[rows, col_chunk]

    return compiled


def _chunk_arrays(n, rows, col_chunk, mc, over):
    """The ``(M, width)`` shapes of a design's chunk arrays."""
    first, width = rows
    out = [(mc, col_chunk), (over if first is not None else mc, width)]
    if first is not None:
        fold = 8 // np.gcd(first, 8)
        out.append((-(-n // fold), fold * first))
    return out


def _no_chunk_array_in_the_lanes(text, shapes):
    for rows, width in shapes:
        laid = set(re.findall(rf"[fs]32\[{rows},{width}\]{{([0-9,]+):",
                              text))
        assert laid <= {"0,1"} or width == 128, (rows, width, laid)


def _row_rows(text, n):
    """Per scatter of the row side (``design.matvec``), the number of
    chunk sums it adds: the index operand of its fused computation."""
    sums = []
    for head, body in re.findall(
            r"\n(%\S+ \([^\n]*\) -> [^\n]*\{)\n(.*?)\n\}", text, re.S):
        if " scatter(" in body and "design.matvec/scatter-add" in body:
            sums += [int(m) for m in re.findall(r": s32\[(\d+)(?:,1)?\]",
                                               head)]
    return sums


@pytest.mark.parametrize("widths", SPARSE_WIDTHS, ids=str)
def test_sparse_evaluation_gathers_whole_rows(sparse_evaluation, widths):
    """Every gather of the evaluation fetches rows of 128 lanes (a gather of
    scalars runs one element at a time on the chip), and both sides'
    operations carry their scope."""
    text, _ = sparse_evaluation(*widths)
    gathers = re.findall(r"= f32\[([0-9,]+)\]\S* gather\(", text)
    assert gathers and all(g.endswith(",128") for g in gathers), gathers
    assert "design.matvec" in text and "design.rmatvec" in text


@pytest.mark.parametrize("widths", SPARSE_WIDTHS, ids=str)
def test_sparse_evaluation_pads_no_chunk_array(sparse_evaluation, widths):
    """A ``(M, C)`` chunk array is never re-laid with C (8 to 64) in the
    128-lane dimension (at C = 16 and 40, 8 and 3.2 times its bytes: the
    parent's evaluation did not fit the chip at 8,000,000 rows for it), the
    row side's segment-sum takes the chunks beyond the rows' first alone,
    and the temporaries stay near the two blocks of gathered rows."""
    text, memory = sparse_evaluation(*widths)
    rows, col_chunk = widths
    _no_chunk_array_in_the_lanes(text, _chunk_arrays(
        SPARSE_ROWS, rows, col_chunk, SPARSE_CHUNKS, SPARSE_OVER))
    summed = _row_rows(text, SPARSE_ROWS)
    assert summed and set(summed) == {
        SPARSE_OVER if rows[0] is not None else SPARSE_CHUNKS}, summed
    assert memory.temp_size_in_bytes < 4 * 2**30


#: the sparse cell as PR 36 builds it (PERF.md, section 5): 5,000,000 rows,
#: first chunks of 10 four rows a lane column, 1,255,656 overflow chunks of
#: 2, 3,310,517 column chunks of 16, the planes of 3,072 busy bins
CELL_ROWS, CELL_OVER, CELL_CHUNKS, CELL_HOT = (5_000_000, 1_255_656,
                                               3_310_517, 3072)


def test_the_sparse_cells_evaluation(one_chip):
    """The evaluation at the sparse cell's shapes and widths: no chunk array
    re-laid into the lanes (the first chunks, 40 sublanes, fill five tiles),
    only 128-lane rows gathered, the overflow chunks alone segment-summed,
    temporaries under 4 GiB."""
    sds = _sds(one_chip)
    n, k = CELL_ROWS, CELL_HOT
    design = _sparse_design(
        sds, n, SPARSE_DIM, (10, 2), 16, CELL_CHUNKS, CELL_OVER,
        hot_cols=sds((k,), jnp.int32), hot_vals=sds((k,)),
        hot_by_row=sds((k // 32, n), jnp.uint32),
        hot_by_bin=sds((-(-n // 32), k), jnp.uint32))
    text, memory = _evaluation(sds, design, n, SPARSE_DIM)
    assert design.fvals.shape == (n // 4, 40)
    _no_chunk_array_in_the_lanes(text, _chunk_arrays(
        n, (10, 2), 16, CELL_CHUNKS, CELL_OVER))
    gathers = re.findall(r"= f32\[([0-9,]+)\]\S* gather\(", text)
    assert gathers and all(g.endswith(",128") for g in gathers), gathers
    assert _row_rows(text, n) == [CELL_OVER]
    assert memory.temp_size_in_bytes < 4 * 2**30


def test_busy_bins_planes_are_read_in_one_pass_a_side(one_chip):
    """The bit planes of the busy bins (``hot_by_row``, ``hot_by_bin``) are
    what an evaluation streams: each is the operand of one fusion and of
    nothing else (no copy, no transposition, no pass a bit), and the row side
    with chunks beyond the rows' first sums only those by row."""
    from photon_ml_tpu.ops.design import ChunkedSparseDesign
    from photon_ml_tpu.ops.losses import LogisticLoss
    from photon_ml_tpu.ops.objective import GLMData, GLMObjective

    sds = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    n, d, k, mr, mc = SPARSE_ROWS, SPARSE_DIM, 3072, 600_000, 300_000
    words = -(-n // 32)
    design = ChunkedSparseDesign(
        rvals=sds((mr - n, 8)), rcols=sds((mr - n, 8), jnp.int32),
        rrow=sds((mr - n,), jnp.int32), cvals=sds((mc, 16)),
        crows=sds((mc, 16), jnp.int32), ccol=sds((mc,), jnp.int32),
        n_rows=n, n_cols=d, fvals=sds((n, 8)),
        fcols=sds((n, 8), jnp.int32),
        hot_cols=sds((k,), jnp.int32), hot_vals=sds((k,)),
        hot_by_row=sds((k // 32, n), jnp.uint32),
        hot_by_bin=sds((words, k), jnp.uint32))
    data = GLMData(design=design, labels=sds((n,)), offsets=sds((n,)),
                   weights=sds((n,)))
    objective = GLMObjective(loss=LogisticLoss, fused=True)
    with jax.enable_x64(False):
        program = jax.jit(lambda w, data: objective.value_and_grad(
            w, data, 1.0)).lower(sds((d,)), data).compile()
    text = program.as_text()
    entry = text[text.index("ENTRY"):]
    for shape in (f"{k // 32},{n}", f"{words},{k}"):
        (plane,) = re.findall(rf"(%\S+) = u32\[{shape}\]\S* parameter\(",
                              entry)
        users = re.findall(rf"= \S+ (\w+)\([^)]*{re.escape(plane)}[,)]",
                           entry)
        assert users == ["fusion"], (shape, users)
        assert not re.search(rf"= u32\[{shape}\]", entry.replace(
            f"{plane} = u32[{shape}]", ""))
    # the rows' first chunks are margins as they stand: the segment-sum (a
    # scatter) takes the mr - n chunks beyond them
    assert "design.matvec/scatter-add" in text
    assert re.search(rf"f32\[{mr - n}(,1)?\]", text)
    assert program.memory_analysis().temp_size_in_bytes < 4 * 2**30


# --- the fixed effect's TRON solve (PERF.md, PR 35) --------------------------
TRON_ROWS, TRON_DIM = 1_500_000, 1024  # the cell glm_tron_1024.lambda_path's


@pytest.fixture(scope="module")
def tron_program(one_chip):
    """The compiled text of ``OptimizationProblem.run`` under TRON at the
    benchmark cell's shapes and settings (nothing of that size is held: the
    arguments are shapes)."""
    from photon_ml_tpu.glm.training import build_problem
    from photon_ml_tpu.ops.design import DenseDesign
    from photon_ml_tpu.ops.objective import GLMData
    from photon_ml_tpu.types import OptimizerType

    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,
                                              sharding=one_chip)
    config = GLMOptimizationConfiguration(
        optimizer=OptimizerType.TRON, regularization=L2Regularization,
        optimizer_config=OptimizerConfig(max_iterations=15, tolerance=1e-5,
                                         cg_max_iterations=20))
    data = GLMData(design=DenseDesign(x=sds(TRON_ROWS, TRON_DIM)),
                   labels=sds(TRON_ROWS), offsets=sds(TRON_ROWS),
                   weights=sds(TRON_ROWS))
    with pytest.MonkeyPatch.context() as patch, \
            jax.enable_x64(False):
        patch.setattr(jax, "default_backend", lambda: "tpu")
        compiled = jax.jit(
            build_problem(TaskType.LOGISTIC_REGRESSION, config).run).lower(
                data, sds(TRON_DIM), sds()).compile()
    return compiled.as_text(), compiled.memory_analysis()


def _bodies(text):
    """Per ``while`` of the module, the text of its body and of every
    computation the body calls."""
    computations = dict(re.findall(
        r"^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S))
    out = []
    for body in re.findall(r" while\(.*body=%?([\w.\-]+)", text):
        seen, todo = {}, [body]
        while todo:
            name = todo.pop()
            if name not in seen:
                seen[name] = computations[name]
                todo += re.findall(
                    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)",
                    seen[name])
        out.append("\n".join(seen.values()))
    return out


def test_the_tron_solve_holds_both_kernels_and_no_copy_of_the_design(
        tron_program):
    """Conjugate gradients inside the trust region's loop: two ``while``s,
    ``fused_hvp`` in the inner one's body, ``fused_value_and_grad`` in the
    outer one's (and once before it); the design reaches every call as the
    program's own argument in one layout; and no ``copy``, ``pad`` or
    ``transpose`` makes an array of the design's size anywhere, so none
    inside a loop's body (PRs 29 and 31 each found one there)."""
    text, memory = tron_program
    design = rf"f32\[{TRON_ROWS},{TRON_DIM}\]"
    calls = re.findall(
        r"%(fused_\w+?)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\""
        r"[^\n]*", text)
    assert sorted(calls) == ["fused_hvp", "fused_value_and_grad",
                             "fused_value_and_grad"]
    for line in re.findall(
            r"[^\n]*custom_call_target=\"tpu_custom_call\"[^\n]*", text):
        assert re.search(r"operand_layout_constraints=\{" + design
                         + r"\{1,0\}", line)
    assert set(re.findall(design + r"\{([0-9,]+):", text)) == {"1,0"}
    bodies = _bodies(text)
    assert len(bodies) == 2
    inner, outer = sorted(bodies, key=len)
    assert "%fused_hvp" in inner and "%fused_value_and_grad" not in inner
    assert "%fused_value_and_grad" in outer and "%fused_hvp" in outer
    made = re.findall(rf"= {design}\S* ([\w\-]+)\(", text)
    assert set(made) <= {"parameter", "get-tuple-element", "broadcast",
                         "multiply"}, set(made)
    # broadcast and multiply: inside the fusion that is the curvature pass's
    # margins (a multiply-reduce over the design, nothing of its size stored)
    assert memory.temp_size_in_bytes < 64 * 2**20
