"""The ``glm_sparse`` family's own pieces: its generator against itself, its
work model against a hand count, its plain reference against a dense
computation, its readers on runs made by hand, and whole runs of its cell at
the selfcheck's sizes (``tiny/glm_sparse_ctr_1m.refit.json``), sound and with
the program's timed path broken underneath."""

import copy
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, run
from benchmark.families import glm_sparse as family
from benchmark.gen import glm_sparse_ctr as gen
from benchmark.reference import glm_sparse as reference
from benchmark.selfcheck.conftest import tiny_cell
from benchmark.work import glm_sparse as work

NAME = "glm_sparse_ctr_1m.refit"
_, WORKLOAD, CONFIG = tiny_cell(NAME)
#: the accepted metrics that the cell reports, and the family's own
REPORTED = ("compiles_in_window", "device_idle_pct", "hbm_peak_gib",
            "train_mfu_pct", "glm_solve_roofline", "lbfgs_iters_per_solve",
            "lbfgs_evals_per_iter", "retrace_s_per_unit")
OWN = ("sparse_pass_roofline_pct", "sparse_pad_share_pct", "design_build_s")
CFG = dict(dim=5003, nnz_per_row=6, integer_fields=2, integer_buckets=64,
           categorical_cardinalities=[3, 1460, 10131227, 24])
WL = dict(rows=4000, row_block=1000, planted_scale=0.3, positive_rate=0.256,
          problem_seed=77)


def test_pass_work_hand_count():
    # 10 rows of 3 entries in 7 bins: an entry is read once, value and index
    # (8 B), and multiplied into the margin and into the transpose (4
    # operations); 12 B and 8 operations a row; w read, the gradient written
    assert work.pass_work(30, 10, 7) == (120.0 + 80.0, 240.0 + 120.0 + 56.0)
    assert work.solve_passes(80) == 81
    peaks = manifest.peaks("TPU v5 lite")
    flops, bytes_ = work.pass_work(312_000_000, 8_000_000, 1_000_000)
    assert bytes_ / peaks["hbm_bytes_per_s"] > flops / peaks["flops_per_s"]
    assert bytes_ / peaks["hbm_bytes_per_s"] == pytest.approx(3.17e-3,
                                                              rel=1e-2)


def test_generator_shape_skew_and_rate():
    a = gen.generate(2**31 + 12345, WL, CFG)
    cols, vals, y = (np.asarray(a[k]) for k in ("cols", "vals", "y"))
    assert cols.shape == vals.shape == (4000, 6) and y.shape == (4000,)
    assert cols.dtype == np.int32 and vals.dtype == np.float32
    assert cols.min() >= 0 and cols.max() < 5003
    assert set(np.unique(np.abs(vals))) == {1.0}
    # the planted intercept gives the challenge's positive rate
    assert abs(float(y.mean()) - 0.256) < 0.03
    # Zipf of exponent 1: the field of 3 values holds its first id in
    # log 2 / log 3 of the rows, the field of 10M values in log 2 / log 1e7
    for f, share in ((2, np.log(2) / np.log(3)),
                     (4, np.log(2) / np.log(10131227))):
        top = np.bincount(cols[:, f]).max() / 4000
        assert abs(top - share) < 0.05, (f, top, share)
    # a sign belongs to the bin, not to the entry
    first = {}
    for c, v in zip(cols.reshape(-1), vals.reshape(-1)):
        assert first.setdefault(int(c), float(v)) == float(v)


def test_a_fixed_problem_leaves_the_seed_only_the_bin_signs():
    big = 2**31 + 12345
    a, b, c = (gen.generate(s, WL, CFG) for s in (big, big, 7))
    for k in ("cols", "vals", "y"):
        assert np.array_equal(a[k], b[k])
    assert np.array_equal(a["cols"], c["cols"])
    assert np.array_equal(a["y"], c["y"])
    assert not np.array_equal(a["vals"], c["vals"])
    other = gen.generate(big, dict(WL, problem_seed=78), CFG)
    assert not np.array_equal(a["cols"], other["cols"])
    # the objective at w on one seed and at the mirrored w on the other read
    # bit for bit the same, the gradient mirrored
    flip = np.ones(5003, np.float32)
    flip[np.asarray(a["cols"]).reshape(-1)] = (
        np.asarray(a["vals"]) * np.asarray(c["vals"])).reshape(-1)
    w = np.random.default_rng(0).normal(size=5003).astype(np.float32) * 0.1
    fa, ga = reference.value_and_grad(
        a["cols"], a["vals"], a["y"], jnp.asarray(w), jnp.float32(1.0),
        block=1000)
    fc, gc = reference.value_and_grad(
        c["cols"], c["vals"], c["y"], jnp.asarray(w * flip), jnp.float32(1.0),
        block=1000)
    assert float(fa) == float(fc)
    assert np.array_equal(np.asarray(ga), np.asarray(gc) * flip)


def _dense(cols, vals, dim):
    x = np.zeros((cols.shape[0], dim))
    np.add.at(x, (np.repeat(np.arange(cols.shape[0]), cols.shape[1]),
                  cols.reshape(-1)), vals.reshape(-1).astype(np.float64))
    return x


def test_reference_against_a_dense_computation():
    a = gen.generate(5, WL, CFG)
    cols, vals, y = (np.asarray(a[k]) for k in ("cols", "vals", "y"))
    x = _dense(cols, vals, 5003)
    w = np.random.default_rng(1).normal(size=5003) * 0.2
    m = x @ w
    f = np.sum(np.logaddexp(0, m) - y * m) + 0.5 * 1.5 * w @ w
    g = x.T @ (1 / (1 + np.exp(-m)) - y) + 1.5 * w
    fr, gr = reference.value_and_grad(
        a["cols"], a["vals"], a["y"], jnp.asarray(w, jnp.float32),
        jnp.float32(1.5), block=1000)
    assert float(fr) == pytest.approx(f, rel=1e-5)
    np.testing.assert_allclose(np.asarray(gr), g, rtol=1e-4, atol=1e-4)
    # a bin left out of the transpose keeps only its regularization term
    hot = int(reference.busiest_bin(a["cols"], dim=5003))
    assert hot == int(np.argmax(np.abs(x).sum(axis=0)))
    _, gs = reference.value_and_grad(
        a["cols"], a["vals"], a["y"], jnp.asarray(w, jnp.float32),
        jnp.float32(1.5), jnp.int32(hot), block=1000)
    assert float(gs[hot]) == pytest.approx(1.5 * w[hot], rel=1e-5)
    keep = np.arange(5003) != hot
    assert np.array_equal(np.asarray(gs)[keep], np.asarray(gr)[keep])
    # the control rounds what is gathered: it moves the numbers a little
    fb, gb = reference.value_and_grad(
        a["cols"], a["vals"], a["y"], jnp.asarray(w, jnp.float32),
        jnp.float32(1.5), block=1000, round_to="bfloat16")
    assert 1e-6 < abs(float(fb) - float(fr)) / float(fr) < 1e-2
    assert not np.array_equal(np.asarray(gb), np.asarray(gr))


def test_first_of_duplicates_counts_a_rows_colliding_entries_once():
    cols = jnp.asarray([[4, 9, 4, 4], [1, 2, 3, 2], [5, 6, 7, 8]], jnp.int32)
    vals = jnp.asarray([[1, -1, 1, 1], [1, 1, 1, 1], [-1, 1, 1, 1]],
                       jnp.float32)
    once = np.asarray(reference.first_of_duplicates(cols, vals, block=3))
    assert once.tolist() == [[1, -1, 0, 0], [1, 1, 1, 0], [-1, 1, 1, 1]]


# --- the readers of the build's record, on runs made by hand ----------------
BUILD = {"seconds": 12.5, "entries": 312_000_000, "rows": 8_000_000,
         "dim": 1_000_000, "row_chunk": 40, "col_chunk": 16,
         "row_slots": 320_000_000, "col_slots": 330_000_000}


def _read(metric, run_):
    spec = manifest.metric_file(metric)
    return manifest.reader(spec["reader"]).read(run_, spec.get("params", {}))


def test_build_record_readers():
    run_ = {"counters": {"design_build": BUILD}}
    assert _read("design_build_s", run_) == 12.5
    assert _read("sparse_pad_share_pct", run_) == pytest.approx(
        100 * (1 - 624 / 650))
    # a program from before the span was there: nothing, and no failure
    assert _read("design_build_s", {"counters": {}}) is None
    assert _read("sparse_pad_share_pct", {"counters": {}}) is None
    assert _read("sparse_pad_share_pct",
                 {"counters": {"design_build": {"seconds": 1.0}}}) is None


# --- the program against the reference, control and faults ------------------
@pytest.fixture(scope="module")
def solved():
    """One set-up and one unit of the cell, shared by the tests below."""
    cell = family.setup(11, CONFIG, copy.deepcopy(WORKLOAD),
                        jax.devices()[:1])
    cell.unit()
    outputs = cell.outputs()
    return cell, outputs


def test_program_agrees_with_the_reference(solved):
    cell, outputs = solved
    checked = cell.check()
    assert set(c.name for c in checked) == set(WORKLOAD["limits"])
    bad = [c for c in checked if not c.ok]
    assert not bad, bad
    assert family.compare_outputs(cell, outputs) == checked


def test_control_and_faults_fail_the_comparison(solved):
    cell, _ = solved
    ref = family.reference_outputs(cell)
    stood = []
    for who, outputs in family.stand_ins(cell, family.FAULTS, ref):
        stood.append(who)
        numbers = family.compare_outputs(cell, outputs, ref)
        over = [c for c in numbers if not c.ok]
        assert over, (who, numbers)
        if who.startswith("fault_"):
            # a fault is gross: two numbers or more, one at ten times its
            # limit or more
            assert len(over) >= 2, (who, over)
            assert any(c.value >= 10 * c.limit for c in numbers), numbers
    assert stood == ["control_bfloat16"] + [f"fault_{k}"
                                            for k in family.FAULTS]


def test_a_solve_that_stops_short_is_seen_by_the_full_length_reading(solved):
    """The check's path capped at 12 iterations sees no solve that stops
    after them; with the reference's full-length solve of the problem read
    once (``reference_full``) the same outputs are over their limits, and a
    stand-in as long as the check's path is still held against that path."""
    cell, outputs = solved
    ref = family.reference_outputs(cell)[0]
    full = {"iterations": ref["iterations"], "loss": ref["value"],
            "w_norm": float(np.linalg.norm(ref["w"]))}
    capped = {**cell.workload, "reference_iterations": 12}
    stopped = family.solve_path(cell.entries_again(), CONFIG, capped,
                                iterations=20)
    stopped[0]["cap"] = outputs[0]["cap"]  # as the program would report it
    over = lambda workload, out: {
        c.name for c in family.compare(out, cell.entries_again(), CONFIG,
                                       workload) if not c.ok}
    assert not over(capped, stopped)
    seen = over({**capped, "reference_full": full}, stopped)
    assert seen == {"final_loss_gap", "final_move_gap", "final_count_gap"}
    assert not over({**capped, "reference_full": full}, outputs)
    short = family.solve_path(cell.entries_again(), CONFIG, capped,
                              iterations=12)
    assert not over({**capped, "reference_full": full}, short)


# --- a whole run, sound and with the path broken underneath -----------------
def _run(capsys, trace=0):
    code = run.main(["--workload", NAME, "--seed", str(2**31 + 77),
                     "--seconds", "0.5", "--trace", str(trace)],
                    require_tpu=False)
    out = capsys.readouterr()
    assert code == 0
    return json.loads(out.out.strip().splitlines()[-1])


def test_sound_run_is_correct(tiny_cells, capsys):
    result = _run(capsys)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"train_rows_per_s", "fit_p95_s",
                                      "setup_s"}
    assert all(v["value"] > 0 for v in result["metrics"].values())


def _break(monkeypatch, fault):
    import dataclasses

    from photon_ml_tpu.glm import training

    whole = training.train_glm_sweep

    def broken(task, data, weights, config, **kw):
        if fault == "state_unchanged":
            trained = whole(task, data, weights, config, **kw)
            zero = jnp.zeros_like(trained[0].result.w)
            return [dataclasses.replace(t, result=dataclasses.replace(
                t.result, w=zero)) for t in trained]
        if fault == "stall_after_3":
            config = dataclasses.replace(
                config, optimizer_config=dataclasses.replace(
                    config.optimizer_config, max_iterations=3))
            return whole(task, data, weights, config, **kw)
        raise ValueError(fault)

    monkeypatch.setattr(training, "train_glm_sweep", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "stall_after_3"])
def test_broken_path_is_not_correct(tiny_cells, monkeypatch, capsys, fault):
    _break(monkeypatch, fault)
    result = _run(capsys)
    assert result["correct"] is False
    over = {n for n, c in result["compared"].items()
            if c["value"] > c["limit"]}
    if fault == "stall_after_3":
        # consistent reports: only the answer's own numbers can see it
        assert over == {"final_loss_gap", "final_move_gap",
                        "final_count_gap"}, over


def test_a_dropped_entry_is_not_correct(tiny_cells, monkeypatch, capsys):
    """The build loses one row's entries on the column side: the margins are
    whole, the transpose is not."""
    from photon_ml_tpu.ops import design

    whole = design.ChunkedSparseDesign.layout

    def lossy(rows, cols, vals, *a, **kw):
        lay = whole(rows, cols, vals, *a, **kw)
        lay["cvals"] = jnp.where(lay["crows"] == 3, 0.0, lay["cvals"])
        return lay

    monkeypatch.setattr(design.ChunkedSparseDesign, "layout",
                        staticmethod(lossy))
    result = _run(capsys)
    assert result["correct"] is False
    over = {n for n, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert {"grad0_bin_gap", "kkt_bin_gap"} <= over, over


PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_a_traced_run_carries_the_cells_metrics(tiny_cells, monkeypatch,
                                                capsys):
    """A whole ``--trace 1`` run here, the reduction put in by hand (no chip
    is in a CPU trace) under the names a chip's trace of the solve prints:
    every per-layer metric the manifest is to list for the cell but the
    memory peak, the build's record among what they read, no share over
    100."""
    from benchmark import trace
    from photon_ml_tpu.telemetry import tracing

    tracing.GLOBAL_TRACER._ring.clear()
    monkeypatch.setattr(trace, "reduce", lambda path, chips: {
        "window_s": 1.0, "busy_s": 0.99, "device_ops": [], "idle_gaps": [],
        "per_chip": [{"busy_s": 0.99, "modules_s": {"jit_run": 0.99},
                      "ops_self_s": {"fusion": 0.6, "while": 0.001,
                                     "add_reduce_fusion": 0.1,
                                     "select_reduce_fusion": 0.27,
                                     "add_select_fusion": 0.005,
                                     "copy": 0.01},
                      "collective_s": 0.0}]})
    monkeypatch.setattr(manifest, "peaks", lambda kind: PEAKS)
    code = run.main(["--workload", NAME, "--seed", "5", "--seconds", "0.3",
                     "--trace", "1"], require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    result = json.loads(lines[-1])
    got = {n: m["value"] for n, m in result["metrics"].items()}
    assert set(REPORTED + OWN) - {"hbm_peak_gib"} == set(got)
    assert got["compiles_in_window"] == 0 and got["retrace_s_per_unit"] == 0
    assert got["lbfgs_iters_per_solve"] > 3
    assert 1.0 <= got["lbfgs_evals_per_iter"] < 3.0
    assert got["design_build_s"] > 0
    assert 0 <= got["sparse_pad_share_pct"] < 100
    # chunks of 8 or more slots: some of them pad
    assert got["sparse_pad_share_pct"] > 1
    for share in ("sparse_pass_roofline_pct", "glm_solve_roofline",
                  "train_mfu_pct"):
        assert 0 < got[share] <= 100, (share, got[share])
    # rejected trial points count as work the contractions did, not as
    # required work, and the pass's time is the two contractions' operations
    # alone (0.97 of the program's 0.99 s): the pass's share is the larger
    assert got["sparse_pass_roofline_pct"] >= got["glm_solve_roofline"]
    info = next(json.loads(l.split(": ", 1)[1]) for l in lines
                if l.startswith("info: ") and '"paths"' in l)
    assert info["paths"]["solve_program"] == "xla"
    assert info["paths"]["scopes"] == ["design.matvec", "design.rmatvec"]
    assert info["paths"]["design"] == "ChunkedSparseDesign"
    assert info["bound"] == "bandwidth"


def test_the_pass_roofline_reads_the_contractions_operations_alone(
        monkeypatch):
    """``fusion`` and ``select_reduce_fusion`` (the gathers of whole table
    rows with the column side's scatter-add inside, and the lane picked),
    ``add_reduce_fusion`` (the busy bins' planes, either side),
    ``slice_reduce_fusion`` and ``reshape`` (the per-row vector cut for the
    planes' words: PERF.md, section 5) and a scatter that stands alone; not
    the pointwise loss, the copies, the L-BFGS algebra or the loop's shell."""
    from photon_ml_tpu.telemetry import tracing

    spec = manifest.metric_file("sparse_pass_roofline_pct")
    chip = {"ops_self_s": {"fusion": 0.5, "select_reduce_fusion": 0.2,
                           "add_reduce_fusion": 0.1,
                           "slice_reduce_fusion": 0.05, "reshape": 0.05,
                           "scatter-add": 0.1, "add_select_fusion": 5.0,
                           "copy": 5.0, "multiply_reduce_fusion": 5.0,
                           "while": 5.0}}
    records = [{"name": "glm.solve", "evaluations": 4, "iterations": 3}]
    work_ = {"passes": 4, "flops_per_chip": 0.0, "bytes_per_chip": 819e9}
    monkeypatch.setattr(tracing, "recorded", lambda: records)
    run_ = {"trace": {"per_chip": [chip]}, "peaks": PEAKS, "work": work_,
            "counters": {"solves": 1}}
    got = manifest.reader(spec["reader"]).read(run_, spec["params"])
    assert got == pytest.approx(100.0)
