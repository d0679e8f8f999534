"""The ``glm_tron`` family's own pieces: its work model against a count by
hand, its three metric files through their readers, its plain reference's
conjugate gradients against a solve by hand, what it refuses to run on, and
whole runs of its cell at the selfcheck's sizes
(``tiny/glm_tron_1024.lambda_path.json``), sound and with the program's timed
path broken underneath."""

import copy
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest, run
from benchmark.families import glm_tron as family
from benchmark.readers import counter_mean, hvps_per_iter, op_roofline
from benchmark.reference import tron as reference
from benchmark.selfcheck.conftest import tiny_cell
from benchmark.work import glm_tron as work

#: the family's cells, by their configuration's ``family``
CELLS = sorted(w["name"] for w in manifest.benchmark()["workloads"]
               if manifest.cell(w["name"])[2]["family"] == "glm_tron")
NAME = CELLS[0]
_, WORKLOAD, CONFIG = tiny_cell(NAME)
#: the accepted metrics that the cell reports, and the family's own
REPORTED = ("compiles_in_window", "device_idle_pct", "hbm_peak_gib",
            "train_mfu_pct", "glm_solve_roofline", "glm_kernel_roofline_pct",
            "retrace_s_per_unit")
OWN = ("hvp_kernel_roofline_pct", "tron_hvps_per_iter",
       "tron_iters_per_solve")
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_manifest_lists_the_cells_metrics():
    listed = {m["name"] for m in manifest.metrics_of(NAME, "per_layer")}
    assert listed == set(REPORTED + OWN)
    for name, reader in (("hvp_kernel_roofline_pct", op_roofline),
                         ("tron_hvps_per_iter", hvps_per_iter),
                         ("tron_iters_per_solve", counter_mean)):
        assert manifest.reader(manifest.metric_file(name)["reader"]) is reader
    config = manifest.cell(NAME)[2]
    assert config["reduced"] == [] and len(config["source"]) <= 200
    assert config["optimizer"] == {
        "type": "TRON", "max_iterations": 15, "tolerance": 1e-5,
        "cg_max_iterations": 20, "cg_stop": 0.1, "eta": [1e-4, 0.25, 0.75],
        "sigma": [0.25, 0.5, 4.0]}


# --- the work model and the readers, by hand ---------------------------------
def test_work_hand_count():
    # a product over 10 rows x 4 columns of float32: X v is 10*4 multiply-adds,
    # X'(d2 * t) as many, 2 operations each: 160; the design read once (160 B),
    # d2 beside it (40 B), v read and the product written (32 B)
    assert work.hvp_work(10, 4) == (160.0, 160.0 + 40.0 + 32.0)
    # two solves: 3 and 2 outer iterations (4 + 3 evaluations), 7 + 5 products
    got = work.solves_work(10, 4, 4, [(3, 7), (2, 5)])
    pass_flops, pass_bytes = 160.0 + 80.0, 160.0 + 120.0 + 32.0
    assert got["evaluation_passes"] == 7 and got["hvp_passes"] == 12
    assert got["flops_per_chip"] == 7 * pass_flops + 12 * 160.0
    assert got["bytes_per_chip"] == 7 * pass_bytes + 12 * 232.0
    assert got["hvp_kernel_flops"] == 12 * 160.0
    assert got["hvp_kernel_bytes"] == 12 * 232.0
    # the whole in value-and-gradient passes' bytes: what the evaluation
    # kernel's reader scales by, so that it gets the evaluations' own bytes
    assert got["bytes_per_chip"] * 7 / got["passes"] \
        == pytest.approx(7 * pass_bytes)
    # at the cell's shape a product is bound by the chip's bandwidth
    flops, bytes_ = work.hvp_work(1_500_000, 1024)
    assert bytes_ / PEAKS["hbm_bytes_per_s"] > flops / PEAKS["flops_per_s"]
    assert bytes_ / PEAKS["hbm_bytes_per_s"] == pytest.approx(7.509e-3,
                                                              rel=1e-3)


@pytest.fixture
def program(monkeypatch):
    """Put hand-made records in the place of the program's ring."""
    from photon_ml_tpu.telemetry import tracing

    def hold(records):
        monkeypatch.setattr(tracing, "recorded", lambda: list(records),
                            raising=False)
    return hold


def _solves(counts, **more):
    return [{"name": "glm.solve", "iterations": i, "evaluations": i + 1,
             "hvps": h, **more} for i, h in counts]


def test_the_three_metric_files_through_their_readers(program):
    counts = [(15, 74), (15, 256), (15, 299), (10, 200)]
    program(_solves(counts) + [{"name": "glm.sweep", "seconds": 1.0}])
    w = work.solves_work(1000, 8, 4, counts)
    kernel_s = 2.0 * w["hvp_kernel_bytes"] / PEAKS["hbm_bytes_per_s"]
    run_ = {"counters": {"tron_iterations": [i for i, _ in counts],
                         "solves": len(counts)},
            "work": w, "peaks": PEAKS,
            "trace": {"per_chip": [{"ops_self_s": {
                "fused_hvp": kernel_s, "fused_value_and_grad": 9.0,
                "fused_hvp_other": 9.0, "while": 1.0}}]}}
    read = lambda name: manifest.reader(
        manifest.metric_file(name)["reader"]).read(
            run_, manifest.metric_file(name).get("params", {}))
    assert read("tron_hvps_per_iter") == pytest.approx(829 / 55)
    assert read("tron_iters_per_solve") == pytest.approx(55 / 4)
    # the kernel at half the bandwidth's rate: its own operations alone
    assert read("hvp_kernel_roofline_pct") == pytest.approx(50.0)


def test_hvps_per_iter_reads_nothing_it_cannot(program):
    params = manifest.metric_file("tron_hvps_per_iter")["params"]
    run_ = {"counters": {"solves": 2}}
    # a program from before the count was there: records without ``hvps``
    program([{"name": "glm.solve", "iterations": 3, "evaluations": 4}] * 2)
    assert hvps_per_iter.read(run_, params) is None
    # records that are not the window's
    program(_solves([(3, 7)]))
    assert hvps_per_iter.read(run_, params) is None
    # no iteration made
    program(_solves([(0, 0), (0, 0)]))
    assert hvps_per_iter.read(run_, params) is None
    # every conjugate-gradient solve at its cap
    program(_solves([(15, 300), (15, 300)]))
    assert hvps_per_iter.read(run_, params) == 20.0
    # no kernel of that name in the trace, no work stated: nothing, no raise
    spec = manifest.metric_file("hvp_kernel_roofline_pct")["params"]
    assert op_roofline.read({"work": {}, "peaks": PEAKS, "trace": {
        "per_chip": [{"ops_self_s": {"fused_hvp": 1.0}}]}}, spec) is None
    assert op_roofline.read({
        "work": {"hvp_kernel_flops": 1.0, "hvp_kernel_bytes": 1.0},
        "peaks": PEAKS, "trace": {"per_chip": [{"ops_self_s": {
            "custom-call": 1.0}}]}}, spec) is None


# --- the reference's pieces ---------------------------------------------------
def test_conjugate_gradients_against_a_solve_by_hand():
    """On ``H = diag(1, 4)``, ``g = (-1, -2)``: inside a wide region two
    steps reach the Newton point exactly; inside a short one the first step
    stops on the boundary along ``-g``; the predicted reduction is the
    model's at the step."""
    h = np.array([1.0, 4.0])
    g = np.array([-1.0, -2.0])
    model = lambda s: -(g @ s + 0.5 * s @ (h * s))
    s, predicted, products = reference.conjugate_gradients(
        lambda v: h * v, g, 10.0, cap=20, cg_stop=1e-12)
    assert products == 2
    np.testing.assert_allclose(s, [1.0, 0.5], rtol=1e-12)
    assert predicted == pytest.approx(model(s))
    s, predicted, products = reference.conjugate_gradients(
        lambda v: h * v, g, 0.1, cap=20)
    assert products == 1
    np.testing.assert_allclose(s, 0.1 * -g / np.linalg.norm(g), rtol=1e-12)
    assert predicted == pytest.approx(model(s))
    # the cap: one product, the exact line minimum along -g
    s, _, products = reference.conjugate_gradients(
        lambda v: h * v, g, 10.0, cap=1)
    assert products == 1
    np.testing.assert_allclose(s, -g * (g @ g) / (g @ (h * g)), rtol=1e-12)
    # curvature that is not positive: to the boundary
    s, _, products = reference.conjugate_gradients(
        lambda v: -v, g, 3.0, cap=20)
    assert products == 1 and np.linalg.norm(s) == pytest.approx(3.0)


def test_reference_products_against_plain_numpy():
    rng = np.random.default_rng(4)
    x = rng.normal(size=(600, 16)).astype(np.float32)
    y = (rng.random(600) < 0.4).astype(np.float32)
    w, v = (rng.normal(size=16).astype(np.float32) * 0.3 for _ in range(2))
    xd, wd, vd = (np.asarray(a, np.float64) for a in (x, w, v))
    m = xd @ wd
    sig = 1.0 / (1.0 + np.exp(-m))
    f, g = reference.value_and_grad(jnp.asarray(x), jnp.asarray(y),
                                    jnp.asarray(w), jnp.float32(0.5),
                                    chunk=200)
    assert float(f) == pytest.approx(
        np.sum(np.logaddexp(0, m) - y * m) + 0.25 * wd @ wd, rel=1e-6)
    np.testing.assert_allclose(g, (sig - y) @ xd + 0.5 * wd, rtol=1e-5,
                               atol=1e-5)
    d2 = reference.curvature(jnp.asarray(x), jnp.asarray(w), chunk=200)
    np.testing.assert_allclose(d2, sig * (1 - sig), rtol=1e-5)
    hv = reference.hessian_vector(jnp.asarray(x), d2, jnp.asarray(v),
                                  jnp.float32(0.5), chunk=200)
    np.testing.assert_allclose(
        hv, (sig * (1 - sig) * (xd @ vd)) @ xd + 0.5 * vd, rtol=1e-5,
        atol=1e-5)
    rounded = reference.value_and_grad(
        jnp.asarray(x), jnp.asarray(y), jnp.asarray(w), jnp.float32(0.5),
        chunk=200, round_to="bfloat16")[1]
    assert 1e-4 < float(jnp.linalg.norm(rounded - g) / jnp.linalg.norm(g)) \
        < 1e-2


# --- what the family refuses ---------------------------------------------------
def test_a_program_without_hvps_is_refused_at_setup(monkeypatch):
    """A checkout whose ``OptimizerResult`` counts no products (the parent of
    PR 35) fails in ``setup``, before any data is drawn."""
    from photon_ml_tpu import optimize

    fields = [(f.name, f.type) for f in
              dataclasses.fields(optimize.OptimizerResult) if f.name != "hvps"]
    monkeypatch.setattr(optimize, "OptimizerResult",
                        dataclasses.make_dataclass("OptimizerResult", fields))
    drawn = []
    monkeypatch.setattr(family.importlib, "import_module",
                        lambda name: drawn.append(name))
    with pytest.raises(RuntimeError, match="hvps"):
        family.setup(11, CONFIG, copy.deepcopy(WORKLOAD), jax.devices()[:1])
    assert not drawn


def test_kernels_are_read_from_the_compiled_text_by_name():
    call = ('%{} = f32[1,1024]{{1,0}} custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call", operand_layout={{}}\n')
    both = call.format("fused_hvp.4") + call.format("fused_value_and_grad.11")
    assert family.kernels_in(both) == list(family.KERNELS)
    assert family.kernels_in(call.format("fused_hvp")) == ["fused_hvp"]
    # a Pallas kernel of no name (the parent's product), and XLA's own
    assert family.kernels_in(call.format("custom-call.7")) == []
    assert family.kernels_in("%fused_hvp.1 = f32[8]{0} fusion(%a)\n") == []


def test_a_solve_without_the_kernels_is_refused_on_a_tpu():
    """Set-up on a device that says ``tpu`` whose compiled solve holds no
    Pallas kernel (here: the CPU's): no cell, so no result line."""
    chip = types.SimpleNamespace(platform="tpu")
    with pytest.raises(RuntimeError, match="fused_hvp"):
        family.setup(11, CONFIG, copy.deepcopy(WORKLOAD), [chip])


# --- the program against the reference, control and faults ------------------
@pytest.fixture(scope="module")
def solved():
    """One set-up and one unit of the cell, shared by the tests below."""
    cell = family.setup(11, CONFIG, copy.deepcopy(WORKLOAD),
                        jax.devices()[:1])
    cell.unit()
    counters, required = cell.counters(), cell.required_work()
    cell.describe()  # while the program's state is held
    outputs = cell.outputs()
    return cell, outputs, counters, required


def test_program_agrees_with_the_reference(solved):
    cell, outputs, counters, required = solved
    checked = cell.check()
    assert set(c.name for c in checked) == set(WORKLOAD["limits"])
    bad = [c for c in checked if not c.ok]
    assert not bad, bad
    assert family.compare_outputs(cell, outputs) == checked
    # the counts the work is made of are the results' own
    assert counters["solves"] == 3
    assert counters["tron_iterations"] == [o["iterations"] for o in outputs]
    assert counters["tron_hvps"] == [o["hvps"] for o in outputs]
    assert required == work.solves_work(
        cell.rows, cell.dim, 4,
        [(o["iterations"], o["hvps"]) for o in outputs])
    assert all(o["iterations"] <= o["hvps"] <= 20 * o["iterations"]
               for o in outputs)
    assert outputs[0]["hvp0"].shape == outputs[0]["g0"].shape == (cell.dim,)
    assert cell.describe()["solve_program"] == "xla"  # the CPU's


def test_control_and_faults_fail_the_comparison(solved):
    cell = solved[0]
    ref = family.reference_outputs(cell)
    stood = []
    for who, outputs in family.stand_ins(cell, family.FAULTS, ref):
        stood.append(who)
        numbers = family.compare_outputs(cell, outputs, ref)
        over = [c for c in numbers if not c.ok]
        # two numbers or more, the control too; a fault is gross besides:
        # one number at ten times its limit or more
        assert len(over) >= 2, (who, over)
        if who.startswith("fault_"):
            assert any(c.value >= 10 * c.limit for c in numbers), numbers
    assert stood == ["control_bfloat16"] + [f"fault_{k}"
                                            for k in family.FAULTS]
    assert set(family.FAULTS) >= {"curvature_at_zero", "cg_one_step"}


def _run(capsys, *more):
    code = run.main(["--workload", NAME, "--seed", str(2**31 + 77),
                     "--seconds", "0.3", *(more or ("--trace", "0"))],
                    require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    return json.loads(lines[-1]), lines


def _break(monkeypatch, fault):
    from photon_ml_tpu.glm import training

    whole = training.train_glm_sweep

    def with_optimizer(config, **changed):
        return dataclasses.replace(config, optimizer_config=dataclasses.replace(
            config.optimizer_config, **changed))

    def broken(task, data, weights, config, **kw):
        if fault == "state_unchanged":
            trained = whole(task, data, weights, config, **kw)
            zero = jnp.zeros_like(trained[0].result.w)
            return [dataclasses.replace(t, result=dataclasses.replace(
                t.result, w=zero)) for t in trained]
        if fault == "stall_after_3":
            return whole(task, data, weights,
                         with_optimizer(config, max_iterations=3), **kw)
        if fault == "cg_one_step":
            return whole(task, data, weights,
                         with_optimizer(config, cg_max_iterations=1), **kw)
        if fault == "half_batch":
            half = data.labels.shape[0] // 2
            cut = lambda a: a[:half]
            data = dataclasses.replace(
                data, design=dataclasses.replace(
                    data.design, x=data.design.x[:half]),
                labels=cut(data.labels), offsets=cut(data.offsets),
                weights=cut(data.weights))
            return whole(task, data, weights, config, **kw)
        raise ValueError(fault)

    monkeypatch.setattr(training, "train_glm_sweep", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "stall_after_3",
                                   "cg_one_step", "half_batch"])
def test_broken_path_is_not_correct(tiny_cells, monkeypatch, capsys, fault):
    """The program's own solve broken underneath the family's call."""
    _break(monkeypatch, fault)
    result, _ = _run(capsys)
    assert result["correct"] is False
    over = {n for n, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert len(over) >= 2, over
    if fault in ("stall_after_3", "cg_one_step"):
        # consistent reports: only the path's own numbers can see these
        assert not over & {"grad0_gap", "report_loss_gap", "kkt_gap"}, over


def test_a_curvature_that_is_wrong_is_not_correct(tiny_cells, monkeypatch,
                                                  capsys):
    """The objective's ``d2`` held at 1/4 (the Hessian of ``w = 0``): the
    product at zero is sound, the path from the second iteration on is not."""
    from photon_ml_tpu.glm import training
    from photon_ml_tpu.ops.objective import GLMObjective

    monkeypatch.setattr(
        GLMObjective, "_d2_weights",
        lambda self, w, data: 0.25 * data.weights)
    # the compiled solve outlives a call: none traced before the fault is
    # planted may serve this run, and this run's none after it
    training._sweep_solve_fn.cache_clear()
    try:
        result, _ = _run(capsys)
    finally:
        training._sweep_solve_fn.cache_clear()
    assert result["correct"] is False
    over = {n for n, c in result["compared"].items()
            if c["value"] > c["limit"]}
    assert len(over) >= 2 and "hvp0_gap" not in over, over


def test_a_traced_run_carries_the_cells_metrics(tiny_cells, monkeypatch,
                                                capsys):
    """A whole ``--trace 1`` run here, the reduction put in by hand (no chip
    is in a CPU trace) under the names a chip's trace of the solve prints:
    every per-layer metric the manifest lists for the cell but the memory
    peak, the three new ones from the window's own counts, no share over
    100."""
    from benchmark import trace
    from photon_ml_tpu.telemetry import tracing

    tracing.GLOBAL_TRACER._ring.clear()
    monkeypatch.setattr(trace, "reduce", lambda path, chips: {
        "window_s": 1.0, "busy_s": 0.99, "device_ops": [], "idle_gaps": [],
        "per_chip": [{"busy_s": 0.99, "modules_s": {"jit_run": 0.99},
                      "ops_self_s": {"fused_hvp": 0.9,
                                     "fused_value_and_grad": 0.05,
                                     "multiply_reduce_fusion": 0.03,
                                     "while": 0.001},
                      "collective_s": 0.0}]})
    monkeypatch.setattr(manifest, "peaks", lambda kind: PEAKS)
    result, lines = _run(capsys, "--trace", "1")
    got = {n: m["value"] for n, m in result["metrics"].items()}
    assert set(REPORTED + OWN) - {"hbm_peak_gib"} == set(got)
    assert result["correct"] is True, result["compared"]
    assert got["compiles_in_window"] == 0 and got["retrace_s_per_unit"] == 0
    assert 3 <= got["tron_iters_per_solve"] <= 15
    assert 1.0 <= got["tron_hvps_per_iter"] <= 20.0
    for share in ("hvp_kernel_roofline_pct", "glm_kernel_roofline_pct",
                  "glm_solve_roofline", "train_mfu_pct"):
        assert 0 < got[share] <= 100, (share, got[share])
    info = next(json.loads(l.split(": ", 1)[1]) for l in lines
                if l.startswith("info: ") and '"paths"' in l)
    assert info["paths"]["solve_program"] == "xla"
    assert info["paths"]["optimizer"] == "TRON"
    assert info["bound"] == "bandwidth"
    w = info["work"]
    # the two kernels' shares stand to each other as their passes' bytes over
    # their times: each reads its own work and no more
    least = lambda b: b / PEAKS["hbm_bytes_per_s"]
    assert got["hvp_kernel_roofline_pct"] == pytest.approx(
        100 * least(w["hvp_kernel_bytes"]) / 0.9)
    assert got["glm_kernel_roofline_pct"] == pytest.approx(
        100 * least(w["bytes_per_chip"] - w["hvp_kernel_bytes"]) / 0.05)
    # the window's span records carry the counts the family read off the
    # results
    units = next(json.loads(l.split(": ", 1)[1]) for l in lines
                 if l.startswith("info: ") and '"units"' in l)["units"]
    iterations = w["evaluation_passes"] - 3 * units
    assert got["tron_iters_per_solve"] == pytest.approx(
        iterations / (3 * units))
    assert got["tron_hvps_per_iter"] == pytest.approx(
        w["hvp_passes"] / iterations)
