"""The three readers that take the program's own span records: on hand-made
``run`` dictionaries and hand-made records, the values by hand."""

import pytest

from benchmark import manifest
from benchmark.readers import (evals_per_iter, kernel_roofline,
                               program_records, span_seconds_per)

PEAKS = {"flops_per_s": 100e12, "hbm_bytes_per_s": 1000e9}
#: one pass: 1e9 bytes (1 ms at the peak), 1e9 operations (0.01 ms)
PASS = {"flops": 1e9, "bytes": 1e9}
#: the pair every metric file of the three names: which span's count has to
#: equal which of the family's counters for the ring to be the window's
WINDOW = {"window_span": "glm.solve", "window_count": "solves"}
KERNEL = {"op": "^fused_value_and_grad$", **WINDOW}
COMPILES = {"span": "jit.compile", "per": "glm.sweep", **WINDOW}


def _records(solves, compiles=(0.25, 0.35), sweeps=2):
    """``solves``: (iterations, evaluations) per ``glm.solve``."""
    out = [{"name": "glm.sweep", "seconds": 1.0} for _ in range(sweeps)]
    out += [{"name": "jit.compile", "seconds": s, "fn": "glm.sweep_solve"}
            for s in compiles]
    out += [{"name": "glm.solve", "seconds": 0.001, "iterations": i,
             "evaluations": e, "converged": False} for i, e in solves]
    return out


def _run(solves, kernel_s, *, counted=None, ops=None):
    passes = sum(i + 1 for i, _ in solves)
    chip = {"ops_self_s": {"fused_value_and_grad": kernel_s, "while": 0.5,
                           "fused_value_and_grad_multi": 9.0,
                           **(ops or {})}}
    if kernel_s is None:
        del chip["ops_self_s"]["fused_value_and_grad"]
    return {"counters": {"solves": len(solves) if counted is None
                         else counted},
            "work": {"flops_per_chip": PASS["flops"] * passes,
                     "bytes_per_chip": PASS["bytes"] * passes,
                     "passes": passes},
            "peaks": PEAKS, "trace": {"per_chip": [chip]}}


@pytest.fixture
def program(monkeypatch):
    """Put hand-made records in the place of the program's ring."""
    from photon_ml_tpu.telemetry import tracing

    def hold(records):
        monkeypatch.setattr(tracing, "recorded", lambda: list(records),
                            raising=False)
    return hold


SOLVES = [(80, 101), (80, 97), (40, 41)]  # 200 iterations, 239 evaluations


def test_values_by_hand(program):
    program(_records(SOLVES))
    run = _run(SOLVES, kernel_s=0.478)
    # (100 + 96 + 40) / 200
    assert evals_per_iter.read(run, WINDOW) == pytest.approx(1.18)
    # 239 evaluations x 1 ms at the peak, over 0.478 s of the kernel: the
    # while shell and the kernel of another name are not its time
    assert kernel_roofline.read(run, KERNEL) == pytest.approx(50.0)
    # (0.25 + 0.35) s of compiles over two sweeps
    assert span_seconds_per.read(run, COMPILES) == pytest.approx(0.3)


def test_no_rejected_point_reads_one(program):
    solves = [(80, 81), (3, 4)]
    program(_records(solves))
    assert evals_per_iter.read(_run(solves, 1.0), WINDOW) == 1.0


@pytest.mark.parametrize("reader, params", [
    (evals_per_iter, WINDOW), (kernel_roofline, KERNEL),
    (span_seconds_per, COMPILES)])
def test_none_when_the_solve_count_differs(program, reader, params):
    program(_records(SOLVES))
    assert reader.read(_run(SOLVES, 0.478, counted=4), params) is None


@pytest.mark.parametrize("reader, params", [
    (evals_per_iter, WINDOW), (kernel_roofline, KERNEL),
    (span_seconds_per, COMPILES)])
def test_none_when_the_program_keeps_no_records(monkeypatch, reader, params):
    from photon_ml_tpu.telemetry import tracing

    monkeypatch.delattr(tracing, "recorded", raising=False)
    assert program_records.window_records(_run(SOLVES, 0.478), WINDOW) is None
    assert reader.read(_run(SOLVES, 0.478), params) is None


@pytest.mark.parametrize("reader, params", [
    (evals_per_iter, WINDOW), (kernel_roofline, KERNEL),
    (span_seconds_per, COMPILES)])
@pytest.mark.parametrize("missing", ["window_span", "window_count", "counter"])
def test_none_where_the_window_cannot_be_checked(program, reader, params,
                                                 missing):
    """A metric file that names no span or no counter to check the window by,
    or a family that keeps no such counter: nothing is read, nothing raises."""
    program(_records(SOLVES))
    run = _run(SOLVES, 0.478)
    if missing == "counter":
        run["counters"] = {"steps": 3}
    else:
        params = {k: v for k, v in params.items() if k != missing}
    assert reader.read(run, params) is None


def test_another_family_names_its_own_span_and_counter(program):
    """``span_seconds_per`` in a cell whose unit is not a GLM sweep."""
    program([{"name": "cd.sweep", "seconds": 2.0},
             {"name": "cd.step", "seconds": 0.5},
             {"name": "cd.step", "seconds": 0.7},
             {"name": "jit.compile", "seconds": 0.3}])
    run = {"counters": {"steps": 2}}
    assert span_seconds_per.read(
        run, {"span": "jit.compile", "per": "cd.sweep",
              "window_span": "cd.step", "window_count": "steps"}) \
        == pytest.approx(0.3)


def test_none_when_the_kernel_did_not_run(program):
    program(_records(SOLVES))
    assert kernel_roofline.read(_run(SOLVES, None), KERNEL) is None


def test_none_without_sweeps_or_iterations(program):
    program(_records([(0, 1)], sweeps=0))
    run = _run([(0, 1)], 0.001)
    assert evals_per_iter.read(run, WINDOW) is None
    assert span_seconds_per.read(run, COMPILES) is None


@pytest.mark.parametrize("solves", [SOLVES, [(80, 81)], [(5, 31)]])
def test_a_kernel_at_the_peak_reads_100_and_never_more(program, solves):
    """The kernel's time is at least its evaluations' bytes over the peak:
    at exactly that, whatever the line searches rejected, the share is 100."""
    program(_records(solves))
    evaluations = sum(e for _, e in solves)
    at_peak = evaluations * PASS["bytes"] / PEAKS["hbm_bytes_per_s"]
    assert kernel_roofline.read(_run(solves, at_peak), KERNEL) \
        == pytest.approx(100.0)
    assert kernel_roofline.read(_run(solves, 2 * at_peak), KERNEL) \
        == pytest.approx(50.0)


#: the GLM family's cells, by their configuration's ``family``
GLM_CELLS = sorted(w["name"] for w in manifest.benchmark()["workloads"]
                   if manifest.cell(w["name"])[2]["family"] == "glm")


@pytest.mark.parametrize("cell", GLM_CELLS)
def test_the_manifest_names_the_three_and_their_readers(cell):
    listed = {m["name"] for m in manifest.metrics_of(cell, "per_layer")}
    for name, reader in (("lbfgs_evals_per_iter", evals_per_iter),
                         ("glm_kernel_roofline_pct", kernel_roofline),
                         ("retrace_s_per_unit", span_seconds_per)):
        assert name in listed
        assert manifest.reader(manifest.metric_file(name)["reader"]) is reader
    assert manifest.metric_file("glm_kernel_roofline_pct")["params"] == KERNEL


@pytest.mark.parametrize("cell", GLM_CELLS)
def test_a_traced_run_carries_the_three(tiny_cells, monkeypatch, capsys, cell):
    """A whole ``--trace 1`` run here, where the profiler runs but no chip is
    in its trace: with the reduction put in by hand, the result line holds
    every per-layer metric the manifest lists for the cell, the three each
    from what the program itself recorded in the window (and from nothing
    before it: the warm unit's solves are not counted, and its compile is
    not the window's: the compiled solve outlives the call)."""
    import json

    import jax

    from benchmark import run, trace

    from photon_ml_tpu.telemetry import tracing

    chips = manifest.cell(cell)[0]["chips"]
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} (virtual) devices")
    # a run is a process of its own, and the ring its window's: here one
    # process makes a traced run per cell
    tracing.GLOBAL_TRACER._ring.clear()
    kernel_s = 1e-9  # far under any least time: what is read is the count
    monkeypatch.setattr(trace, "reduce", lambda path, chips: {
        "window_s": 1.0, "busy_s": 0.5, "device_ops": [], "idle_gaps": [],
        "per_chip": [{"busy_s": 0.5, "modules_s": {"jit_run": 0.5},
                      "ops_self_s": {"fused_value_and_grad": kernel_s},
                      "collective_s": 0.001}] * chips})
    monkeypatch.setattr(manifest, "peaks", lambda kind: PEAKS)
    code = run.main(["--workload", cell, "--seed", "11", "--seconds", "0.5",
                     "--trace", "1"], require_tpu=False)
    lines = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    got = json.loads(lines[-1])["metrics"]
    listed = {m["name"] for m in manifest.metrics_of(cell, "per_layer")}
    # the CPU keeps no count of its memory's peak: that one reads nothing
    assert listed - {"hbm_peak_gib"} <= set(got) <= listed
    assert got["lbfgs_evals_per_iter"]["value"] >= 1.0
    # the guard that PR 25's gain stays: no re-trace, no compile in a window
    assert got["retrace_s_per_unit"]["value"] == 0.0
    assert got["compiles_in_window"]["value"] == 0
    # the scale is evaluations over passes, so the two shares of one run
    # stand as evaluations to passes too
    work = next(json.loads(l.split(": ", 1)[1])["work"] for l in lines
                if l.startswith("info: ") and '"work"' in l)
    least = work["bytes_per_chip"] / PEAKS["hbm_bytes_per_s"]
    assert got["glm_kernel_roofline_pct"]["value"] * kernel_s / 100.0 \
        >= least * (1 - 1e-9)
