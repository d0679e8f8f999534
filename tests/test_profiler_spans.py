"""The solve's evaluation counter, and the program's spans on the profiler's
clock (``optimize/``, ``telemetry/tracing.py``, ``telemetry/profiling.py``,
``glm/training.py``, ``tools/perf_report.py --xplane``).

The contracts:

- ``OptimizerResult.evaluations`` is the number of calls of the
  value-and-gradient function, the one at ``w0`` included: equal to a count
  kept on the Python side, and at least ``iterations + 1``;
- while a JAX profiler session runs, a span is kept (``recorded()``) and is
  an event on a host plane of the ``.xplane.pb``; with no profiler, sink or
  tap nothing is kept;
- a device scalar among a span's attributes is held by reference: no span
  waits for the device, the number appears when the record is read or
  flushed;
- ``train_glm_sweep`` under a profiler yields ``glm.sweep`` > ``glm.solve``
  (one a weight) > ``jit.compile`` (at the first solve's call, on the
  process's first call with a signature: the fixtures here clear the held
  solve, ``tests/test_glm_training.py`` holds the second call to none).
"""

import glob
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.glm import training
from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
from photon_ml_tpu.ops.design import DenseDesign
from photon_ml_tpu.ops.objective import GLMData
from photon_ml_tpu.ops.regularization import L1Regularization, L2Regularization
from photon_ml_tpu.optimize import (
    OptimizerConfig,
    minimize_lbfgs,
    minimize_owlqn,
    minimize_tron,
)
from photon_ml_tpu.telemetry import profiling, tracing
from photon_ml_tpu.telemetry.metrics import MetricsRegistry
from photon_ml_tpu.telemetry.tracing import Tracer
from photon_ml_tpu.types import OptimizerType, TaskType

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import perf_report  # noqa: E402

WEIGHTS = [10.0, 1.0, 0.1]


# --- the evaluation counter ------------------------------------------------

def _counted_problem():
    """A badly scaled quadratic plus a quartic (so that line searches and
    trust regions reject points), and the list its calls are counted in."""
    scales = jnp.asarray(10.0 ** np.linspace(-2, 2, 6))
    calls = []

    def value(w):
        return 0.5 * jnp.sum(scales * jnp.square(w - 1.0)) \
            + 0.25 * jnp.sum(jnp.square(jnp.square(w)))

    def fun(w):
        jax.debug.callback(lambda _: calls.append(1), w)
        return value(w), jax.grad(value)(w)

    def hvp(w, v):
        return jax.jvp(jax.grad(value), (w,), (v,))[1]

    return fun, hvp, calls


def _solve(name, fun, hvp, w0, config):
    if name == "lbfgs":
        return minimize_lbfgs(fun, w0, config)
    if name == "owlqn":
        return minimize_owlqn(fun, w0, 0.05, config)
    return minimize_tron(fun, hvp, w0, config)


@pytest.mark.parametrize("name", ["lbfgs", "owlqn", "tron"])
def test_evaluations_equal_a_python_side_count(name):
    fun, hvp, calls = _counted_problem()
    result = _solve(name, fun, hvp, jnp.full((6,), 3.0),
                    OptimizerConfig(max_iterations=30, tolerance=1e-9))
    jax.effects_barrier()
    evaluations, iterations = int(result.evaluations), int(result.iterations)
    assert result.evaluations.dtype == jnp.int32
    assert evaluations == len(calls)
    assert evaluations >= iterations + 1 > 1
    if name == "tron":
        # one call of fun an iteration; the Hessian-vector products of the
        # inner CG are another quantity
        assert evaluations == iterations + 1
    else:
        assert evaluations > iterations + 1  # a rejected trial point counted


def test_evaluations_is_one_where_the_start_is_the_answer():
    fun = lambda w: (0.5 * jnp.vdot(w, w), w)
    result = minimize_lbfgs(fun, jnp.zeros((4,)))
    assert int(result.iterations) == 0 and int(result.evaluations) == 1


def test_vmapped_lanes_count_their_own_evaluations():
    """Under vmap the line search runs until the slowest lane accepts; a
    lane that accepted earlier keeps its own count."""
    scales = jnp.asarray(10.0 ** np.linspace(-2, 2, 6))

    def solve(center):
        def value(w):
            return 0.5 * jnp.sum(scales * jnp.square(w - center)) \
                + 0.25 * jnp.sum(jnp.square(jnp.square(w)))
        fun = lambda w: (value(w), jax.grad(value)(w))
        return minimize_lbfgs(fun, jnp.full((6,), 3.0), OptimizerConfig(
            max_iterations=20, track_states=False))

    centers = jnp.asarray([0.0, 1.0, 30.0])
    batched = jax.vmap(solve)(centers)
    assert batched.evaluations.shape == (3,)
    assert bool(jnp.all(batched.evaluations >= batched.iterations + 1))
    for i, c in enumerate(centers):
        alone = solve(c)
        assert int(batched.evaluations[i]) == int(alone.evaluations)
        assert int(batched.iterations[i]) == int(alone.iterations)


# --- spans under a profiler ------------------------------------------------

def _glm_data(n=300, d=6, seed=5):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    y = (rng.random(n) < 1 / (1 + np.exp(-(x @ rng.normal(size=d)))))
    y = jnp.asarray(y, jnp.float64)
    return GLMData(design=DenseDesign(jnp.asarray(x)), labels=y,
                   offsets=jnp.zeros(n), weights=jnp.ones(n))


def _sweep(data):
    return training.train_glm_sweep(
        TaskType.LOGISTIC_REGRESSION, data, WEIGHTS,
        GLMOptimizationConfiguration(regularization=L2Regularization))


@pytest.fixture(autouse=True)
def no_solve_held():
    """``train_glm_sweep`` keeps its compiled solve across calls; the tests
    here read the span tree of a signature's FIRST call, ``jit.compile``
    and all, whatever ran before them in the process."""
    training._sweep_solve_fn.cache_clear()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiler session over the global tracer: a span with a device
    scalar, nested spans one of which outlives its parent's thread hand-off,
    and a ``train_glm_sweep``. Returns the ring's records as read after the
    session, and the trace file."""
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    data = _glm_data()
    training._sweep_solve_fn.cache_clear()  # set up before no_solve_held
    tracing.GLOBAL_TRACER._ring.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with tracing.span("t.outer", kind="test") as outer:
            with tracing.span("t.inner") as inner:
                inner.set(total=jnp.arange(5).sum(), flag=jnp.asarray(True))
            with tracing.span_under(outer.span_id, "t.leg"):
                with tracing.span("t.leg_child"):
                    pass
        with tracing.span_under(outer.span_id, "t.late_leg"):
            pass  # its parent closed above: re-parented to root
        trained = _sweep(data)
    finally:
        jax.profiler.stop_trace()
    with tracing.span("t.after"):
        pass
    records = tracing.recorded()
    tracing.GLOBAL_TRACER._ring.clear()
    (xplane,) = glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    return {"records": records, "xplane": xplane, "trained": trained,
            "by_name": {r["name"]: r for r in records}}


def test_span_is_kept_with_device_scalars_as_numbers(traced):
    inner = traced["by_name"]["t.inner"]
    assert inner["total"] == 10 and type(inner["total"]) is int
    assert inner["flag"] is True
    assert traced["by_name"]["t.outer"]["kind"] == "test"
    json.dumps(traced["records"])  # every record is plain data


def test_nothing_is_kept_once_the_profiler_stopped(traced):
    assert "t.after" not in traced["by_name"]


def test_nesting_contract_holds_for_kept_records(traced):
    by = traced["by_name"]
    assert by["t.outer"]["parent_id"] is None
    assert by["t.inner"]["parent_id"] == by["t.outer"]["span_id"]
    assert by["t.leg"]["parent_id"] == by["t.outer"]["span_id"]
    assert by["t.leg_child"]["parent_id"] == by["t.leg"]["span_id"]
    assert by["t.late_leg"]["parent_id"] is None
    by_id = {r["span_id"]: r for r in traced["records"]}
    for r in traced["records"]:
        if r["parent_id"] is not None:
            parent = by_id[r["parent_id"]]
            assert parent["t0"] <= r["t0"] and r["t1"] <= parent["t1"]


def test_spans_are_events_on_a_host_plane_of_the_trace(traced):
    spans = perf_report.load_program_spans(traced["xplane"])
    names = [n for _, _, n in spans]
    for name in ("t.outer", "t.inner", "glm.sweep", "jit.compile"):
        assert names.count(name) == 1, name
    assert names.count("glm.solve") == len(WEIGHTS)
    assert "t.after" not in names
    # on one clock: the event nests as the record does
    at = {n: (s, e) for s, e, n in spans}
    assert at["t.outer"][0] <= at["t.inner"][0] \
        and at["t.inner"][1] <= at["t.outer"][1]
    assert at["glm.sweep"][0] <= at["jit.compile"][0] \
        and at["jit.compile"][1] <= at["glm.sweep"][1]


def test_train_glm_sweep_span_tree(traced):
    records = traced["records"]
    by_id = {r["span_id"]: r for r in records}
    (sweep,) = [r for r in records if r["name"] == "glm.sweep"]
    assert sweep["solves"] == len(WEIGHTS) and sweep["warm_start"] is True
    assert sweep["optimizer"] == "LBFGS"
    solves = [r for r in records if r["name"] == "glm.solve"]
    assert [s["regularization_weight"] for s in solves] \
        == sorted(WEIGHTS, reverse=True)
    for s, t in zip(solves, traced["trained"]):
        assert s["parent_id"] == sweep["span_id"]
        assert s["iterations"] == int(t.result.iterations)
        assert s["evaluations"] == int(t.result.evaluations)
        assert s["evaluations"] >= s["iterations"] + 1
        assert s["hvps"] == 0 and type(s["hvps"]) is int
        assert s["converged"] is bool(t.result.converged)
    (compiled,) = [r for r in records if r["name"] == "jit.compile"]
    assert compiled["fn"] == "glm.sweep_solve"
    assert compiled["seconds"] >= compiled["lower_s"] + compiled["compile_s"] \
        > 0
    # the compile happens at the first solve's call
    assert compiled["parent_id"] == solves[0]["span_id"]
    ancestors = []
    at = compiled
    while at["parent_id"] is not None:
        at = by_id[at["parent_id"]]
        ancestors.append(at["name"])
    assert ancestors == ["glm.solve", "glm.sweep"]


@pytest.mark.parametrize("optimizer, regularization, named", [
    (OptimizerType.TRON, L2Regularization, "TRON"),
    (OptimizerType.LBFGS, L1Regularization, "OWLQN"),
])
def test_sweep_names_its_minimizer_and_solves_carry_their_products(
        optimizer, regularization, named):
    """``glm.sweep{optimizer}`` is the minimizer the configuration selects
    (OWL-QN whenever the regularization has an L1 part), and a TRON solve's
    ``glm.solve{hvps}`` is its result's count of Hessian-vector products."""
    data = _glm_data()
    records = []
    untap = tracing.GLOBAL_TRACER.add_tap(records.append)
    try:
        trained = training.train_glm_sweep(
            TaskType.LOGISTIC_REGRESSION, data, WEIGHTS,
            GLMOptimizationConfiguration(
                optimizer=optimizer, regularization=regularization,
                optimizer_config=OptimizerConfig(max_iterations=15,
                                                 cg_max_iterations=20)))
        tracing.flush()
    finally:
        untap()
    (sweep,) = [r for r in records if r["name"] == "glm.sweep"]
    assert sweep["optimizer"] == named
    solves = [r for r in records if r["name"] == "glm.solve"]
    assert len(solves) == len(WEIGHTS)
    for s, t in zip(solves, trained):
        assert s["hvps"] == int(t.result.hvps) and type(s["hvps"]) is int
        if named == "TRON":
            assert s["iterations"] <= s["hvps"] <= 20 * s["iterations"]
            assert s["evaluations"] == s["iterations"] + 1
        else:
            assert s["hvps"] == 0


# --- without a profiler ------------------------------------------------------

def test_nothing_is_kept_without_profiler_sink_or_tap():
    tracing.GLOBAL_TRACER._ring.clear()
    assert not jax.profiler.TraceAnnotation.is_enabled()
    _sweep(_glm_data(n=64))
    with tracing.span("t.unkept"):
        pass
    assert tracing.recorded() == []


def test_a_process_without_jax_keeps_nothing_and_imports_nothing(monkeypatch):
    monkeypatch.setitem(sys.modules, "jax", None)
    assert tracing._profiler_running() is False
    tracer = Tracer()
    with tracer.span("t.router") as sp:
        pass
    assert sp.seconds >= 0 and tracer.recorded() == []


class _Scalar:
    """What the tracer takes for a device scalar, with its readiness in the
    test's hands: one that is not ready never will be, one that ``fails``
    raises where a wait for it would end."""

    def __init__(self, value, ready=False, fails=False):
        self.value, self.ready, self.fails = value, ready, fails

    def block_until_ready(self):
        return self

    def is_ready(self):
        return self.ready

    def tolist(self):
        if self.fails or not self.ready:
            raise RuntimeError("the program failed")
        return self.value


def test_no_span_waits_for_the_device(tmp_path, monkeypatch):
    """With a sink, the ``glm.solve`` records hold device scalars: whatever
    is written before ``flush`` is written without a wait, ``flush`` writes
    the rest, and the file keeps its order (a child before its parent)."""
    waits = []
    whole = tracing._resolve

    def resolve(record, wait=True):
        waits.append(wait)
        return whole(record, wait)
    monkeypatch.setattr(tracing, "_resolve", resolve)
    path = str(tmp_path / "trace.jsonl")
    tracing.configure(path)
    try:
        trained = _sweep(_glm_data(n=64))
        assert waits and not any(waits)
        tracing.flush()
        written = [json.loads(line) for line in open(path)]
    finally:
        tracing.close()
    assert not any("unresolved" in r for r in written)
    solves = [r for r in written if r["name"] == "glm.solve"]
    assert [s["evaluations"] for s in solves] \
        == [int(t.result.evaluations) for t in trained]
    names = [r["name"] for r in written]
    assert names.count("glm.sweep") == 1 == names.count("jit.compile")
    at = {r["span_id"]: i for i, r in enumerate(written)}
    assert all(at[r["span_id"]] < at[r["parent_id"]] for r in written
               if r["parent_id"] is not None)
    assert tracing.recorded() == []  # a sink alone fills no ring


def test_a_value_not_computed_holds_its_record_and_those_behind_it(tmp_path):
    tracer = Tracer()
    path = str(tmp_path / "t.jsonl")
    tracer.configure(path)
    seen = []
    remove = tracer.add_tap(seen.append)
    count = _Scalar(4)
    with tracer.span("held") as sp:
        sp.set(count=count)
    with tracer.span("plain", k=1):
        pass
    assert seen == []
    count.ready = True
    with tracer.span("later"):  # finds the value computed: all three go
        pass
    assert [r["name"] for r in seen] == ["held", "plain", "later"]
    assert seen[0]["count"] == 4 and "unresolved" not in seen[0]
    tracer.close()
    remove()
    assert [json.loads(line)["name"] for line in open(path)] \
        == ["held", "plain", "later"]


def test_close_writes_what_was_held_back(tmp_path):
    tracer = Tracer()
    path = str(tmp_path / "t.jsonl")
    tracer.configure(path)
    count = jnp.int32(3) + 1
    with tracer.span("held") as sp:
        sp.set(count=count)
    with tracer.span("plain", k=1):
        pass
    tracer.close()
    written = [json.loads(line) for line in open(path)]
    assert [r["name"] for r in written] == ["held", "plain"]
    assert written[0]["count"] == 4


def test_flush_without_wait_marks_what_is_not_computed(tmp_path):
    tracer = Tracer()
    seen = []
    remove = tracer.add_tap(seen.append)
    with tracer.span("held", k=2) as sp:
        sp.set(count=_Scalar(4), done=_Scalar(True, ready=True))
    tracer.flush(wait=False)
    remove()
    (record,) = seen
    assert record["count"] is None and record["unresolved"] == ["count"]
    assert record["done"] is True and record["k"] == 2
    json.dumps(record)


def test_a_failed_value_is_marked_and_close_does_not_raise(tmp_path):
    tracer = Tracer()
    path = str(tmp_path / "t.jsonl")
    tracer.configure(path)
    with tracer.span("failed") as sp:
        sp.set(count=_Scalar(0, fails=True))
    with tracer.span("plain"):
        pass
    tracer.close()  # waits for the value, and the wait ends in an error
    written = [json.loads(line) for line in open(path)]
    assert [r["name"] for r in written] == ["failed", "plain"]
    assert written[0]["count"] is None
    assert written[0]["unresolved"] == ["count"]


def test_the_held_back_records_are_bounded():
    tracer = Tracer()
    seen = []
    remove = tracer.add_tap(seen.append)
    with tracer.span("held") as sp:
        sp.set(count=_Scalar(4))
    for i in range(tracing.PENDING_RECORDS - 1):
        with tracer.span("s", i=i):
            pass
    assert seen == [] and len(tracer._pending) == tracing.PENDING_RECORDS
    with tracer.span("one_more"):
        pass
    remove()
    assert len(tracer._pending) == 0
    assert len(seen) == tracing.PENDING_RECORDS + 1
    assert seen[0]["name"] == "held" and seen[0]["unresolved"] == ["count"]
    assert seen[-1]["name"] == "one_more"


def test_a_flight_dump_holds_the_spans_a_hung_device_held_back(tmp_path):
    from photon_ml_tpu.telemetry.flightrec import FlightRecorder

    tracer = Tracer()
    recorder = FlightRecorder(str(tmp_path))
    recorder.install(tracer=tracer)
    with tracer.span("glm.solve") as sp:
        sp.set(evaluations=_Scalar(97))
    assert recorder.records() == []
    path = recorder.dump("watchdog_stall")
    recorder.close()
    (span,) = [r["record"] for r in map(json.loads, open(path))
               if r.get("kind") == "span"]
    assert span["name"] == "glm.solve" and span["evaluations"] is None
    assert span["unresolved"] == ["evaluations"]


def test_the_ring_is_bounded_and_fills_under_a_profiler_only(monkeypatch):
    tracer = Tracer()
    remove = tracer.add_tap(lambda record: None)
    with tracer.span("s", i=-1):
        pass
    assert tracer.recorded() == []  # a tap alone fills no ring
    monkeypatch.setattr(tracing, "_profiler_running", lambda: True)
    for i in range(tracing.RING_RECORDS + 10):
        with tracer.span("s", i=i):
            pass
    remove()
    kept = tracer.recorded()
    assert len(kept) == tracing.RING_RECORDS
    assert kept[0]["i"] == 10 and kept[-1]["i"] == tracing.RING_RECORDS + 9


def test_compile_counter_reads_the_span_bracket(tmp_path):
    """``photon_compile_seconds_total`` and the ``jit.compile`` span are one
    bracket: the same seconds, to the last digit."""
    reg = MetricsRegistry()
    seen = []
    remove = tracing.GLOBAL_TRACER.add_tap(seen.append)
    try:
        p = profiling.profile_jit(lambda x: jnp.tanh(x).sum(), "t.bracket",
                                  registry=reg)
        p(jnp.ones((8,)))
        p(jnp.ones((8,)))  # cached: no second compile, no second span
    finally:
        remove()
    (span,) = [r for r in seen if r["name"] == "jit.compile"]
    assert span["fn"] == "t.bracket"
    counted = reg.get("photon_compile_seconds_total").labels(
        fn="t.bracket").value
    assert counted == span["seconds"] > 0


# --- tools/perf_report.py --xplane ------------------------------------------

def test_gaps_go_under_the_innermost_span_at_their_midpoint():
    ms = 1_000_000
    spans = [(0, 100 * ms, "bench.unit"), (5 * ms, 60 * ms, "glm.sweep"),
             (10 * ms, 40 * ms, "glm.solve"), (12 * ms, 38 * ms, "jit.compile")]
    ops = [(0, 11 * ms, "%a = f32[] fusion()"),       # busy to 11
           (36 * ms, 45 * ms, "%k.1 = custom-call()"),  # gap 11-36: compile
           (45 * ms + 1000, 50 * ms, "%k.2 = custom-call()"),  # 1 us: short
           (58 * ms, 80 * ms, "%k.3 = custom-call()"),  # gap 50-58: sweep's own
           (95 * ms, 99 * ms, "%b = f32[] fusion()")]   # gap 80-95: unit's own
    table = perf_report.gaps_by_span(ops, spans)
    assert table["window_ns"] == 100 * ms
    assert table["rows"] == {"jit.compile": [1, 25 * ms],
                             "glm.sweep": [1, 8 * ms],
                             "bench.unit": [1, 15 * ms]}
    assert table["short_ns"] == 1000 + 1 * ms  # the 1 us and the last 1 ms
    assert table["idle_ns"] == sum(ns for _, ns in table["rows"].values()) \
        + table["short_ns"]


def test_a_gap_outside_every_span_is_named_so():
    ms = 1_000_000
    spans = [(0, 10 * ms, "glm.sweep"), (30 * ms, 40 * ms, "glm.sweep")]
    ops = [(0, 10 * ms, "%a = fusion()"), (30 * ms, 40 * ms, "%b = fusion()")]
    table = perf_report.gaps_by_span(ops, spans)
    assert table["rows"] == {perf_report.NO_SPAN: [1, 20 * ms]}


def test_xplane_report_refuses_a_trace_without_device_operations(
        traced, capsys):
    """A trace made here holds the spans and no chip: the tool says so and
    writes no table."""
    assert perf_report.main(["--xplane", traced["xplane"]]) == 1
    captured = capsys.readouterr()
    assert "no device operation" in captured.err and captured.out == ""
    with pytest.raises(SystemExit):
        perf_report.main([])
