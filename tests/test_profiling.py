"""Performance-profiling layer tests (telemetry/profiling.py + tools).

The load-bearing contracts:

- **compile/execute split**: a profiled jit compiles once per abstract
  signature (counted, timed) and dispatches the cached executable on
  every later call — statics key by value, shapes by abstract signature,
  tracer calls inline without counting;
- **program memory**: ``photon_peak_memory_bytes`` holds the heaviest
  compiled program's footprint, and later calls do not move it;
- **training flat-recompile contract**: a second GAME fit of identical
  shapes — and every CD sweep after the first — triggers ZERO new
  compiles (the training analog of serving's zero-recompile warmup
  contract);
- **perf_report golden**: the critical-path report is a deterministic
  function of (trace.jsonl, metrics.prom);
- **bench_gate verdicts**: ok / regression / infra-failure /
  missing-baseline, including a device-unreachable (rc=3) artifact.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

from photon_ml_tpu.telemetry import profiling
from photon_ml_tpu.telemetry.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_gate  # noqa: E402
import perf_report  # noqa: E402


def _val(reg, name, fn):
    fam = reg.get(name)
    assert fam is not None, name
    return fam.labels(fn=fn).value


class TestProfiledFunction:
    def test_compile_once_execute_many(self):
        import jax.numpy as jnp

        reg = MetricsRegistry()

        def f(x, w):
            return x @ w

        p = profiling.profile_jit(f, "t.matmul", registry=reg)
        x = jnp.ones((16, 8), jnp.float32)
        w = jnp.ones((8, 4), jnp.float32)
        outs = [np.asarray(p(x, w)) for _ in range(3)]
        assert all(np.array_equal(o, outs[0]) for o in outs)
        np.testing.assert_allclose(outs[0], np.full((16, 4), 8.0))
        assert p.compiles == 1
        assert _val(reg, "photon_compiles_total", "t.matmul") == 1
        assert _val(reg, "photon_compile_seconds_total", "t.matmul") > 0
        assert reg.get("photon_execute_latency_seconds").labels(
            fn="t.matmul").count == 3

    def test_new_shape_and_static_value_compile_again(self):
        import jax.numpy as jnp

        reg = MetricsRegistry()
        p = profiling.profile_jit(lambda x, n: x * n, "t.scale",
                                  static_argnames=("n",), registry=reg)
        x = jnp.ones((4,), jnp.float32)
        assert float(p(x, 2)[0]) == 2.0
        assert float(p(x, 2)[0]) == 2.0
        assert p.compiles == 1
        assert float(p(x, 3)[0]) == 3.0  # new static value
        assert p.compiles == 2
        assert p(jnp.ones((8,), jnp.float32), 3).shape == (8,)  # new shape
        assert p.compiles == 3

    def test_peak_memory_on_the_gauge_and_stable_across_calls(self):
        """One executable: its memory footprint is on the gauge after the
        compile, and executing it again moves nothing."""
        import jax.numpy as jnp

        reg = MetricsRegistry()
        p = profiling.profile_jit(
            lambda x, w: jnp.tanh(x @ w).sum(), "t.cost", registry=reg)
        x = jnp.ones((32, 16), jnp.float32)
        w = jnp.ones((16, 8), jnp.float32)
        p(x, w)
        peak = _val(reg, "photon_peak_memory_bytes", "t.cost")
        assert peak > 0
        p(x, w)
        p(x, w)
        assert _val(reg, "photon_peak_memory_bytes", "t.cost") == peak
        assert _val(reg, "photon_compiles_total", "t.cost") == 1

    def test_pytree_args_and_outputs(self):
        import jax.numpy as jnp

        reg = MetricsRegistry()
        p = profiling.profile_jit(
            lambda d: {"sum": d["a"] + d["b"], "prod": d["a"] * d["b"]},
            "t.tree", registry=reg)
        out = p({"a": jnp.float32(2.0), "b": jnp.float32(3.0)})
        assert float(out["sum"]) == 5.0 and float(out["prod"]) == 6.0
        assert p.compiles == 1

    def test_tracer_call_inlines_without_counting(self):
        import jax
        import jax.numpy as jnp

        reg = MetricsRegistry()
        inner = profiling.profile_jit(lambda x: x * 2, "t.inner",
                                      registry=reg)
        outer = jax.jit(lambda x: inner(x) + 1)
        assert float(outer(jnp.float32(3.0))) == 7.0
        assert inner.compiles == 0
        assert _val(reg, "photon_compiles_total", "t.inner") == 0

    def test_concurrent_same_signature_compiles_once(self):
        import jax.numpy as jnp

        reg = MetricsRegistry()
        p = profiling.profile_jit(lambda x: (x * x).sum(), "t.race",
                                  registry=reg)
        x = jnp.ones((64, 64), jnp.float32)
        results = []
        threads = [threading.Thread(target=lambda: results.append(
            float(p(x)))) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert results == [4096.0] * 8
        assert p.compiles == 1

    def test_record_compile_and_total_compiles(self):
        reg = MetricsRegistry()
        profiling.record_compile("t.manual", registry=reg)
        profiling.record_compile("t.manual", seconds=1.5, registry=reg)
        profiling.record_compile("t.other", registry=reg)
        assert _val(reg, "photon_compiles_total", "t.manual") == 2
        assert _val(reg, "photon_compile_seconds_total", "t.manual") == 1.5
        assert profiling.total_compiles(reg) == 3


class TestTrainingFlatRecompile:
    def test_second_fit_and_later_sweeps_compile_nothing(self):
        """The training zero-recompile contract, estimator-level: after
        the shapes are warm, neither extra CD sweeps nor a whole second
        fit of the same shapes triggers a single profiled-jit compile."""
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from test_game import make_mixed_data

        from photon_ml_tpu.game.data import RandomEffectDatasetConfig
        from photon_ml_tpu.game.estimator import (
            FixedEffectCoordinateConfig,
            GameEstimator,
            GameOptimizationConfiguration,
            RandomEffectCoordinateConfig,
        )
        from photon_ml_tpu.glm.problem import GLMOptimizationConfiguration
        from photon_ml_tpu.ops.regularization import L2Regularization
        from photon_ml_tpu.types import TaskType

        data, _ = make_mixed_data(n=400, n_entities=9)

        def fit(n_sweeps):
            est = GameEstimator(
                task=TaskType.LOGISTIC_REGRESSION,
                coordinate_configs={
                    "global": FixedEffectCoordinateConfig(
                        feature_shard_id="fixed",
                        optimization=GLMOptimizationConfiguration(
                            regularization=L2Regularization)),
                    "perEntity": RandomEffectCoordinateConfig(
                        dataset=RandomEffectDatasetConfig("entityId", "re"),
                        optimization=GLMOptimizationConfiguration(
                            regularization=L2Regularization)),
                },
                update_sequence=["global", "perEntity"],
                n_cd_iterations=n_sweeps)
            return est.fit(data, [GameOptimizationConfiguration(
                {"global": 0.01, "perEntity": 1.0})])[0]

        fit(1)  # pays whatever compiles the shapes need
        warm = profiling.total_compiles()
        r = fit(3)  # three more sweeps AND a fresh estimator/dataset
        assert profiling.total_compiles() == warm, \
            "extra sweeps / a second same-shape fit must not recompile"
        assert r.model is not None


TRACE_FIXTURE = [
    {"name": "train_game", "span_id": 1, "parent_id": None, "ts": 100.0,
     "t0": 0.0, "t1": 10.0, "seconds": 10.0},
    {"name": "Read training data", "span_id": 2, "parent_id": 1,
     "ts": 100.1, "t0": 0.1, "t1": 2.1, "seconds": 2.0, "kind": "stage"},
    {"name": "cd.sweep", "span_id": 3, "parent_id": 1, "ts": 102.0,
     "t0": 2.2, "t1": 9.2, "seconds": 7.0, "sweep": 0, "compiles": 2},
    {"name": "cd.step", "span_id": 4, "parent_id": 3, "ts": 102.1,
     "t0": 2.3, "t1": 6.3, "seconds": 4.0, "coordinate": "global",
     "sweep": 0, "loss": 1.0, "grad_norm": 0.5},
    {"name": "cd.step", "span_id": 5, "parent_id": 3, "ts": 106.0,
     "t0": 6.4, "t1": 8.9, "seconds": 2.5, "coordinate": "perUser",
     "sweep": 0, "loss": 0.8, "grad_norm": 0.3},
    {"name": "optimizer_trace", "span_id": None, "parent_id": 4,
     "ts": 105.0, "coordinate": "global"},  # annotation: must be ignored
]

PROM_FIXTURE = """\
# HELP photon_compiles_total compiles
# TYPE photon_compiles_total counter
photon_compiles_total{fn="game.fixed_effect"} 1
photon_compiles_total{fn="game.re.sweep_fused"} 1
# HELP photon_compile_seconds_total compile seconds
# TYPE photon_compile_seconds_total counter
photon_compile_seconds_total{fn="game.fixed_effect"} 2.5
photon_compile_seconds_total{fn="game.re.sweep_fused"} 1.5
# HELP photon_execute_latency_seconds execute latency
# TYPE photon_execute_latency_seconds histogram
photon_execute_latency_seconds_bucket{fn="game.fixed_effect",le="1"} 2
photon_execute_latency_seconds_bucket{fn="game.fixed_effect",le="+Inf"} 2
photon_execute_latency_seconds_sum{fn="game.fixed_effect"} 0.5
photon_execute_latency_seconds_count{fn="game.fixed_effect"} 2
# HELP photon_optimizer_iterations_total iters
# TYPE photon_optimizer_iterations_total counter
photon_optimizer_iterations_total{coordinate="global"} 12
"""

EXPECTED_REPORT = """\
== photon performance report ==
wall 10.000 s across 1 root span(s) [train_game]

-- critical path: top 5 span groups by exclusive seconds --
 exclusive_s    total_s  calls  span
       4.000      4.000      1  cd.step{coordinate=global}
       2.500      2.500      1  cd.step{coordinate=perUser}
       2.000      2.000      1  Read training data
       1.000     10.000      1  train_game
       0.500      7.000      1  cd.sweep

-- compile vs execute (profiled jits) --
fn                           compiles  compile_s   execs  execute_s
game.fixed_effect                   1      2.500       2      0.500
game.re.sweep_fused                 1      1.500       0      0.000
TOTAL                               2      4.000       2      0.500
compile share of (compile+execute): 88.9%

-- coordinate descent: per-coordinate --
coordinate        steps    total_s    mean_s  opt_iters
global                1      4.000     4.000         12
perUser               1      2.500     2.500          0
"""


class TestPerfReport:
    def test_golden_report(self, tmp_path):
        trace = tmp_path / "trace.jsonl"
        trace.write_text("".join(json.dumps(r) + "\n"
                                 for r in TRACE_FIXTURE))
        spans = perf_report.load_spans(str(trace))
        assert len(spans) == 5  # the annotation is dropped
        got = perf_report.build_report(spans, PROM_FIXTURE, top=5)
        assert got == EXPECTED_REPORT

    def test_cli_renders_run_dir(self, tmp_path, capsys):
        (tmp_path / "trace.jsonl").write_text(
            "".join(json.dumps(r) + "\n" for r in TRACE_FIXTURE))
        (tmp_path / "metrics.prom").write_text(PROM_FIXTURE)
        assert perf_report.main([str(tmp_path), "--top", "3"]) == 0
        out = capsys.readouterr().out
        assert "critical path" in out and "compile vs execute" in out

    def test_prefers_merged_artifacts(self, tmp_path):
        (tmp_path / "trace.jsonl").write_text("")
        (tmp_path / "trace.merged.jsonl").write_text("")
        (tmp_path / "metrics.prom").write_text("")
        (tmp_path / "metrics.aggregate.prom").write_text("")
        t, m = perf_report.resolve_inputs(str(tmp_path))
        assert t.endswith("trace.merged.jsonl")
        assert m.endswith("metrics.aggregate.prom")


# async-I/O overlap fixture: one sweep [2, 8]; a background save spans
# [6, 10] (2 s of its 4 s hidden under the sweep), its child part span
# must NOT double-count; a read spans [0, 4] (2 s hidden)
OVERLAP_TRACE = [
    {"name": "train_game", "span_id": 1, "parent_id": None, "ts": 100.0,
     "t0": 0.0, "t1": 11.0, "seconds": 11.0},
    {"name": "cd.sweep", "span_id": 2, "parent_id": 1, "ts": 102.0,
     "t0": 2.0, "t1": 8.0, "seconds": 6.0, "sweep": 0},
    {"name": "io.save.model", "span_id": 3, "parent_id": 1, "ts": 106.0,
     "t0": 6.0, "t1": 10.0, "seconds": 4.0, "path": "out/best"},
    {"name": "io.save.part", "span_id": 4, "parent_id": 3, "ts": 106.1,
     "t0": 6.1, "t1": 9.9, "seconds": 3.8, "coordinate": "perUser"},
    {"name": "io.read.validation", "span_id": 5, "parent_id": 1,
     "ts": 100.0, "t0": 0.0, "t1": 4.0, "seconds": 4.0},
]


def _with_process(spans):
    # load_spans stamps process=0; direct fixtures do the same here
    return [dict(s, process=0) for s in spans]


class TestIoOverlap:
    def test_overlap_numbers(self):
        ov = perf_report.io_overlap(_with_process(OVERLAP_TRACE))
        assert ov["train_wall_s"] == pytest.approx(6.0)
        # nested io.save.part is counted through its parent only
        assert ov["save"]["spans"] == 1
        assert ov["save"]["seconds"] == pytest.approx(4.0)
        assert ov["save"]["hidden_seconds"] == pytest.approx(2.0)
        assert ov["save"]["hidden_pct"] == pytest.approx(50.0)
        assert ov["read"]["seconds"] == pytest.approx(4.0)
        assert ov["read"]["hidden_seconds"] == pytest.approx(2.0)

    def test_report_renders_overlap_section(self):
        report = perf_report.build_report(_with_process(OVERLAP_TRACE),
                                          "", top=5)
        assert "-- async I/O overlap (hidden under train) --" in report
        assert "save: 4.000 s across 1 span(s), 50.0% hidden" in report
        assert "read: 4.000 s across 1 span(s), 50.0% hidden" in report

    def test_no_io_spans_no_section(self):
        assert perf_report.io_overlap(
            _with_process([s for s in TRACE_FIXTURE
                           if s["span_id"] is not None])) is None
        # the golden above already proves the section is absent there


def _summary(metrics, error=None):
    doc = {"metric": "suite_summary", "value": 1.0, "unit": "x",
           "vs_baseline": 1.0, "n_metrics": len(metrics),
           "metrics": {k: {"value": v, "unit": "x"}
                       for k, v in metrics.items()}}
    if error is not None:
        doc["error"] = error
    return doc


class TestBenchGate:
    def _write(self, tmp_path, name, doc):
        p = tmp_path / name
        p.write_text(json.dumps(doc))
        return str(p)

    def test_ok_within_noise(self, tmp_path):
        cur = self._write(tmp_path, "c.json",
                          _summary({"a": 80.0, "b": 52.0}))
        base = self._write(tmp_path, "b.json",
                           _summary({"a": 100.0, "b": 50.0}))
        v = bench_gate.gate(bench_gate.load_artifact(cur),
                            bench_gate.load_artifact(base), threshold=0.3)
        assert v["verdict"] == "ok" and v["compared"] == 2

    def test_regression_below_threshold(self, tmp_path):
        cur = self._write(tmp_path, "c.json", _summary({"a": 60.0}))
        base = self._write(tmp_path, "b.json", _summary({"a": 100.0}))
        v = bench_gate.gate(bench_gate.load_artifact(cur),
                            bench_gate.load_artifact(base), threshold=0.3)
        assert v["verdict"] == "regression"
        assert v["regressions"][0]["metric"] == "a"
        assert v["regressions"][0]["ratio"] == pytest.approx(0.6)

    def test_metric_vanishing_is_a_regression(self, tmp_path):
        cur = self._write(tmp_path, "c.json", _summary({"a": 100.0}))
        base = self._write(tmp_path, "b.json",
                           _summary({"a": 100.0, "gone": 10.0}))
        v = bench_gate.gate(bench_gate.load_artifact(cur),
                            bench_gate.load_artifact(base))
        assert v["verdict"] == "regression"
        assert v["regressions"][0]["metric"] == "gone"

    def test_saturation_families_absent_from_baseline_never_gate(
            self, tmp_path):
        """Old baselines predate the capacity plane: duty_cycle /
        conn_peak readings in the current run must be surfaced as
        ``new_nongating``, not compared (bench_gate module docstring)."""
        cur = self._write(tmp_path, "c.json",
                          _summary({"serving_slo_qps": 95.0,
                                    "duty_cycle": 0.82,
                                    "conn_peak": 4.0}))
        base = self._write(tmp_path, "b.json",
                           _summary({"serving_slo_qps": 100.0}))
        v = bench_gate.gate(bench_gate.load_artifact(cur),
                            bench_gate.load_artifact(base), threshold=0.3)
        assert v["verdict"] == "ok"
        assert v["compared"] == 1
        assert v["new_nongating"] == ["conn_peak", "duty_cycle"]

    def test_capacity_extras_inside_metric_payloads_are_invisible(
            self, tmp_path):
        """bench.py attaches duty_cycle/conn_peak as per-line extras
        inside the metric payload; the gate reads only ``value``, so an
        old baseline without them compares clean."""
        doc = _summary({"serving_slo_qps": 95.0})
        doc["metrics"]["serving_slo_qps"].update(
            {"duty_cycle": 0.82, "conn_peak": 4})
        cur = self._write(tmp_path, "c.json", doc)
        base = self._write(tmp_path, "b.json",
                           _summary({"serving_slo_qps": 100.0}))
        v = bench_gate.gate(bench_gate.load_artifact(cur),
                            bench_gate.load_artifact(base), threshold=0.3)
        assert v["verdict"] == "ok" and v["compared"] == 1
        assert "new_nongating" not in v

    def test_infra_failure_on_error_key_and_rc(self, tmp_path):
        cur = self._write(tmp_path, "c.json",
                          _summary({}, error="device unreachable"))
        v = bench_gate.gate(bench_gate.load_artifact(cur), None)
        assert v["verdict"] == "infra-failure"
        wrapped = self._write(tmp_path, "w.json",
                              {"rc": 124, "parsed": _summary({"a": 1.0})})
        v = bench_gate.gate(bench_gate.load_artifact(wrapped), None)
        assert v["verdict"] == "infra-failure"

    def test_device_unreachable_artifact_is_infra_failure(self, tmp_path):
        """The harness wrapper of a run whose probe exited 3 with an error
        summary and nothing measured: the shape the gate was built to
        classify, against a real sound baseline."""
        parsed = _summary({}, error="device unreachable: a trivial device "
                                    "round-trip did not complete within 90s; "
                                    "nothing was measured")
        art = bench_gate.load_artifact(self._write(
            tmp_path, "unreachable.json",
            {"n": 5, "cmd": "python bench.py", "rc": 3,
             "tail": json.dumps(parsed) + "\n", "parsed": parsed}))
        v = bench_gate.gate(art, bench_gate.load_artifact(
            os.path.join(REPO, "BENCH_r04.json")))
        assert v["verdict"] == "infra-failure"
        assert "rc=3" in v["error"]

    def test_missing_and_infra_baseline(self, tmp_path):
        cur = bench_gate.load_artifact(self._write(
            tmp_path, "c.json", _summary({"a": 1.0})))
        assert bench_gate.gate(cur, None)["verdict"] == "missing-baseline"
        bad = bench_gate.load_artifact(self._write(
            tmp_path, "bad.json", _summary({}, error="stalled")))
        assert bench_gate.gate(cur, bad)["verdict"] == "missing-baseline"

    def test_exit_codes(self, tmp_path, capsys):
        cur = self._write(tmp_path, "c.json", _summary({"a": 100.0}))
        base = self._write(tmp_path, "b.json", _summary({"a": 100.0}))
        assert bench_gate.main([cur, base]) == 0
        worse = self._write(tmp_path, "w.json", _summary({"a": 10.0}))
        assert bench_gate.main([worse, base]) == 1
        broken = self._write(tmp_path, "x.json",
                             {"rc": 3, "parsed": _summary({})})
        assert bench_gate.main([broken, base]) == 2
        assert bench_gate.main([cur]) == 0  # missing baseline
        for line in capsys.readouterr().out.strip().splitlines():
            json.loads(line)  # every verdict is one valid JSON line
