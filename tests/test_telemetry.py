"""Telemetry subsystem tests (photon_ml_tpu/telemetry/ + integrations).

The load-bearing contracts:

- **registry correctness under threads**: N threads x M increments lands
  exactly N*M (the whole point of owning locks instead of hoping);
- **histogram semantics**: cumulative bucket counts, sum/count, and
  bucket-interpolated quantiles are exact on known inputs;
- **exposition**: the Prometheus text format is golden-tested and
  round-trips through the in-repo parser;
- **span tracing**: nested spans record correct parentage AND interval
  enclosure in ``trace.jsonl``;
- **bridge**: existing bus events (``serving_request``, ``retry_*``,
  ``stage_finished``, registry lifecycle) translate to metrics without
  call-site changes, idempotently;
- **end-to-end**: a ``train_game --telemetry-dir`` run yields a
  well-formed span tree plus per-coordinate loss/grad-norm metrics for
  every CD iteration, and a live ``serve_game`` server exposes
  ``/metrics`` whose recompile counter stays flat across varying batch
  sizes (the zero-recompile contract, now scrape-visible).
"""

import json
import math
import os
import threading
import urllib.request

import numpy as np
import pytest

from photon_ml_tpu.telemetry import metrics as tmetrics
from photon_ml_tpu.telemetry import prometheus as tprom
from photon_ml_tpu.telemetry.metrics import (
    MetricsRegistry,
    quantile_from_buckets,
)
from photon_ml_tpu.telemetry.tracing import Tracer


class TestRegistry:
    def test_counter_concurrency(self):
        reg = MetricsRegistry()
        child = reg.counter("c_total", "x", labels=("t",)).labels(t="a")
        n_threads, n_incs = 8, 5000

        def work():
            for _ in range(n_incs):
                child.inc()

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert child.value == n_threads * n_incs

    def test_get_or_create_idempotent_and_conflict_loud(self):
        reg = MetricsRegistry()
        a = reg.counter("x_total", "first", labels=("op",))
        b = reg.counter("x_total", "second declaration ignored",
                        labels=("op",))
        assert a is b
        with pytest.raises(ValueError):
            reg.gauge("x_total")  # type conflict
        with pytest.raises(ValueError):
            reg.counter("x_total", labels=("other",))  # label conflict

    def test_label_validation(self):
        reg = MetricsRegistry()
        fam = reg.counter("y_total", labels=("op",))
        with pytest.raises(ValueError):
            fam.labels(wrong="x")
        with pytest.raises(ValueError):
            fam.labels()  # missing label
        fam.labels(op="a").inc()
        assert fam.labels(op="a").value == 1
        assert fam.labels(op="b").value == 0  # distinct series

    def test_counter_rejects_decrease_gauge_allows(self):
        reg = MetricsRegistry()
        with pytest.raises(ValueError):
            reg.counter("c_total").inc(-1)
        g = reg.gauge("g")
        g.set(5)
        g.dec(2)
        assert g.value == 3


class TestHistogram:
    def test_bucket_counts_and_sum(self):
        reg = MetricsRegistry()
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0, 10.0)).labels()
        for v in (0.05, 0.1, 0.5, 5.0, 50.0):
            h.observe(v)
        cum, total, count = h.snapshot()
        # le-semantics: 0.1 falls IN the le=0.1 bucket
        assert cum == [2, 3, 4, 5]
        assert count == 5
        assert total == pytest.approx(55.65)

    def test_quantiles_interpolated(self):
        # 2 obs in (0, 1], 2 obs in (1, 2] -> p50 = 1.0 exactly, p75
        # halfway through the second bucket
        uppers = (1.0, 2.0)
        cum = [2, 4, 4]  # le=1, le=2, +Inf
        assert quantile_from_buckets(uppers, cum, 0.5) == pytest.approx(1.0)
        assert quantile_from_buckets(uppers, cum, 0.75) == pytest.approx(1.5)
        assert quantile_from_buckets(uppers, cum, 1.0) == pytest.approx(2.0)
        assert math.isnan(quantile_from_buckets(uppers, [0, 0, 0], 0.5))

    def test_timer_observes_and_exposes_seconds(self):
        reg = MetricsRegistry()
        h = reg.histogram("t_seconds").labels()
        with h.time() as t:
            pass
        assert t.seconds >= 0
        assert h.count == 1

    def test_timer_observes_on_exception(self):
        reg = MetricsRegistry()
        h = reg.histogram("t_seconds").labels()
        with pytest.raises(RuntimeError):
            with h.time():
                raise RuntimeError("boom")
        assert h.count == 1  # failed requests are latency too


class TestPrometheus:
    def test_golden_exposition(self):
        reg = MetricsRegistry()
        reg.counter("photon_x_total", "things done",
                    labels=("op",)).labels(op="read").inc(3)
        reg.gauge("photon_v", "a version").set(2)
        h = reg.histogram("photon_lat_seconds", "latency",
                          buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        assert tprom.render(reg) == (
            "# HELP photon_x_total things done\n"
            "# TYPE photon_x_total counter\n"
            'photon_x_total{op="read"} 3\n'
            "# HELP photon_v a version\n"
            "# TYPE photon_v gauge\n"
            "photon_v 2\n"
            "# HELP photon_lat_seconds latency\n"
            "# TYPE photon_lat_seconds histogram\n"
            'photon_lat_seconds_bucket{le="0.1"} 1\n'
            'photon_lat_seconds_bucket{le="1"} 2\n'
            'photon_lat_seconds_bucket{le="+Inf"} 3\n'
            "photon_lat_seconds_sum 5.55\n"
            "photon_lat_seconds_count 3\n")

    def test_parse_roundtrip(self):
        reg = MetricsRegistry()
        reg.counter("a_total", labels=("k",)).labels(k="v1").inc(7)
        reg.histogram("b_seconds", buckets=(1.0,)).observe(0.5)
        parsed = tprom.parse_text(tprom.render(reg))
        assert tprom.series_value(parsed, "a_total", {"k": "v1"}) == 7
        assert tprom.series_value(parsed, "b_seconds_bucket",
                                  {"le": "1"}) == 1
        assert tprom.series_value(parsed, "b_seconds_bucket",
                                  {"le": "+Inf"}) == 1
        assert tprom.series_value(parsed, "b_seconds_count") == 1

    def test_label_escaping_roundtrip(self):
        reg = MetricsRegistry()
        nasty = 'a"b\\c\nd'
        reg.counter("e_total", labels=("p",)).labels(p=nasty).inc()
        parsed = tprom.parse_text(tprom.render(reg))
        (labels, value), = parsed["e_total"]
        assert labels["p"] == nasty
        assert value == 1


class TestTracing:
    def test_nested_spans_parent_and_enclosure(self, tmp_path):
        tracer = Tracer()
        path = str(tmp_path / "trace.jsonl")
        tracer.configure(path)
        try:
            with tracer.span("root", run="r1"):
                with tracer.span("child_a") as a:
                    a.set(loss=0.5)
                with tracer.span("child_b"):
                    with tracer.span("grandchild"):
                        pass
        finally:
            tracer.close()
        recs = [json.loads(line) for line in open(path)]
        by_name = {r["name"]: r for r in recs}
        assert by_name["root"]["parent_id"] is None
        assert by_name["child_a"]["parent_id"] == by_name["root"]["span_id"]
        assert by_name["child_b"]["parent_id"] == by_name["root"]["span_id"]
        assert (by_name["grandchild"]["parent_id"]
                == by_name["child_b"]["span_id"])
        assert by_name["child_a"]["loss"] == 0.5
        assert by_name["root"]["run"] == "r1"
        by_id = {r["span_id"]: r for r in recs}
        for r in recs:
            if r["parent_id"] is not None:
                parent = by_id[r["parent_id"]]
                assert parent["t0"] <= r["t0"] and r["t1"] <= parent["t1"]

    def test_unconfigured_spans_are_cheap_noops(self, tmp_path):
        tracer = Tracer()
        assert not tracer.enabled
        with tracer.span("a") as sp:
            assert sp.parent_id is None
            with tracer.span("b") as child:
                assert child.parent_id == sp.span_id  # parentage still live
        tracer.annotate("note", k=1)  # no sink -> silently dropped

    def test_span_finished_bridged_onto_bus(self, tmp_path):
        from photon_ml_tpu.events import EventBus

        bus = EventBus()
        seen = []
        bus.subscribe(seen.append)
        tracer = Tracer()
        tracer.configure(str(tmp_path / "t.jsonl"), bus=bus)
        try:
            with tracer.span("outer"):
                with tracer.span("inner"):
                    pass
        finally:
            tracer.close()
        assert [e.name for e in seen] == ["span_finished"] * 2
        assert seen[0].payload["span"] == "inner"  # completion order
        assert seen[1].payload["span"] == "outer"
        assert seen[0].payload["parent_id"] == seen[1].payload["span_id"]

    def test_annotate_records_current_parent(self, tmp_path):
        tracer = Tracer()
        path = str(tmp_path / "t.jsonl")
        tracer.configure(path)
        try:
            with tracer.span("work") as sp:
                tracer.annotate("optimizer_trace", values=[1.0, 0.5])
        finally:
            tracer.close()
        recs = [json.loads(line) for line in open(path)]
        note = next(r for r in recs if r["span_id"] is None)
        assert note["parent_id"] == sp.span_id
        assert note["values"] == [1.0, 0.5]


class TestBridge:
    def _fresh(self):
        from photon_ml_tpu.events import EventBus
        from photon_ml_tpu.telemetry import bridge

        bus = EventBus()
        reg = MetricsRegistry()
        unbind = bridge.bind(bus=bus, registry=reg)
        return bus, reg, unbind

    def test_serving_request_translation(self):
        bus, reg, _ = self._fresh()
        bus.post("serving_request", batch=4, latency_ms=1.2, version=1)
        bus.post("serving_request", batch=1, latency_ms=0.4, version=1)
        assert reg.get("photon_serving_requests_total").value == 2
        assert reg.get("photon_serving_scored_rows_total").value == 5

    def test_retry_translation_bounds_op_cardinality(self):
        bus, reg, _ = self._fresh()
        bus.post("retry_attempt", op="avro.read:part-00001.avro",
                 attempt=1, delay_s=0.1, elapsed_s=0.0, error="E")
        bus.post("retry_attempt", op="avro.read:part-00099.avro",
                 attempt=1, delay_s=0.1, elapsed_s=0.0, error="E")
        bus.post("retry_succeeded", op="avro.read:part-00099.avro",
                 attempt=2, elapsed_s=0.2)
        bus.post("retry_exhausted", op="ckpt.save:step-3", attempts=3,
                 elapsed_s=1.0, deadline_hit=False, error="E")
        fam = reg.get("photon_retry_attempts_total")
        assert fam.labels(op="avro.read").value == 2  # one bounded series
        assert reg.get("photon_retry_recoveries_total").labels(
            op="avro.read").value == 1
        assert reg.get("photon_retry_exhausted_total").labels(
            op="ckpt.save").value == 1

    def test_stage_and_lifecycle_translation(self):
        bus, reg, _ = self._fresh()
        bus.post("stage_finished", stage="Train", seconds=2.0)
        bus.post("model_loaded", version=1, path="/x", n_entities={})
        bus.post("model_activated", version=3, previous=1)
        bus.post("model_reload_rejected", path="/bad", error="boom")
        bus.post("divergence_detected", coordinate="global", sweep=0,
                 failures=1)
        bus.post("coordinate_rollback", coordinate="global", sweep=0,
                 attempt=1, reg_backoff=10.0)
        bus.post("coordinate_frozen", coordinate="global", sweep=0,
                 failures=3)
        assert reg.get("photon_stage_seconds").labels(
            stage="Train").count == 1
        assert reg.get("photon_model_reloads_total").value == 1
        assert reg.get("photon_model_active_version").value == 3
        assert reg.get("photon_model_reload_rejects_total").value == 1
        assert reg.get("photon_divergence_detected_total").labels(
            coordinate="global").value == 1
        assert reg.get("photon_coordinate_rollbacks_total").labels(
            coordinate="global").value == 1
        assert reg.get("photon_coordinate_freezes_total").labels(
            coordinate="global").value == 1

    def test_bind_idempotent_and_unbind(self):
        from photon_ml_tpu.telemetry import bridge

        bus, reg, unbind = self._fresh()
        again = bridge.bind(bus=bus, registry=reg)  # no-op second bind
        bus.post("serving_request", batch=1, latency_ms=0.1, version=1)
        assert reg.get("photon_serving_requests_total").value == 1
        again()
        unbind()
        bus.post("serving_request", batch=1, latency_ms=0.1, version=1)
        assert reg.get("photon_serving_requests_total").value == 1
        # a REAL re-bind after unbind translates again
        bridge.bind(bus=bus, registry=reg)
        bus.post("serving_request", batch=1, latency_ms=0.1, version=1)
        assert reg.get("photon_serving_requests_total").value == 2


class TestEventBusThreadSafety:
    def test_concurrent_post_and_subscribe_churn(self):
        from photon_ml_tpu.events import EventBus

        bus = EventBus()
        seen = []
        bus.subscribe(seen.append)  # stable listener sees every post
        n_threads, n_posts = 6, 400
        failures = []

        def poster(k):
            try:
                for i in range(n_posts):
                    # churn the listener list mid-post from many threads:
                    # the pre-fix bus raced list mutation against iteration
                    unsub = bus.subscribe(lambda e: None)
                    bus.post("tick", thread=k, i=i)
                    unsub()
            except Exception as e:  # pragma: no cover - failure path
                failures.append(e)

        threads = [threading.Thread(target=poster, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not failures
        assert len(seen) == n_threads * n_posts
        assert len(bus) == 1  # every churn listener unsubscribed


class TestRunLoggerMetricsFile:
    def test_single_handle_flush_and_close(self, tmp_path):
        from photon_ml_tpu.logging_util import RunLogger

        rl = RunLogger(str(tmp_path))
        try:
            rl.metric(stage="a", v=1)
            # the handle flushes per line: visible BEFORE close
            with open(tmp_path / "metrics.jsonl") as f:
                assert len(f.readlines()) == 1
            fh = rl._metrics_fh
            rl.metric(stage="b", v=2)
            assert rl._metrics_fh is fh  # no reopen per call
        finally:
            rl.close()
        assert rl._metrics_fh is None
        lines = [json.loads(line)
                 for line in open(tmp_path / "metrics.jsonl")]
        assert [ln["stage"] for ln in lines] == ["a", "b"]

    def test_concurrent_metric_writes_do_not_shear(self, tmp_path):
        from photon_ml_tpu.logging_util import RunLogger

        rl = RunLogger(str(tmp_path))
        n_threads, n_lines = 8, 200
        try:
            threads = [
                threading.Thread(
                    target=lambda k=k: [rl.metric(t=k, i=i)
                                        for i in range(n_lines)])
                for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            rl.close()
        lines = open(tmp_path / "metrics.jsonl").readlines()
        assert len(lines) == n_threads * n_lines
        for line in lines:  # every line is intact JSON — no interleaving
            json.loads(line)

    def test_metric_after_close_is_log_only(self, tmp_path):
        from photon_ml_tpu.logging_util import RunLogger

        rl = RunLogger(str(tmp_path))
        rl.metric(v=1)
        rl.close()
        rl.metric(v=2)  # must not raise, must not write
        assert len(open(tmp_path / "metrics.jsonl").readlines()) == 1


class TestProfiledConfirmation:
    def test_confirmation_survives_body_exception(self, tmp_path, caplog):
        import logging

        from photon_ml_tpu.logging_util import profiled

        out = str(tmp_path / "profile")
        with caplog.at_level(logging.INFO, logger="photon_ml_tpu"):
            with pytest.raises(RuntimeError):
                with profiled(out):
                    raise RuntimeError("mid-stage failure")
        assert any("profiler trace written to" in r.message
                   for r in caplog.records)
        assert os.path.isdir(out)  # the trace the message points at


# ---------------------------------------------------------------------------
# End-to-end: train_game --telemetry-dir and a live serve_game /metrics
# ---------------------------------------------------------------------------

SHARDS = "global=fixed|intercept,user=user|noIntercept"
COORDS = [
    "global=fixed,shard=global,reg=L2",
    "perUser=random,entity=userId,shard=user,reg=L2",
]
N_SWEEPS = 2
UPDATE_SEQUENCE = ["global", "perUser"]


def _records(n, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        feats = [{"name": f"fixed.x{j}", "term": "",
                  "value": float(rng.normal())} for j in range(4)]
        feats += [{"name": f"user.z{j}", "term": "",
                   "value": float(rng.normal())} for j in range(2)]
        out.append({
            "uid": str(i),
            "response": float(rng.integers(0, 2)),
            "offset": None, "weight": None, "features": feats,
            "metadataMap": {"userId": f"u{rng.integers(0, 6)}"},
        })
    return out


@pytest.fixture(scope="module")
def telemetry_run(tmp_path_factory):
    """One tiny train_game run WITH --telemetry-dir; the output model also
    backs the serving /metrics test."""
    from photon_ml_tpu.cli import train_game as train_game_cli
    from photon_ml_tpu.io.data_reader import write_training_examples

    tmp = str(tmp_path_factory.mktemp("telemetry"))
    train_path = os.path.join(tmp, "train.avro")
    write_training_examples(train_path, _records(150))
    out = os.path.join(tmp, "run")
    tdir = os.path.join(tmp, "telemetry")
    train_game_cli.run([
        "--training-data", train_path,
        "--output-dir", out,
        "--feature-shards", SHARDS,
        "--coordinates", *COORDS,
        "--update-sequence", ",".join(UPDATE_SEQUENCE),
        "--cd-iterations", str(N_SWEEPS),
        "--grid", "global=0.1", "perUser=1",
        "--evaluators", "",
        "--telemetry-dir", tdir,
    ])
    spans, notes = [], []
    for line in open(os.path.join(tdir, "trace.jsonl")):
        rec = json.loads(line)
        (spans if rec.get("span_id") is not None else notes).append(rec)
    return {"tmp": tmp, "model_dir": out, "telemetry_dir": tdir,
            "spans": spans, "notes": notes}


class TestTrainGameTelemetry:
    def test_spans_nest_correctly(self, telemetry_run):
        """Every non-root span's parent exists and encloses it — the
        acceptance contract for trace.jsonl."""
        spans = telemetry_run["spans"]
        assert spans, "trace.jsonl holds no spans"
        by_id = {s["span_id"]: s for s in spans}
        roots = [s for s in spans if s["parent_id"] is None]
        assert [r["name"] for r in roots] == ["train_game"]
        for s in spans:
            if s["parent_id"] is None:
                continue
            assert s["parent_id"] in by_id, \
                f"span {s['name']} orphaned (parent {s['parent_id']})"
            parent = by_id[s["parent_id"]]
            assert parent["t0"] <= s["t0"] and s["t1"] <= parent["t1"], \
                f"span {s['name']} leaks outside parent {parent['name']}"

    def test_stages_and_sweeps_in_tree(self, telemetry_run):
        names = [s["name"] for s in telemetry_run["spans"]]
        assert "Read training data" in names  # timed() rides spans now
        assert sum(1 for s in telemetry_run["spans"]
                   if s["name"] == "cd.sweep") == N_SWEEPS

    def test_per_coordinate_loss_and_grad_every_iteration(
            self, telemetry_run):
        steps = [s for s in telemetry_run["spans"] if s["name"] == "cd.step"]
        got = {(s["sweep"], s["coordinate"]) for s in steps}
        want = {(sw, cid) for sw in range(N_SWEEPS)
                for cid in UPDATE_SEQUENCE}
        assert got == want
        for s in steps:
            assert math.isfinite(s["loss"]), s
            assert math.isfinite(s["grad_norm"]), s
        # the objective CD minimizes must not increase along the walk
        ordered = sorted(steps, key=lambda s: s["span_id"])
        losses = [s["loss"] for s in ordered]
        assert losses[-1] <= losses[0] + 1e-6

    def test_optimizer_trace_annotations(self, telemetry_run):
        notes = [n for n in telemetry_run["notes"]
                 if n["name"] == "optimizer_trace"]
        # the fixed effect records its per-iteration table every sweep
        assert {n["sweep"] for n in notes
                if n["coordinate"] == "global"} == set(range(N_SWEEPS))
        for n in notes:
            assert len(n["values"]) == len(n["grad_norms"]) >= 1
            assert all(math.isfinite(v) for v in n["values"])

    def test_metrics_prom_snapshot(self, telemetry_run):
        path = os.path.join(telemetry_run["telemetry_dir"], "metrics.prom")
        parsed = tprom.parse_text(open(path).read())
        for cid in UPDATE_SEQUENCE:
            assert math.isfinite(tprom.series_value(
                parsed, "photon_game_coordinate_loss",
                {"coordinate": cid}, default=math.nan))
            assert tprom.series_value(
                parsed, "photon_game_coordinate_steps_total",
                {"coordinate": cid}) >= N_SWEEPS
        assert tprom.series_value(
            parsed, "photon_optimizer_iterations_total",
            {"coordinate": "global"}) >= 1
        # stage timings arrived through the bridge
        assert tprom.series_value(
            parsed, "photon_stage_seconds_count",
            {"stage": "Read training data"}) >= 1

    def test_tracer_released_after_run(self, telemetry_run):
        from photon_ml_tpu.telemetry import tracing

        assert not tracing.enabled()  # session closed its sink


class TestTrainGameProfiling:
    """The PR-5 acceptance contract: a --telemetry-dir train_game run
    exposes the compile accounting, the compile counter goes flat
    after sweep 1, and perf_report renders the run's artifacts."""

    def _parsed(self, telemetry_run):
        path = os.path.join(telemetry_run["telemetry_dir"], "metrics.prom")
        return tprom.parse_text(open(path).read())

    def test_compile_families_exposed(self, telemetry_run):
        parsed = self._parsed(telemetry_run)
        for fn in ("game.fixed_effect", "game.re.sweep_fused"):
            assert tprom.series_value(
                parsed, "photon_compiles_total", {"fn": fn}) >= 1, fn
            assert tprom.series_value(
                parsed, "photon_compile_seconds_total", {"fn": fn}) > 0, fn
        # the process-wide XLA pipeline listener saw the backend compiles
        assert tprom.series_value(
            parsed, "photon_xla_compile_seconds_total",
            {"phase": "backend"}) > 0
        # dispatch timing flows through the registry histogram (rule 5)
        assert tprom.series_value(
            parsed, "photon_game_step_dispatch_seconds_count",
            {"coordinate": "global"}) >= N_SWEEPS

    def test_compile_counter_flat_after_first_sweep(self, telemetry_run):
        """The training flat-recompile contract, trace-visible: every
        cd.sweep span past the first carries compiles == 0."""
        sweeps = sorted((s for s in telemetry_run["spans"]
                         if s["name"] == "cd.sweep"),
                        key=lambda s: s["sweep"])
        assert len(sweeps) == N_SWEEPS
        assert all("compiles" in s for s in sweeps)
        assert sweeps[0]["compiles"] >= 1  # the cold sweep pays them all
        for s in sweeps[1:]:
            assert s["compiles"] == 0, \
                f"sweep {s['sweep']} recompiled {s['compiles']} programs"

    def test_perf_report_renders_run_artifacts(self, telemetry_run):
        import sys as _sys

        _sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import perf_report

        trace_path, prom_path = perf_report.resolve_inputs(
            telemetry_run["telemetry_dir"])
        spans = perf_report.load_spans(trace_path)
        report = perf_report.build_report(spans, open(prom_path).read())
        assert "critical path" in report
        assert "cd.step{coordinate=global}" in report
        assert "game.fixed_effect" in report
        assert "per-coordinate" in report
        # the report is a pure function of the artifacts
        assert report == perf_report.build_report(
            spans, open(prom_path).read())


class TestServeGameMetricsEndpoint:
    def _get(self, url):
        with urllib.request.urlopen(url, timeout=60) as resp:
            return resp.read().decode()

    def _post(self, url, payload):
        req = urllib.request.Request(
            url, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as resp:
            return json.loads(resp.read())

    def test_metrics_endpoint_live_server(self, telemetry_run):
        """curl /metrics on a running serve_game: valid Prometheus text
        with the acceptance families, and a recompile counter that stays
        flat across varying batch sizes."""
        from photon_ml_tpu.cli import serve_game as serve_game_cli

        server = serve_game_cli.build_server([
            "--model-dir", telemetry_run["model_dir"],
            "--feature-shards", SHARDS,
            "--port", "0", "--max-batch", "8", "--max-wait-ms", "1",
        ]).start()
        try:
            base = server.url
            m0 = tprom.parse_text(self._get(base + "/metrics"))
            assert tprom.series_value(
                m0, "photon_model_active_version") >= 1
            # serving traces count under the system-wide compile family
            assert tprom.series_value(
                m0, "photon_compiles_total", {"fn": "serving.score"}) >= 1
            assert "photon_serving_request_latency_seconds_bucket" in m0

            recs = _records(8, seed=11)
            for size in (1, 2, 3, 5, 8):
                out = self._post(base + "/score", {"records": recs[:size]})
                assert len(out["scores"]) == size
            m1 = tprom.parse_text(self._get(base + "/metrics"))

            def delta(name, labels=None):
                return (tprom.series_value(m1, name, labels)
                        - tprom.series_value(m0, name, labels))

            # zero-recompile contract, scrape-visible: warmup pre-traced
            # every bucket, so varied request sizes move nothing
            assert delta("photon_compiles_total",
                         {"fn": "serving.score"}) == 0
            assert delta("photon_serving_requests_total") == 5
            assert delta("photon_serving_scored_rows_total") == 1 + 2 + 3 + 5 + 8
            assert delta(
                "photon_serving_request_latency_seconds_count") == 5
            # per-bucket engine histogram populated for the padded shapes
            assert delta("photon_serving_score_latency_seconds_count",
                         {"bucket": "8"}) >= 2  # sizes 5 and 8 pad to 8
            # microbatcher gauges/histograms registered and sane
            assert tprom.series_value(
                m1, "photon_serving_batch_size_count") >= 1
        finally:
            server.stop()
            server.telemetry.close()


class TestServingStageHistograms:
    _get = TestServeGameMetricsEndpoint._get
    _post = TestServeGameMetricsEndpoint._post

    def test_every_stage_lands_and_perf_report_renders_section(
            self, telemetry_run):
        """The request-path critical path: one live request populates all
        five photon_serving_stage_seconds stages (parse and respond from
        the HTTP layer, queue_wait from the microbatcher, batch_assemble
        and execute from the engine), and perf_report renders the serving
        section from the scrape alone."""
        from photon_ml_tpu.cli import serve_game as serve_game_cli

        server = serve_game_cli.build_server([
            "--model-dir", telemetry_run["model_dir"],
            "--feature-shards", SHARDS,
            "--port", "0", "--max-batch", "8", "--max-wait-ms", "1",
        ]).start()
        try:
            base = server.url
            recs = _records(4, seed=31)
            # a single record rides the microbatcher (queue_wait); the
            # batch goes straight to the engine (batch_assemble/execute)
            self._post(base + "/score", {"record": recs[0]})
            self._post(base + "/score", {"records": recs})
            text = self._get(base + "/metrics")
        finally:
            server.stop()
            server.telemetry.close()
        parsed = tprom.parse_text(text)
        for stage in ("parse", "queue_wait", "batch_assemble", "execute",
                      "respond"):
            assert tprom.series_value(
                parsed, "photon_serving_stage_seconds_count",
                {"stage": stage}) >= 1, stage
        import sys as _sys

        _sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        import perf_report

        report = perf_report.build_report([], text)
        assert "serving request path" in report
        for stage in ("parse", "queue_wait", "batch_assemble", "execute",
                      "respond"):
            assert stage in report
        assert "requests " in report  # the end-to-end histogram line


class TestTelemetryOverheadGuard:
    def test_scores_bit_identical_and_zero_recompiles_with_tracing(
            self, telemetry_run, tmp_path):
        """The overhead guard: turning the tracer ON changes nothing the
        engine computes — scores stay bit-identical and warmup's
        executables still cover every request size."""
        from photon_ml_tpu.cli.config import parse_feature_shard_config
        from photon_ml_tpu.serving import ModelRegistry
        from photon_ml_tpu.telemetry import tracing

        shard_configs = tuple(parse_feature_shard_config(s)
                              for s in SHARDS.split(","))
        registry = ModelRegistry(shard_configs, max_batch=8)
        sm = registry.load(telemetry_run["model_dir"])
        sm.engine.warmup()
        recs = _records(8, seed=23)
        baseline = sm.score(recs)
        frozen = sm.engine.compile_count
        tracing.configure(str(tmp_path / "trace.jsonl"))
        try:
            for size in (1, 3, 5, 8):
                got = sm.score(recs[:size])
                assert np.array_equal(got, baseline[:size])
        finally:
            tracing.close()
        assert sm.engine.compile_count == frozen


class TestDeviceSampler:
    def test_sample_once_populates_gauges(self):
        from photon_ml_tpu.telemetry.device import DeviceStatsSampler

        reg = MetricsRegistry()
        sampler = DeviceStatsSampler(60.0, registry=reg)
        sampler.sample_once()
        assert reg.get("photon_host_rss_bytes").value > 0
        assert reg.get("photon_device_samples_total").value == 1

    def test_start_close_lifecycle(self):
        from photon_ml_tpu.telemetry.device import DeviceStatsSampler

        reg = MetricsRegistry()
        sampler = DeviceStatsSampler(30.0, registry=reg).start()
        sampler.close()  # immediate: the wait is an Event, not a sleep
        assert reg.get("photon_device_samples_total").value >= 1

    def test_rejects_nonpositive_interval(self):
        from photon_ml_tpu.telemetry.device import DeviceStatsSampler

        with pytest.raises(ValueError):
            DeviceStatsSampler(0.0)
