"""Open-loop load-generator tests (tools/bench_serving.py --mode open).

The acceptance contract: against a server with a deliberate stall, the
schedule-corrected (HdrHistogram-style) p99 must come out FAR above the
uncorrected send→response p99 — the coordinated omission a closed-loop
client hides. Plus: the p99 SLO gate renders ok/regression verdicts
through tools/bench_gate.py, and the closed-loop output now labels its
percentiles ``closed_loop_*`` (old keys kept as bench_gate aliases).

All tests run against a stub single-threaded HTTP server — no model, no
jax — so they are fast and the stall is exactly where we put it.
"""

import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

import bench_serving  # noqa: E402


class _StubHandler(BaseHTTPRequestHandler):
    """Fast /score responder with a per-request stall schedule
    (``server.stall_at[request_index] = seconds``)."""

    def log_message(self, fmt, *args):
        pass

    def do_GET(self):  # /healthz for the bench preamble; no /metrics
        if self.path == "/healthz":
            body = json.dumps({"status": "ok", "version": 1,
                               "compiles": 0}).encode()
            self.send_response(200)
        else:
            body = b"{}"
            self.send_response(404)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_POST(self):
        length = int(self.headers.get("Content-Length") or 0)
        payload = json.loads(self.rfile.read(length))
        n = len(payload["records"])
        with self.server.lock:
            i = self.server.request_index
            self.server.request_index += 1
        if i in self.server.shed_at:
            # an admission-control refusal, as serve_game sheds it
            body = json.dumps({"error": "request shed (queue_full)",
                               "reason": "queue_full"}).encode()
            self.send_response(429)
            self.send_header("Retry-After", "1")
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        stall = self.server.stall_at.get(i, 0.0)
        if stall:
            time.sleep(stall)
        body = json.dumps({"scores": [0.0] * n, "version": 1,
                           "latency_ms": 0.1}).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)


@pytest.fixture
def stub_server():
    httpd = HTTPServer(("127.0.0.1", 0), _StubHandler)
    httpd.lock = threading.Lock()
    httpd.request_index = 0
    httpd.stall_at = {}
    httpd.shed_at = set()
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    try:
        yield httpd
    finally:
        httpd.shutdown()
        httpd.server_close()
        thread.join()


def _base(httpd):
    host, port = httpd.server_address[:2]
    return f"http://{host}:{port}"


POOL = [{"features": [], "metadataMap": {}, "offset": None}]


class TestCoordinatedOmission:
    def test_corrected_p99_exposes_server_stall(self, stub_server):
        """The acceptance gate: one worker, one 1 s server stall early in
        a 200-QPS schedule. The uncorrected numbers see ONE slow request;
        the corrected numbers see every request whose schedule slot the
        stall consumed — corrected p99 ≫ uncorrected p99."""
        stub_server.stall_at[3] = 1.0
        run = bench_serving.open_loop_run(
            _base(stub_server), POOL, [1],
            target_qps=200.0, requests=100, concurrency=1)
        assert not run["errors"]
        assert len(run["corrected_ms"]) == 100
        corrected_p99 = bench_serving._percentile(run["corrected_ms"], 99)
        uncorrected_p99 = bench_serving._percentile(
            run["uncorrected_ms"], 99)
        # most requests were delayed by most of the stall
        assert corrected_p99 > 300.0, corrected_p99
        assert corrected_p99 > 5 * uncorrected_p99, (
            corrected_p99, uncorrected_p99)
        # the stall hit exactly one uncorrected sample: the p50s agree
        # that individual requests were fast
        assert bench_serving._percentile(run["uncorrected_ms"], 50) < 100.0

    def test_unstalled_schedule_keeps_pace(self, stub_server):
        """A healthy server builds no backlog: every request leaves when
        its slot comes, so its corrected latency (from the slot) and its
        uncorrected one (from the send) agree to within a slot. No absolute
        time: how long an answer takes is the machine's business (the
        suite runs under six workers), and at 10 requests/s eight senders
        ride out a stall of most of a second before one is late for that."""
        qps, requests = 10.0, 20
        run = bench_serving.open_loop_run(
            _base(stub_server), POOL, [1],
            target_qps=qps, requests=requests, concurrency=8)
        assert not run["errors"]
        assert len(run["corrected_ms"]) == requests
        late_ms = [c - u for c, u in zip(run["corrected_ms"],
                                         run["uncorrected_ms"])]
        assert min(late_ms) >= 0.0  # nothing leaves before its slot
        assert max(late_ms) < 1e3 / qps, sorted(late_ms)[-3:]
        # and the schedule was kept: the run took its slots, not twice them
        assert run["achieved_qps"] > qps / 2


class TestShedClassification:
    def test_429s_counted_as_shed_not_errors_and_excluded(self,
                                                          stub_server):
        """Satellite: shed (429) responses are a separate population —
        counted in ``shed``, excluded from both latency lists, never in
        ``errors`` — and the accounting identity served + shed + errored
        == offered holds."""
        stub_server.shed_at = {2, 5, 9}
        run = bench_serving.open_loop_run(
            _base(stub_server), POOL, [1],
            target_qps=400.0, requests=40, concurrency=4)
        assert run["shed"] == 3
        assert not run["errors"]
        assert len(run["corrected_ms"]) == 37
        assert len(run["uncorrected_ms"]) == 37
        assert (len(run["corrected_ms"]) + run["shed"]
                + len(run["errors"]) == run["offered"] == 40)


class TestSloGate:
    def test_ok_and_regression_verdicts_via_bench_gate(self):
        ok = bench_serving.slo_gate_verdict(
            corrected_p99_ms=50.0, slo_p99_ms=100.0)
        assert ok["verdict"] == "ok"
        assert ok["headroom"] == 2.0
        bad = bench_serving.slo_gate_verdict(
            corrected_p99_ms=400.0, slo_p99_ms=100.0)
        assert bad["verdict"] == "regression"
        assert bad["headroom"] == 0.25
        assert bad["regressions"][0]["metric"] == "serving_p99_slo_headroom"

    def test_open_mode_main_emits_gate_line(self, stub_server, tmp_path,
                                            capsys):
        data = self._data_file(tmp_path)
        bench_serving.main([
            "--url", _base(stub_server), "--data", data,
            "--mode", "open", "--target-qps", "300",
            "--requests", "30", "--slo-p99-ms", "5000"])
        lines = [json.loads(line) for line in
                 capsys.readouterr().out.strip().splitlines()]
        by_metric = {ln["metric"]: ln for ln in lines}
        open_line = by_metric["serving_open_loop_latency_ms"]
        assert {"corrected_p50_ms", "corrected_p99_ms",
                "uncorrected_p99_ms", "target_qps",
                "achieved_qps"} <= open_line.keys()
        assert by_metric["serving_slo_gate"]["verdict"] == "ok"
        assert by_metric["suite_summary"]["slo_verdict"] == "ok"

    def test_open_mode_main_fails_on_slo_regression(self, stub_server,
                                                    tmp_path, capsys):
        stub_server.stall_at[2] = 0.6
        data = self._data_file(tmp_path)
        with pytest.raises(SystemExit, match="SLO"):
            bench_serving.main([
                "--url", _base(stub_server), "--data", data,
                "--mode", "open", "--target-qps", "300",
                "--requests", "30", "--concurrency", "1",
                "--slo-p99-ms", "50"])
        lines = [json.loads(line) for line in
                 capsys.readouterr().out.strip().splitlines()]
        gate = next(ln for ln in lines
                    if ln["metric"] == "serving_slo_gate")
        assert gate["verdict"] == "regression"

    def _data_file(self, tmp_path) -> str:
        from photon_ml_tpu.io.data_reader import write_training_examples

        path = str(tmp_path / "records.avro")
        write_training_examples(path, [
            {"uid": "0", "response": 0.0, "offset": None, "weight": None,
             "features": [{"name": "f.x", "term": "", "value": 1.0}],
             "metadataMap": {"userId": "u0"}}])
        return path


class TestClosedLoopLabels:
    def test_closed_loop_percentiles_are_labeled(self, stub_server,
                                                 tmp_path, capsys):
        """Satellite: closed-loop output says what it is —
        ``closed_loop_*`` keys — while the historical ``value``/``p99_ms``
        keys survive as aliases for bench_gate baseline continuity."""
        from photon_ml_tpu.io.data_reader import write_training_examples

        data = str(tmp_path / "records.avro")
        write_training_examples(data, [
            {"uid": "0", "response": 0.0, "offset": None, "weight": None,
             "features": [{"name": "f.x", "term": "", "value": 1.0}],
             "metadataMap": {"userId": "u0"}}])
        bench_serving.main([
            "--url", _base(stub_server), "--data", data,
            "--requests", "24", "--concurrency", "2"])
        lines = [json.loads(line) for line in
                 capsys.readouterr().out.strip().splitlines()]
        head = next(ln for ln in lines
                    if ln["metric"] == "serving_score_latency_ms")
        assert head["closed_loop_p50_ms"] == head["value"]
        assert head["closed_loop_p99_ms"] == head["p99_ms"]
        assert "closed-loop" in head["unit"]
        assert next(ln for ln in lines
                    if ln["metric"] == "suite_summary")