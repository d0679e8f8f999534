"""Fleet observability plane tests (photon_ml_tpu/fleet/observe.py +
router wiring + tools/fleet_report.py).

The contracts locked here:

- **fold**: the router's live N=2×R=2 ``/metrics`` fold is byte-identical
  to ``tools/metrics_fold.py`` over the same dumped host snapshots;
  host-owned gauges disambiguate per (shard, replica); snapshot ORDER
  changes rendering only, never merged content;
- **traces**: one scored request produces ONE request-id-tagged
  ``fleet.request`` tree — fan-out, hedged legs as siblings, and the
  hosts' stage breakdowns (leg-summary header) as ``host.*`` children;
- **SLO burn**: a synthetic latency regression past the objective fires
  an edge-triggered ``slo_burn_alert`` within two ticks and increments
  ``photon_slo_burn_total{window}`` through the telemetry bridge,
  re-arming after recovery;
- **hardening**: hosts failing mid-scrape annotate
  ``photon_fleet_scrape_errors_total`` and the partial fold is served;
  a shard with zero live replicas flips ``/readyz`` to 503
  ``reason=shard_uncovered``;
- **parity**: with the whole plane enabled (tracing + SLO + scrapes),
  fleet f32 scores stay bit-identical to an unsharded host and steady
  state stays at zero recompiles;
- **report**: ``tools/fleet_report.py`` is a deterministic golden.
"""

import json
import os
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from photon_ml_tpu.cli import serve_fleet as serve_fleet_cli
from photon_ml_tpu.cli import serve_game as serve_game_cli
from photon_ml_tpu.cli import train_game as train_game_cli
from photon_ml_tpu.events import EventBus, GLOBAL_BUS
from photon_ml_tpu.fleet.observe import (
    FleetObserver,
    SloBurnTracker,
    fold_fleet_snapshots,
    tag_host_owned,
)
from photon_ml_tpu.io.data_reader import write_training_examples
from photon_ml_tpu.resilience import FaultPlan, injected
from photon_ml_tpu.serving.http import (
    LEG_SUMMARY_STAGES,
    format_leg_summary,
    parse_leg_summary,
)
from photon_ml_tpu.telemetry import bridge, tracing
from photon_ml_tpu.telemetry.prometheus import parse_text, series_value
from photon_ml_tpu.telemetry.saturation import RESOURCES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

SHARDS = "global=fixed|intercept,user=user|noIntercept"
COMMON = [
    "--feature-shards", SHARDS,
    "--coordinates",
    "global=fixed,shard=global,reg=L2,maxIter=20",
    "perUser=random,entity=userId,shard=user,reg=L2,maxIter=20",
    "--update-sequence", "global,perUser",
    "--grid", "global=0.1", "perUser=1",
    "--evaluators", "",
]
D_FIXED, D_USER, N_USERS = 4, 3, 10


def _records(n, seed, *, cold_users=0):
    prng = np.random.default_rng(777)
    w = prng.normal(size=D_FIXED)
    u = 1.5 * prng.normal(size=(N_USERS, D_USER))
    rng = np.random.default_rng(seed)
    xf = rng.normal(size=(n, D_FIXED))
    xu = rng.normal(size=(n, D_USER))
    users = rng.integers(0, N_USERS, size=n)
    margin = xf @ w + np.einsum("nd,nd->n", xu, u[users])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-margin))).astype(float)
    out = []
    for i in range(n):
        feats = [{"name": f"fixed.x{j}", "term": "",
                  "value": float(xf[i, j])} for j in range(D_FIXED)]
        feats += [{"name": f"user.z{j}", "term": "",
                   "value": float(xu[i, j])} for j in range(D_USER)]
        out.append({"uid": str(i), "response": float(y[i]),
                    "offset": None, "weight": None, "features": feats,
                    "metadataMap": {"userId": (
                        f"uCOLD{i}" if i >= n - cold_users
                        else f"u{users[i]}")}})
    return out


def _get(url, timeout=60.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return json.loads(resp.read())


def _get_text(url, timeout=60.0):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def _post(url, payload, timeout=60.0, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


@pytest.fixture(scope="module")
def env(tmp_path_factory):
    """One model served two ways with the WHOLE plane armed on the fleet
    side: an N=2 × R=2 fleet (tiny fixed hedge delay, so every leg
    hedges deterministically and the trace tree shows hedge siblings)
    with an SLO tracker attached, and an unsharded parity reference."""
    tmp = str(tmp_path_factory.mktemp("fleet_obs"))
    d0 = os.path.join(tmp, "d0.avro")
    write_training_examples(d0, _records(300, 0))
    model = os.path.join(tmp, "model")
    train_game_cli.run(["--training-data", d0, "--output-dir", model]
                       + COMMON)
    fleet = serve_fleet_cli.build_fleet(
        ["--model-dir", model, "--feature-shards", SHARDS,
         "--port", "0", "--fleet-shards", "2", "--replicas", "2",
         "--hedge-delay-ms", "0.05", "--no-warmup"])
    # the plane: SLO burn tracking on every routed request (generous
    # objective — the burn tests below drive their own tracker), plus
    # tracing/scrapes armed per-test
    fleet.router.observer.attach_slo(
        SloBurnTracker(GLOBAL_BUS, objective_s=30.0), tick_s=0.0)
    single = serve_game_cli.build_server(
        ["--model-dir", model, "--feature-shards", SHARDS,
         "--port", "0", "--no-warmup"]).start()
    requests = _records(48, 11, cold_users=4)
    # warm pass: the tiny hedge delay drives every replica of every
    # shard, so all four hosts compile the steady-state shapes here
    for _ in range(3):
        _post(fleet.url + "/score", {"records": requests})
        _post(fleet.url + "/score", {"record": requests[0]})
    yield {"tmp": tmp, "model": model, "single": single, "fleet": fleet,
           "requests": requests}
    fleet.stop()
    single.stop()


# ---------------------------------------------------------------------------
# leg-summary header (the cross-host stitching contract)
# ---------------------------------------------------------------------------


class TestLegSummary:
    def test_round_trip(self):
        stages = {"span": 41, "parse": 0.001, "queue_wait": 0.0025,
                  "batch_assemble": 0.002, "execute": 0.01,
                  "respond": 0.0005}
        header = format_leg_summary(stages)
        assert header.startswith("span=41")
        out = parse_leg_summary(header)
        assert out.pop("span") == 41
        assert set(out) <= set(LEG_SUMMARY_STAGES)
        for key, value in out.items():
            assert value == pytest.approx(stages[key], abs=1e-6)

    def test_parser_drops_junk_and_foreign_keys(self):
        # the parser is the cardinality firewall: an upstream must not
        # be able to inject attribute keys or non-numeric values
        hostile = ("span=nope;parse=0.001;userId=u123;evil=1e3;"
                   "execute=abc;;=;queue_wait=0.002")
        out = parse_leg_summary(hostile)
        assert out == {"parse": pytest.approx(0.001),
                       "queue_wait": pytest.approx(0.002)}
        assert parse_leg_summary(None) == {}
        assert parse_leg_summary("") == {}

    def test_format_emits_only_the_closed_vocabulary(self):
        header = format_leg_summary({"parse": 0.1, "userId": 123.0})
        assert "userId" not in header
        assert parse_leg_summary(header) == {"parse": pytest.approx(0.1)}


# ---------------------------------------------------------------------------
# the fold (N=2 x R=2)
# ---------------------------------------------------------------------------


class TestFleetFold:
    def test_live_fold_matches_offline_tool_byte_for_byte(self, env,
                                                          tmp_path):
        import metrics_fold

        router = env["fleet"].router
        snapshots = router.observer.scrape()
        assert len(snapshots) == 4  # N=2 x R=2, all live
        router_text = "# TYPE photon_fleet_hosts gauge\n" \
                      "photon_fleet_hosts 4\n"
        live = fold_fleet_snapshots(router_text, snapshots)
        run_dir = tmp_path / "telemetry"
        (run_dir / "hosts").mkdir(parents=True)
        (run_dir / "metrics.prom").write_text(router_text)
        for s, r, text in snapshots:
            d = run_dir / "hosts" / f"shard-{s}-replica-{r}"
            d.mkdir()
            (d / "metrics.prom").write_text(text)
        folded = metrics_fold.fold_metrics(str(run_dir))
        assert open(folded).read() == live

    def test_gauges_disambiguate_per_replica(self, env):
        # all four hosts share this process's registry, so only the
        # shard/replica tags keep their gauges apart in the fold
        text = env["fleet"].router.metrics_text()
        snap = parse_text(text)
        depth = snap.get("photon_serving_queue_depth", [])
        tags = {(labels.get("shard"), labels.get("replica"))
                for labels, _v in depth}
        assert {("0", "0"), ("0", "1"), ("1", "0"), ("1", "1")} <= tags

    def test_fold_content_is_snapshot_order_independent(self):
        from photon_ml_tpu.telemetry.metrics import mark_host_owned

        mark_host_owned("photon_obs_unit_gauge")
        texts = {}
        for s in range(2):
            for r in range(2):
                texts[(s, r)] = (
                    "# TYPE photon_obs_unit_total counter\n"
                    f"photon_obs_unit_total {10 * s + r + 1}\n"
                    "# TYPE photon_obs_unit_gauge gauge\n"
                    f"photon_obs_unit_gauge {float(100 * s + r)}\n")
        router_text = ("# TYPE photon_obs_unit_total counter\n"
                       "photon_obs_unit_total 1\n")
        major = [(s, r, texts[(s, r)])
                 for s in range(2) for r in range(2)]
        shuffled = [major[2], major[0], major[3], major[1]]
        folded_a = parse_text(fold_fleet_snapshots(router_text, major))
        folded_b = parse_text(fold_fleet_snapshots(router_text, shuffled))
        # counters sum identically; every (shard, replica) keeps its own
        # gauge value under its tag, whatever order the scrapes landed
        assert series_value(folded_a, "photon_obs_unit_total") == 1 + 1 \
            + 2 + 11 + 12
        for snap in (folded_a, folded_b):
            got = {(labels["shard"], labels["replica"]): v
                   for labels, v in snap["photon_obs_unit_gauge"]}
            assert got == {("0", "0"): 0.0, ("0", "1"): 1.0,
                           ("1", "0"): 100.0, ("1", "1"): 101.0}
        assert {k: sorted((sorted(ls.items()), v) for ls, v in series)
                for k, series in folded_a.items()} \
            == {k: sorted((sorted(ls.items()), v) for ls, v in series)
                for k, series in folded_b.items()}

    def test_tag_host_owned_leaves_counters_alone(self):
        from photon_ml_tpu.telemetry.metrics import mark_host_owned

        mark_host_owned("photon_obs_unit_gauge")
        text = ("# TYPE photon_obs_unit_total counter\n"
                "photon_obs_unit_total 3\n"
                "# TYPE photon_obs_unit_gauge gauge\n"
                "photon_obs_unit_gauge 7.0\n")
        tagged = parse_text(tag_host_owned(text, ("shard", "1")))
        assert tagged["photon_obs_unit_total"] == [({}, 3.0)]
        assert tagged["photon_obs_unit_gauge"] == [({"shard": "1"}, 7.0)]


# ---------------------------------------------------------------------------
# cross-host traces
# ---------------------------------------------------------------------------


class TestTraceStitching:
    def test_one_request_yields_one_stitched_tree(self, env, tmp_path):
        path = str(tmp_path / "trace.jsonl")
        closed: list = []  # every record the tracer completes, as it does
        remove = tracing.GLOBAL_TRACER.add_tap(closed.append)
        tracing.GLOBAL_TRACER.configure(path)

        def siblings_in_tree(rid: str) -> bool:
            # the request's root closes last: wait for it, however long the
            # machine takes (the limit is for a root that never closes)
            limit = time.monotonic() + 300.0
            root = None
            while root is None and time.monotonic() < limit:
                root = next((r for r in closed
                             if r.get("name") == "fleet.request"
                             and r.get("request_id") == rid), None)
                time.sleep(0.01)
            assert root is not None, "the request's span never closed"
            fan_out = {r["span_id"] for r in closed
                       if r.get("name") == "fleet.score"
                       and r.get("parent_id") == root["span_id"]}
            return {"primary", "hedge"} <= {
                r.get("kind") for r in closed
                if r.get("name") == "fleet.leg"
                and r.get("parent_id") in fan_out}

        try:
            # The 0.05 ms hedge delay fires a backup beside every primary,
            # and the first answer wins. A leg that answers after the
            # fan-out has closed is re-parented to the root's level
            # (tracing.span_under), so which legs the tree shows is the
            # hosts' race: ask until a primary and a backup both landed
            # inside the fan-out.
            for attempt in range(20):
                rid = f"obs-rid-{attempt}"
                _post(env["fleet"].url + "/score",
                      {"records": env["requests"][:16]},
                      headers={"X-Photon-Request-Id": rid})
                if siblings_in_tree(rid):
                    break
            else:
                pytest.fail("in none of twenty requests did a primary and "
                            "a backup both answer inside the fan-out")
        finally:
            remove()
            tracing.GLOBAL_TRACER.close()
        spans = [json.loads(line) for line in open(path)]
        by_id = {s["span_id"]: s for s in spans
                 if s.get("span_id") is not None}

        roots = [s for s in spans if s.get("name") == "fleet.request"
                 and s.get("request_id") == rid]
        assert len(roots) == 1
        root = roots[0]
        # the ONE request-id-tagged tree: everything reachable from the
        # root (spans opened BEFORE the tracer was configured — e.g. a
        # warm pass's losing hedge leg — may also land in the file, but
        # they are un-reachable from this root and stay out of scope)
        kids: dict = {}
        for s in by_id.values():
            kids.setdefault(s.get("parent_id"), []).append(s)
        in_tree = {root["span_id"]}
        frontier = [root["span_id"]]
        while frontier:
            for child in kids.get(frontier.pop(), []):
                if child["span_id"] not in in_tree:
                    in_tree.add(child["span_id"])
                    frontier.append(child["span_id"])
        tree = [by_id[i] for i in in_tree]

        scores = [s for s in tree if s["name"] == "fleet.score"]
        assert len(scores) == 1
        assert scores[0]["parent_id"] == root["span_id"]

        # every replica attempt is a SIBLING under the one fan-out span
        legs = [s for s in tree if s["name"] == "fleet.leg"]
        assert legs and all(s["parent_id"] == scores[0]["span_id"]
                            for s in legs)
        kinds = {s["kind"] for s in legs}
        assert "primary" in kinds
        assert "hedge" in kinds
        assert {s["shard"] for s in legs} == {"0", "1"}
        # stitching: winning legs carry the HOST-side span id
        assert any(s.get("host_span") is not None for s in legs)

        stages = [s for s in tree if s["name"].startswith("host.")]
        assert stages, "host stage spans must ride the leg summary"
        leg_ids = {s["span_id"] for s in legs}
        for stage in stages:
            assert stage["parent_id"] in leg_ids
            assert stage["name"][len("host."):] in LEG_SUMMARY_STAGES
            assert stage["seconds"] >= 0.0
        # the tree holds the WHOLE story: router fan-out plus at least
        # one stitched host-side stage breakdown per shard
        staged_shards = {by_id[s["parent_id"]]["shard"] for s in stages}
        assert staged_shards == {"0", "1"}


# ---------------------------------------------------------------------------
# SLO burn rate
# ---------------------------------------------------------------------------


class TestSloBurn:
    def _tracker(self, bus):
        return SloBurnTracker(bus, objective_s=0.1, target=0.999)

    def test_regression_fires_within_two_ticks_and_bridges(self):
        bus = EventBus()
        unbind = bridge.bind(bus)
        try:
            before = {w: series_value(
                parse_text(self._render()), "photon_slo_burn_total",
                {"window": w}) for w in ("5m", "1h")}
            events = []
            bus.subscribe(lambda e: events.append(e)
                          if e.name == "slo_burn_alert" else None)
            slo = self._tracker(bus)
            for _ in range(50):
                slo.observe(0.01)
            assert slo.tick(now=0.0) == []  # healthy: no alert
            # the synthetic regression: latencies past the objective
            for _ in range(40):
                slo.observe(0.25)
            alerts = slo.tick(now=10.0)  # second tick — within budget
            assert {a["window"] for a in alerts} == {"5m", "1h"}
            assert all(a["burn_rate"] >= a["threshold"] for a in alerts)
            assert {e.payload["window"] for e in events} == {"5m", "1h"}
            after = {w: series_value(
                parse_text(self._render()), "photon_slo_burn_total",
                {"window": w}) for w in ("5m", "1h")}
            assert after == {w: before[w] + 1 for w in ("5m", "1h")}
        finally:
            unbind()

    @staticmethod
    def _render():
        from photon_ml_tpu.telemetry.prometheus import render

        return render()

    def test_alerts_are_edge_triggered_and_rearm(self):
        bus = EventBus()
        slo = self._tracker(bus)
        for _ in range(40):
            slo.observe(0.25)
        assert {a["window"] for a in slo.tick(now=0.0)} == {"5m", "1h"}
        # still burning: the latch holds, no repeat alert
        for _ in range(40):
            slo.observe(0.25)
        assert slo.tick(now=10.0) == []
        # recovery: the bad fraction dilutes under both thresholds
        for _ in range(100_000):
            slo.observe(0.01)
        assert slo.tick(now=20.0) == []
        assert not any(w["burning"] for w in slo.status())
        # regress again: the re-armed latch fires a fresh alert
        for _ in range(20_000):
            slo.observe(0.25)
        again = slo.tick(now=30.0)
        assert {a["window"] for a in again} == {"5m", "1h"}

    def test_errors_count_as_bad_and_windows_expire(self):
        bus = EventBus()
        slo = SloBurnTracker(bus, objective_s=10.0, target=0.99,
                             windows=(("5m", 300.0, 14.4),))
        for _ in range(40):
            slo.observe(0.001, ok=False)  # fast but FAILED
        assert [a["window"] for a in slo.tick(now=0.0)] == ["5m"]
        # 301 s later the bad bucket has aged out of the window
        assert slo.tick(now=301.0) == []
        assert slo.status()[0]["total"] == 0
        assert not slo.status()[0]["burning"]

    def test_target_validation(self):
        with pytest.raises(ValueError):
            SloBurnTracker(EventBus(), objective_s=1.0, target=1.0)


# ---------------------------------------------------------------------------
# hardening: scrape failures + shard coverage
# ---------------------------------------------------------------------------


class TestHardening:
    PLAN = {"seed": 0, "specs": [{"site": "fleet.fanout", "rate": 1.0}]}

    def test_scrape_failure_serves_partial_fold_with_annotation(self, env):
        router = env["fleet"].router
        snap0 = parse_text(router.metrics_text())
        errs0 = sum(v for _l, v in snap0.get(
            "photon_fleet_scrape_errors_total", []))
        with injected(FaultPlan.from_json(self.PLAN)):
            text = router.metrics_text()  # must NOT raise
        snap = parse_text(text)
        errs = {(labels["shard"], labels["replica"]): v for labels, v
                in snap.get("photon_fleet_scrape_errors_total", [])}
        # every host's scrape faulted: all four annotated, fold served
        assert set(errs) == {("0", "0"), ("0", "1"), ("1", "0"),
                             ("1", "1")}
        assert sum(errs.values()) >= errs0 + 4
        assert series_value(snap, "photon_fleet_hosts") == 4

    def test_readyz_flips_to_shard_uncovered(self, env):
        router = env["fleet"].router
        with injected(FaultPlan.from_json(self.PLAN)):
            status, body = router.readyz()
        assert status == 503
        assert body["reason"] == "shard_uncovered"
        assert body["uncovered_shards"] == [0, 1]
        # recovered: the pooled clients reconnect and coverage returns
        status, body = router.readyz()
        assert status == 200 and body["ready"]
        assert "reason" not in body

    def test_healthz_counts_replicas_per_shard(self, env):
        body = _get(env["fleet"].url + "/healthz")
        assert body["shard_replicas_up"] == [2, 2]


# ---------------------------------------------------------------------------
# /statusz
# ---------------------------------------------------------------------------


class TestStatusz:
    def test_topology_page(self, env):
        fleet = env["fleet"]
        _get_text(fleet.url + "/metrics")  # populate last-scrape bookkeeping
        fleet.router.observer.slo.tick()
        body = _get(fleet.url + "/statusz")
        assert body["status"] == "ok"
        assert body["n_shards"] == 2 and body["replicas"] == 2
        assert body["shard_replicas_up"] == [2, 2]
        assert body["shard_map"]["hash"]
        assert len(body["hosts"]) == 4
        for host in body["hosts"]:
            scrape = host["last_scrape"]
            assert scrape is not None and scrape["ok"]
            assert scrape["age_s"] >= 0.0
        assert [h["shard"] for h in body["shards"]] == [0, 1]
        for heat in body["shards"]:
            assert heat["samples"] > 0 and "p99_s" in heat
        assert isinstance(body["slo"], list) and len(body["slo"]) == 2
        assert {w["window"] for w in body["slo"]} == {"5m", "1h"}
        assert not any(w["burning"] for w in body["slo"])

    def test_shard_heat_gauges_exported(self, env):
        snap = parse_text(env["fleet"].router.metrics_text())
        for name in ("photon_fleet_shard_p50_seconds",
                     "photon_fleet_shard_p99_seconds",
                     "photon_fleet_shard_load"):
            shards = {labels["shard"] for labels, _v in snap.get(name, [])}
            assert {"0", "1"} <= shards, name
        p99 = {labels["shard"]: v for labels, v in
               snap["photon_fleet_shard_p99_seconds"]}
        assert all(v > 0.0 for v in p99.values())


# ---------------------------------------------------------------------------
# parity + steady state with the plane enabled
# ---------------------------------------------------------------------------


class TestPlaneIsFree:
    def test_f32_parity_with_plane_enabled(self, env, tmp_path):
        # tracing on, SLO attached, scrapes interleaved: the plane must
        # not perturb a single bit of the scores
        tracing.GLOBAL_TRACER.configure(str(tmp_path / "t.jsonl"))
        try:
            _get_text(env["fleet"].url + "/metrics")
            fleet_scores = _post(env["fleet"].url + "/score",
                                 {"records": env["requests"]})["scores"]
            _get(env["fleet"].url + "/statusz")
        finally:
            tracing.GLOBAL_TRACER.close()
        single_scores = _post(env["single"].url + "/score",
                              {"records": env["requests"]})["scores"]
        assert fleet_scores == single_scores
        assert all(s == float(np.float32(s)) for s in fleet_scores)

    def test_zero_steady_state_recompiles(self, env, tmp_path):
        fleet = env["fleet"]
        compiles0 = [_get(u + "/healthz")["compiles"]
                     for u in fleet.host_urls()]
        tracing.GLOBAL_TRACER.configure(str(tmp_path / "t2.jsonl"))
        try:
            for _ in range(2):
                _post(fleet.url + "/score",
                      {"records": env["requests"]})
                _post(fleet.url + "/score",
                      {"record": env["requests"][0]})
                _get_text(fleet.url + "/metrics")
                _get(fleet.url + "/statusz")
        finally:
            tracing.GLOBAL_TRACER.close()
        compiles1 = [_get(u + "/healthz")["compiles"]
                     for u in fleet.host_urls()]
        assert compiles1 == compiles0


# ---------------------------------------------------------------------------
# retained telemetry: /history (host + router fold) and /advisor
# ---------------------------------------------------------------------------


class TestRetainedHistory:
    @staticmethod
    def _tick_all(fleet, now):
        """One aligned manual tick everywhere: host rings first, then
        the router ring (whose pre_sample refreshes the heat gauges)."""
        for h in fleet.hosts:
            h.history.sample(now=now)
        fleet.history.sample(now=now)

    def test_host_endpoint_serves_the_ring(self, env):
        host = env["fleet"].hosts[0]
        host.history.sample(now=50.0)
        body = _get(host.url + "/history?series=requests,queue_depth"
                    "&window=1")
        assert body["source"] == "host"
        assert body["series"] == ["requests", "queue_depth"]
        assert len(body["snapshots"]) == 1
        snap = body["snapshots"][0]
        assert set(snap["series"]) == {"requests", "queue_depth"}
        assert "prom" not in snap  # raw text only ships with ?raw=1
        raw = _get(host.url + "/history?window=1&raw=1")
        assert "photon_serving_requests_total" \
            in raw["snapshots"][0]["prom"]

    def test_unknown_series_is_a_400_on_both_tiers(self, env):
        fleet = env["fleet"]
        self._tick_all(fleet, 60.0)
        for url in (fleet.hosts[0].url, fleet.url):
            with pytest.raises(urllib.error.HTTPError) as err:
                _get(url + "/history?series=userId")
            assert err.value.code == 400
            assert "closed" in json.loads(err.value.read())["error"]

    def test_router_fold_matches_offline_metrics_fold(self, env,
                                                      tmp_path):
        import metrics_fold

        fleet = env["fleet"]
        self._tick_all(fleet, 100.0)
        _post(fleet.url + "/score", {"records": env["requests"][:8]})
        self._tick_all(fleet, 101.0)
        body = _get(fleet.url + "/history?raw=1")
        assert body["source"] == "fleet"
        assert len(body["snapshots"]) >= 2
        newest = body["snapshots"][-1]
        assert newest["tick"] == fleet.history.snapshots()[-1]["tick"]
        assert newest["series"]["requests"] > 0  # the traffic landed
        # parity: dump the SAME per-host ring rows the fold consumed and
        # refold them offline with tools/metrics_fold.py — byte-identical
        run_dir = tmp_path / "telemetry"
        (run_dir / "hosts").mkdir(parents=True)
        (run_dir / "metrics.prom").write_text(
            fleet.history.snapshots()[-1]["prom"])
        for s, r, snaps in fleet.router.observer.scrape_history():
            d = run_dir / "hosts" / f"shard-{s}-replica-{r}"
            d.mkdir()
            (d / "metrics.prom").write_text(snaps[-1]["prom"])
        folded = metrics_fold.fold_metrics(str(run_dir))
        assert open(folded).read() == newest["prom"]

    def test_capacity_series_serve_on_both_tiers(self, env):
        """The four capacity series (ISSUE 20) ride the retained ring on
        the host tier AND the router's fold; shard attribution only
        exists on the folded tick (host-tier shard_binding is {})."""
        fleet = env["fleet"]
        self._tick_all(fleet, 150.0)
        q = ("/history?window=1&series=duty_cycle,open_connections,"
             "resource_util,shard_binding")
        host_snap = _get(fleet.hosts[0].url + q)["snapshots"][-1]
        assert host_snap["series"]["shard_binding"] == {}
        assert host_snap["series"]["open_connections"] >= 0.0
        # the host's own USE gauges carry at least the device resource
        assert "device" in host_snap["series"]["resource_util"]
        body = _get(fleet.url + q)
        assert body["source"] == "fleet"
        snap = body["snapshots"][-1]["series"]
        assert set(snap) == {"duty_cycle", "open_connections",
                             "resource_util", "shard_binding"}
        # folded: every shard attributes a binding resource, and the
        # names stay inside the closed vocabulary
        assert set(snap["shard_binding"]) == {"0", "1"}
        assert set(snap["shard_binding"].values()) <= set(RESOURCES)
        assert snap["duty_cycle"] >= 0.0

    def test_advisor_endpoint_rides_the_router_ring(self, env):
        fleet = env["fleet"]
        before = _get(fleet.url + "/advisor")
        fleet.history.sample(now=200.0)  # the sampler listener ticks it
        body = _get(fleet.url + "/advisor")
        assert body["ticks"] == before["ticks"] + 1
        assert body["history_tick"] \
            == fleet.history.snapshots()[-1]["tick"]
        assert body["params"] == {"enter_ratio": 2.0, "exit_ratio": 1.25,
                                  "sustain_ticks": 3}
        assert set(body["shards"]) == {"0", "1"}
        # the warm fleet is balanced: no latch, no advice
        assert body["hot"] == []
        assert body["recommendation"] is None

    def test_plane_stays_free_with_retained_armed(self, env):
        fleet = env["fleet"]
        compiles0 = [_get(u + "/healthz")["compiles"]
                     for u in fleet.host_urls()]
        for i in range(2):
            fleet_scores = _post(fleet.url + "/score",
                                 {"records": env["requests"]})["scores"]
            self._tick_all(fleet, 300.0 + i)
            _get(fleet.url + "/history?window=1")
            _get(fleet.url + "/advisor")
        single_scores = _post(env["single"].url + "/score",
                              {"records": env["requests"]})["scores"]
        assert fleet_scores == single_scores
        assert all(s == float(np.float32(s)) for s in fleet_scores)
        compiles1 = [_get(u + "/healthz")["compiles"]
                     for u in fleet.host_urls()]
        assert compiles1 == compiles0


# ---------------------------------------------------------------------------
# tools/fleet_report.py golden
# ---------------------------------------------------------------------------

REPORT_PROM = """\
# TYPE photon_fleet_hosts gauge
photon_fleet_hosts 4
# TYPE photon_fleet_shardmap_version gauge
photon_fleet_shardmap_version 3
# TYPE photon_fleet_requests_total counter
photon_fleet_requests_total{endpoint="score"} 120
photon_fleet_requests_total{endpoint="metrics"} 2
# TYPE photon_fleet_shard_p50_seconds gauge
photon_fleet_shard_p50_seconds{shard="0"} 0.004
photon_fleet_shard_p50_seconds{shard="1"} 0.0065
# TYPE photon_fleet_shard_p99_seconds gauge
photon_fleet_shard_p99_seconds{shard="0"} 0.012
photon_fleet_shard_p99_seconds{shard="1"} 0.0301
# TYPE photon_fleet_shard_load gauge
photon_fleet_shard_load{shard="0"} 2
photon_fleet_shard_load{shard="1"} 0
# TYPE photon_fleet_fanout_seconds histogram
photon_fleet_fanout_seconds_count{shard="0"} 130
photon_fleet_fanout_seconds_count{shard="1"} 128
# TYPE photon_fleet_hedges_total counter
photon_fleet_hedges_total{shard="0"} 10
# TYPE photon_fleet_hedge_wins_total counter
photon_fleet_hedge_wins_total{shard="0"} 4
# TYPE photon_fleet_replica_retries_total counter
photon_fleet_replica_retries_total{shard="1"} 2
# TYPE photon_fleet_upstream_errors_total counter
photon_fleet_upstream_errors_total{shard="1"} 1
# TYPE photon_fleet_scrape_errors_total counter
photon_fleet_scrape_errors_total{shard="1",replica="0"} 3
# TYPE photon_slo_burn_total counter
photon_slo_burn_total{window="5m"} 2
photon_slo_burn_total{window="1h"} 1
"""

REPORT_STATUSZ = {
    "status": "ok", "n_shards": 2, "replicas": 2,
    "shard_map": {"hash": "deadbeefcafe1234", "version": 3},
    "shard_replicas_up": [2, 1],
    "hosts": [
        {"shard": 0, "replica": 0, "url": "http://127.0.0.1:9000",
         "status": "ok", "last_scrape": {"age_s": 1.0, "ok": True}},
        {"shard": 0, "replica": 1, "url": "http://127.0.0.1:9001",
         "status": "ok", "last_scrape": None},
        {"shard": 1, "replica": 0, "url": "http://127.0.0.1:9002",
         "status": "ok",
         "last_scrape": {"age_s": 2.0, "ok": False, "error": "timeout"}},
    ],
    "slo": [
        {"window": "5m", "burn_rate": 0.0, "threshold": 14.4,
         "burning": False, "bad": 0, "total": 120},
        {"window": "1h", "burn_rate": 7.2, "threshold": 6.0,
         "burning": True, "bad": 12, "total": 120},
    ],
}

REPORT_SPANS = [
    {"name": "fleet.request", "span_id": 1, "parent_id": None,
     "request_id": "r1"},
    {"name": "fleet.score", "span_id": 2, "parent_id": 1},
    {"name": "fleet.leg", "span_id": 3, "parent_id": 2,
     "kind": "primary", "host_span": 77},
    {"name": "fleet.leg", "span_id": 4, "parent_id": 2, "kind": "hedge"},
    {"name": "fleet.leg", "span_id": 5, "parent_id": 2,
     "kind": "retry", "host_span": 81},
    {"name": "host.execute", "span_id": 6, "parent_id": 3,
     "seconds": 0.01},
    {"name": "host.parse", "span_id": 7, "parent_id": 3,
     "seconds": 0.001},
]

EXPECTED_REPORT = """\
== photon fleet report ==
4 host(s); shard map v3; requests: metrics 2, score 120

-- per-shard heat --
shard    p50_ms   p99_ms  load    legs  hedge  won  retry  upstream  scrape_err
0         4.000   12.000     2     130     10    4      0         0           0
1         6.500   30.100     0     128      0    0      2         1           3

-- SLO burn alerts --
1h: 1 alert(s)
5m: 2 alert(s)

-- fan-out traces --
1 fleet.request tree(s); legs: hedge 1, primary 1, retry 1
2 leg(s) stitched to a host span, 2 host stage span(s) attached

-- topology (statusz) --
status ok; 2 shard(s) x 2 replica(s); map deadbeefcafe v3
replicas up per shard: s0=2 s1=1
  s0r0 http://127.0.0.1:9000: ok, scrape ok
  s0r1 http://127.0.0.1:9001: ok, never scraped
  s1r0 http://127.0.0.1:9002: ok, scrape FAILED (timeout)
  slo[5m]: burn 0.0 (threshold 14.4) — ok, 0/120 bad
  slo[1h]: burn 7.2 (threshold 6.0) — BURNING, 12/120 bad
"""


REPORT_HISTORY = {
    "source": "fleet", "capacity": 240,
    "series": ["requests", "shed_rate", "hedge_rate", "latency_p50",
               "latency_p99", "queue_depth", "duty_cycle",
               "open_connections", "slo_burn", "shard_p99"],
    "snapshots": [
        {"tick": 7, "ts": 100.0, "series": {
            "requests": 24.0, "shed_rate": 0.0, "hedge_rate": 0.125,
            "latency_p50": 0.004, "latency_p99": 0.012,
            "queue_depth": 0.0, "duty_cycle": 1.25,
            "open_connections": 6.0, "slo_burn": 0.0,
            "shard_p99": {"0": 0.012, "1": 0.008}}},
        {"tick": 8, "ts": 101.0, "series": {
            "requests": 30.0, "shed_rate": 0.0625, "hedge_rate": 0.1,
            "latency_p50": 0.005, "latency_p99": 0.0301,
            "queue_depth": 2.0, "duty_cycle": 2.75,
            "open_connections": 8.0, "slo_burn": 1.0,
            "shard_p99": {"0": 0.009, "1": 0.0301}}},
    ],
}

REPORT_ADVISOR = {
    "hot": [1], "ticks": 42, "detections": 1, "history_tick": 8,
    "params": {"enter_ratio": 2.0, "exit_ratio": 1.25,
               "sustain_ticks": 3},
    "shards": {
        "0": {"p99_s": 0.009, "p99_ratio": 0.299, "load": 1.0,
              "load_ratio": 0.6667, "skew": 0.6667,
              "binding_resource": "device"},
        "1": {"p99_s": 0.0301, "p99_ratio": 3.3444, "load": 2.0,
              "load_ratio": 1.5, "skew": 3.3444,
              "binding_resource": "batcher_queue"},
    },
    "recommendation": {"kind": "scale_out", "n_shards": 3,
                       "base_version": 3,
                       "base_hash": "deadbeefcafe1234",
                       "n_moves": 1365, "moves_from_hot": 683,
                       "binding_resources": {"1": "batcher_queue"},
                       "moves": {}},
}

EXPECTED_RETAINED_TAIL = """\
-- fleet timeline (last 2 of 2 retained tick(s), source fleet) --
t7 requests=24 shed_rate=0 hedge_rate=0.125 latency_p50=0.004 \
latency_p99=0.012 queue_depth=0 duty_cycle=1.25 open_connections=6 \
slo_burn=0 hottest=s0:12.000ms
t8 requests=30 shed_rate=0.0625 hedge_rate=0.1 latency_p50=0.005 \
latency_p99=0.0301 queue_depth=2 duty_cycle=2.75 open_connections=8 \
slo_burn=1 hottest=s1:30.100ms

-- hot-shard advisor --
hot: s1; 1 detection(s) over 42 tick(s) (enter 2.0x, exit 1.25x, \
sustain 3)
  s0: skew 0.6667x (p99 9.000ms ratio 0.299; load 1.0 ratio 0.6667; \
binding device)
  s1: skew 3.3444x (p99 30.100ms ratio 3.3444; load 2.0 ratio 1.5; \
binding batcher_queue)
advice: scale_out to 3 shard(s) — 1365 bucket move(s), 683 off hot \
shard(s), from map v3 — binding: s1=batcher_queue
"""


class TestFleetReport:
    def test_report_is_a_deterministic_golden(self):
        import fleet_report

        got = fleet_report.build_report(REPORT_PROM, REPORT_STATUSZ,
                                        REPORT_SPANS)
        assert got == EXPECTED_REPORT
        # pure function: same artifacts, same bytes
        assert got == fleet_report.build_report(
            REPORT_PROM, REPORT_STATUSZ, REPORT_SPANS)

    def test_retained_sections_extend_the_golden(self):
        import fleet_report

        got = fleet_report.build_report(REPORT_PROM, REPORT_STATUSZ,
                                        REPORT_SPANS,
                                        history=REPORT_HISTORY,
                                        advisor=REPORT_ADVISOR)
        assert got == EXPECTED_REPORT + "\n" + EXPECTED_RETAINED_TAIL
        # a cool advisor renders advice: none, not a recommendation
        cool = dict(REPORT_ADVISOR, hot=[], recommendation=None)
        got = fleet_report.build_report(REPORT_PROM, advisor=cool)
        assert "hot: (none); 1 detection(s)" in got
        assert "advice: none (fleet is cool)" in got

    def test_sections_degrade_without_optional_artifacts(self):
        import fleet_report

        got = fleet_report.build_report(REPORT_PROM)
        assert "-- per-shard heat --" in got
        assert "-- topology (statusz) --" not in got
        assert "-- fan-out traces --" not in got
        empty = fleet_report.build_report("")
        assert "(no photon_fleet_* series in snapshot)" in empty

    def test_cli_resolves_artifacts(self, tmp_path, capsys):
        import fleet_report

        run_dir = tmp_path / "artifacts"
        run_dir.mkdir()
        (run_dir / "metrics.aggregate.prom").write_text(REPORT_PROM)
        (run_dir / "statusz.json").write_text(json.dumps(REPORT_STATUSZ))
        with open(run_dir / "trace.jsonl", "w") as f:
            for span in REPORT_SPANS:
                f.write(json.dumps(span) + "\n")
            f.write(json.dumps({"name": "note", "span_id": None,
                                "parent_id": 1}) + "\n")  # annotation
        assert fleet_report.main([str(run_dir)]) == 0
        assert capsys.readouterr().out == EXPECTED_REPORT

    def test_cli_resolves_retained_artifacts(self, tmp_path, capsys):
        import fleet_report

        run_dir = tmp_path / "artifacts"
        run_dir.mkdir()
        (run_dir / "metrics.aggregate.prom").write_text(REPORT_PROM)
        (run_dir / "statusz.json").write_text(json.dumps(REPORT_STATUSZ))
        with open(run_dir / "trace.jsonl", "w") as f:
            for span in REPORT_SPANS:
                f.write(json.dumps(span) + "\n")
        (run_dir / "history.json").write_text(json.dumps(REPORT_HISTORY))
        (run_dir / "advisor.json").write_text(json.dumps(REPORT_ADVISOR))
        assert fleet_report.main([str(run_dir)]) == 0
        assert capsys.readouterr().out \
            == EXPECTED_REPORT + "\n" + EXPECTED_RETAINED_TAIL

    def test_cli_errors_without_a_snapshot(self, tmp_path, capsys):
        import fleet_report

        assert fleet_report.main([str(tmp_path)]) == 1
        assert "no metrics snapshot" in capsys.readouterr().err
