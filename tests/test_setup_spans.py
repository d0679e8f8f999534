"""What a process spent before its first profiler session, kept past it
(``telemetry/tracing.py::totals``, ``telemetry/profiling.py``'s listeners):

- every span's count and seconds go to a table by name at its exit, whatever
  listens; with a sink the table equals the records; the ring stays the
  profiled spans' alone, so a span of the set-up never enters it;
- the compile listener counts the persistent cache's misses and hits, and its
  before-profile split leaves out a compile made while a profiler runs or
  after one has run.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

from photon_ml_tpu.telemetry import tracing
from photon_ml_tpu.telemetry.tracing import Tracer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spans(tracer, names):
    for name in names:
        with tracer.span(name, k=1):
            with tracer.span("inner"):
                pass


def test_totals_count_every_span_with_nothing_listening():
    tracer = Tracer()
    assert not tracer._sinking and not tracing._profiler_running()
    _spans(tracer, ["a", "b", "a"])
    totals = tracer.totals()
    assert {n: t["count"] for n, t in totals.items()} \
        == {"a": 2, "b": 1, "inner": 3}
    assert all(t["seconds"] >= 0.0 for t in totals.values())
    # nothing but the table: no record kept, none pending
    assert tracer.recorded() == [] and not tracer._pending


def test_totals_equal_the_records_seconds_under_a_sink(tmp_path):
    tracer = Tracer().configure(str(tmp_path / "trace.jsonl"))
    _spans(tracer, ["a", "b", "a", "a"])
    tracer.close()
    with open(tmp_path / "trace.jsonl") as f:
        records = [json.loads(line) for line in f]
    totals = tracer.totals()
    for name in ("a", "b", "inner"):
        mine = [r["seconds"] for r in records if r["name"] == name]
        assert totals[name]["count"] == len(mine)
        assert totals[name]["seconds"] == pytest.approx(sum(mine), abs=0)
    assert tracer.recorded() == []


def test_a_span_of_the_set_up_never_enters_the_ring(tmp_path):
    """Spans before a profiler session are in the totals only; the ring is
    the session's; totals less the ring's records are the set-up's."""
    tracer = Tracer()
    _spans(tracer, ["setup"])
    with tracer.span("unit"):
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            with tracer.span("unit"):
                pass
    finally:
        jax.profiler.stop_trace()
    with tracer.span("after"):
        pass
    ring = tracer.recorded()
    assert [r["name"] for r in ring] == ["unit"] * 3
    totals = tracer.totals()
    assert totals["unit"]["count"] - len(ring) == 1
    before = totals["unit"]["seconds"] - sum(r["seconds"] for r in ring)
    assert 0.0 <= before <= totals["unit"]["seconds"]
    assert {"setup", "inner", "after"} <= set(totals)
    assert tracer._profiled


_PROBE = r"""
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from photon_ml_tpu.telemetry import metrics, profiling

def cache_events():
    fam = metrics.default_registry().get("photon_xla_cache_events_total")
    return {result: c.value for (result,), c in fam.children()}

def program(x):
    return jnp.tanh(x) @ jnp.cos(x).T + jnp.where(x > 0, x, 0.0).sum()

jax.jit(program)(jnp.ones((16, 16))).block_until_ready()
out = {"setup": profiling.compiles_before_profile(), "events": cache_events()}
jax.profiler.start_trace(sys.argv[2])
jax.jit(lambda x: jnp.exp(x) * 3.0)(jnp.ones(7)).block_until_ready()
jax.profiler.stop_trace()
jax.jit(lambda x: jnp.log1p(x) - 2.0)(jnp.ones(5)).block_until_ready()
out["after"] = profiling.compiles_before_profile()
out["events_after"] = cache_events()
print(json.dumps(out))
"""


def _probe(cache_dir, trace_dir):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": REPO + os.pathsep + os.environ.get("PYTHONPATH", "")}
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", _PROBE, str(cache_dir), str(trace_dir)],
        env=env, capture_output=True, text=True, timeout=300, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_the_listener_counts_a_miss_then_a_hit_and_keeps_set_up_apart(
        tmp_path):
    """Two processes on one persistent cache: the first compiles and writes
    every program (misses), the second loads them (hits). In each, the
    programs compiled under the profiler or after it are in the counter and
    not in the before-profile split."""
    cold = _probe(tmp_path / "cache", tmp_path / "t1")
    warm = _probe(tmp_path / "cache", tmp_path / "t2")
    for run, hits, misses in ((cold, 0, None), (warm, None, 0)):
        setup = run["setup"]
        assert setup["programs"] >= 1 and setup["seconds"] > 0.0
        assert setup["cache_hits"] + setup["cache_misses"] \
            == setup["programs"]
        if hits is not None:
            assert setup["cache_hits"] == hits
            assert setup["cache_misses"] == setup["programs"]
        if misses is not None:
            assert setup["cache_misses"] == misses
            assert setup["cache_hits"] == setup["programs"]
        # the split froze at the profiler's start; the counter did not
        assert run["after"] == setup
        assert sum(run["events_after"].values()) \
            > sum(run["events"].values())
    assert cold["events"].get("miss", 0) == cold["setup"]["programs"]
    assert warm["events"].get("hit", 0) == warm["setup"]["programs"]
