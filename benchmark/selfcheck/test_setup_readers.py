"""The four readers of the set-up (``readers/setup_spans.py``,
``readers/setup_compiles.py``): a whole ``--trace 1`` run of every cell here,
each as the first run of its process would be; the warm unit's span against
the warm unit's call around ``cell.unit()``; nothing read from a program that
keeps no totals or no before-profile split, or where the window's count
fails."""

import json
import time
import types

import jax
import pytest

from benchmark import manifest
from benchmark.readers import setup_compiles, setup_spans

SETUP = ("setup_compile_s", "setup_cache_misses", "setup_warm_unit_s",
         "setup_build_s")
CELLS = sorted(w["name"] for w in manifest.benchmark()["workloads"])


@pytest.fixture
def fresh_process(monkeypatch):
    """The tracer's and the listener's process-wide state as a process that
    has run nothing yet holds it: a run is a process of its own."""
    from photon_ml_tpu.telemetry import profiling, tracing

    tracer = tracing.GLOBAL_TRACER
    tracer._ring.clear()
    monkeypatch.setattr(tracer, "_totals", {})
    monkeypatch.setattr(tracer, "_profiled", False)
    monkeypatch.setattr(profiling, "_before_profile",
                        dict.fromkeys(profiling._before_profile, 0))


def _timed_family(monkeypatch) -> list:
    """The cell, then every unit's ``perf_counter`` interval around
    ``cell.unit()``, the warm one first, as ``run.main`` reaches them through
    ``manifest.family``."""
    family, seen = manifest.family, []

    def timed(config):
        module = family(config)

        def setup(*args):
            cell = module.setup(*args)
            unit = cell.unit

            def timed_unit():
                t = time.perf_counter()
                unit()
                seen.append((t, time.perf_counter()))
            cell.unit = timed_unit
            seen.insert(0, cell)
            return cell
        return types.SimpleNamespace(setup=setup)
    monkeypatch.setattr(manifest, "family", timed)
    return seen


def _metric(name):
    spec = manifest.metric_file(name)
    return manifest.reader(spec["reader"]), spec["params"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reads_the_set_up(tiny_cells, fresh_process,
                                       monkeypatch, capsys, cell):
    from benchmark import run, trace

    from photon_ml_tpu.telemetry import profiling, tracing

    chips = manifest.cell(cell)[0]["chips"]
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} (virtual) devices")
    monkeypatch.setattr(trace, "reduce", lambda path, chips: {
        "window_s": 1.0, "busy_s": 0.5, "device_ops": [], "idle_gaps": [],
        "per_chip": [{"busy_s": 0.5, "modules_s": {}, "ops_self_s": {},
                      "collective_s": 0.0}] * chips})
    monkeypatch.setattr(manifest, "peaks", lambda kind: {
        "flops_per_s": 100e12, "hbm_bytes_per_s": 1000e9})
    seen = _timed_family(monkeypatch)
    kept = []
    untap = tracing.GLOBAL_TRACER.add_tap(kept.append)
    try:
        code = run.main(["--workload", cell, "--seed", "2147483659",
                         "--seconds", "0.5", "--trace", "1"],
                        require_tpu=False)
    finally:
        untap()
    assert code == 0
    got = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = {k: v["value"] for k, v in got["metrics"].items()}
    listed = {m["name"] for m in manifest.metrics_of(cell, "per_layer")}
    assert {"setup_compile_s", "setup_cache_misses",
            "setup_warm_unit_s"} <= listed
    for name in set(SETUP) & listed:
        assert name in got, name
    the_cell, (warm_t0, warm_t1) = seen[0], seen[1]
    # the unit's top span, once, inside the warm unit's call: what the host
    # spent there, its compiles and waits included. It returns once the
    # unit's programs are dispatched, so what the device runs after that,
    # and the family's barrier on it, are the unit's wall and not the span's
    spans = {"glm.sweep", "game.fit"}
    (warm,) = [r for r in kept if r["name"] in spans
               and warm_t0 <= r["t0"] < warm_t1]
    assert warm["t1"] <= warm_t1
    assert got["setup_warm_unit_s"] == pytest.approx(warm["seconds"])
    # 0 where this process compiled the cell's programs in an earlier test
    assert got["setup_compile_s"] >= 0.0
    counted = profiling.compiles_before_profile()
    assert got["setup_cache_misses"] \
        == counted["programs"] - counted["cache_hits"] >= 0
    if "setup_build_s" in listed:
        assert 0.0 < got["setup_build_s"]

    # the same window, read again: nothing from a program without the
    # totals or the split, nor where the window's count fails
    state = {"counters": the_cell.counters()}
    for name in set(SETUP) & listed:
        reader, params = _metric(name)
        assert reader.read(state, params) == pytest.approx(got[name])
        assert reader.read({"counters": {"units": 1}}, params) is None
        wrong = {k: (v + 1 if isinstance(v, int) else v)
                 for k, v in state["counters"].items()}
        assert reader.read({"counters": wrong}, params) is None
    with monkeypatch.context() as m:
        m.delattr(tracing, "totals")
        m.delattr(profiling, "compiles_before_profile")
        for name in set(SETUP) & listed:
            reader, params = _metric(name)
            assert reader.read(state, params) is None


def test_the_readers_are_the_files_named():
    for name in SETUP:
        reader, params = _metric(name)
        assert reader in (setup_spans, setup_compiles)
        assert params["windows"]
